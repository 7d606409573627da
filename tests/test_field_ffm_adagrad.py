"""``avazu_ffm_r16_adagrad``: the fused FieldFFM body under per-coordinate
AdaGrad (``sparse.make_field_ffm_adagrad_body``) against the benchmark's
plain reference (``benchmark/reference/adagrad.py``), at a small size on
the CPU: 5 fields, rank 4, 64 buckets, batch 128, ids WITH duplicates.

- three steps of the fused step against the reference's three: losses,
  touched rows AND accumulators (the chip's check cannot see those);
- one step against ``optim.adagrad_rows`` applied by hand to the
  coalesced gradient of the same batch;
- exact laziness: untouched rows and accumulators keep their bits, and
  so does an FFM row's own diagonal block;
- the accumulators through ``models/rows.py``: held padded as their
  tables are, stepped, and back;
- the same through ``cli train``: the driver's own comparison
  (``benchmark/drivers/train_adagrad.py`` ``compare`` under the limits of
  ``traffic/train_fed_adagrad.json``: what decides the cell's
  ``correct``), ``unique_rows`` on every log line, the loop's hot
  intervals on the branch that carries the slots, the slot gauge;
- save, kill, resume: continues bit-identically, slots restored;
- the six faults the check exists to catch
  (``benchmark/tests/adagrad_faults.py``), each of which must FAIL that
  comparison;
- which optimizers which fused body takes, by name.
"""

import dataclasses
import importlib.util
import json
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark.drivers import train_adagrad  # noqa: E402
from benchmark.reference import adagrad, ffm  # noqa: E402
from fm_spark_tpu import cli, models, obs, optim, sparse  # noqa: E402
from fm_spark_tpu import configs as configs_lib  # noqa: E402
from fm_spark_tpu.models import rows as rows_lib  # noqa: E402
from fm_spark_tpu.ops import scatter  # noqa: E402
from fm_spark_tpu.train import TrainConfig  # noqa: E402

_spec = importlib.util.spec_from_file_location(
    "adagrad_faults",
    os.path.join(ROOT, "benchmark", "tests", "adagrad_faults.py"))
faults = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(faults)

F, K, BUCKET, BATCH = 5, 4, 64, 128
W = F * K + 1
MIX = harness.load_mix("train_fed_adagrad")
STEPS, EARLY_STEPS = int(MIX["check_steps"]), int(MIX["early_steps"])
SEED = 11
# The registry's recipe against a mean over 128 examples, not 8,192: the
# accumulator's start is 1/B^2 as the configuration's is, and the rate is
# cut so that a set of 128 rows is descended, not bounced about.
TINY = dict(bucket=BUCKET, num_fields=F, rank=K, batch_size=BATCH,
            learning_rate=0.05, adagrad_init_accumulator=1.0 / BATCH ** 2)


def spec_and_config(**overrides):
    cfg = dataclasses.replace(
        configs_lib.CONFIGS["avazu_ffm_r16_adagrad"], **TINY)
    return cfg.spec(), cfg.train_config(**overrides)


def one_batch(seed=0, batch=BATCH, hot=8):
    """``hot`` ids take most of the lanes, so every field has duplicates."""
    rng = np.random.default_rng(seed)
    ids = np.where(rng.random((batch, F)) < 0.7,
                   rng.integers(0, hot, (batch, F)),
                   rng.integers(0, BUCKET, (batch, F))).astype(np.int32)
    return (ids, rng.uniform(0.5, 1.5, (batch, F)).astype(np.float32),
            rng.integers(0, 2, batch).astype(np.float32),
            np.ones((batch,), np.float32))


def reference_steps(params, config, batch, steps):
    ids, vals, labels, _ = batch
    uniq, counts, inv, _ = adagrad.touched(ids)
    rows0 = np.stack([np.asarray(params["vw"][f])[uniq[f]]
                      for f in range(F)])
    want = adagrad.train(
        ffm.scores, K, F * K, rows0, inv, vals, labels, steps=steps,
        learning_rate=config.learning_rate, reg_factors=config.reg_factors,
        reg_linear=config.reg_linear, reg_bias=config.reg_bias,
        init_accumulator=config.adagrad_init_accumulator)
    return want, uniq, counts


# ------------------------------------------------------ the step itself


def test_three_fused_steps_match_the_reference_rows_and_slots():
    spec, config = spec_and_config()
    step = sparse.make_field_ffm_adagrad_step(spec, config)
    params = spec.init(jax.random.key(3))
    batch = one_batch()
    want, uniq, counts = reference_steps(params, config, batch, 3)
    assert (counts > 1).any(axis=1).all()           # duplicates everywhere
    slots = step.init_opt_state(params)
    assert all(t.dtype == jnp.float32 and t.shape == (BUCKET, W)
               for t in slots["vw"]["n"])
    for i in range(3):
        params, slots, loss, stats = step(params, slots, jnp.int32(i), *batch)
        assert float(loss) == pytest.approx(want["losses"][i], rel=1e-5)
        assert int(stats["unique_rows"]) == int((counts > 0).sum())
    for f in range(F):
        live = counts[f] > 0
        np.testing.assert_allclose(
            np.asarray(params["vw"][f])[uniq[f]][live],
            want["rows"][f][live], rtol=2e-4, atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(slots["vw"]["n"][f])[uniq[f]][live],
            want["slots"][f][live], rtol=2e-4)
    assert float(params["w0"]) == pytest.approx(want["w0"], rel=1e-4)


def test_one_step_is_adagrad_rows_on_the_coalesced_gradient():
    spec, config = spec_and_config()
    body, init_slots = sparse.make_field_ffm_adagrad_body(spec, config)
    params = spec.init(jax.random.key(4))
    slots = init_slots(params)
    ids, vals, labels, weights = one_batch(seed=1)
    _, _, lr, g_fulls, *_ = sparse._field_ffm_grads(spec, config)(
        params, jnp.int32(0), ids, vals, labels, weights, None)
    new, new_slots, _, _ = jax.jit(body)(params, slots, jnp.int32(0), ids,
                                         vals, labels, weights)
    for f in range(F):
        g_bar = np.zeros((BUCKET, W), np.float32)
        np.add.at(g_bar, ids[:, f], np.asarray(g_fulls[f]))
        rows, n = optim.adagrad_rows(params["vw"][f], slots["vw"]["n"][f],
                                     g_bar, lr)
        np.testing.assert_allclose(np.asarray(new["vw"][f]),
                                   np.asarray(rows), rtol=1e-5, atol=1e-8)
        np.testing.assert_allclose(np.asarray(new_slots["vw"]["n"][f]),
                                   np.asarray(n), rtol=1e-5)
        # (sum g)^2, not sum g^2: a row met twice is told apart.
        twice = np.flatnonzero(np.bincount(ids[:, f], minlength=BUCKET) > 1)
        squares = np.zeros((BUCKET, W), np.float32)
        np.add.at(squares, ids[:, f], np.asarray(g_fulls[f]) ** 2)
        assert not np.allclose(
            np.asarray(new_slots["vw"]["n"][f])[twice],
            (np.asarray(slots["vw"]["n"][f]) + squares)[twice], rtol=1e-3)


@pytest.mark.parametrize("chunk", [16, 32, 128])
def test_chunks_of_lanes_give_what_one_pass_gives(monkeypatch, chunk):
    """The rule walks a field's unique rows ``RULE_CHUNK`` lanes at a
    time, as many chunks as hold them: whatever the chunk, each row and
    its accumulator are read once and written once (to a rounding: the
    compiler contracts a [16, w] rule otherwise than a [128, w] one)."""
    spec, config = spec_and_config()
    batch = one_batch(seed=3)
    unique = max(len(np.unique(batch[0][:, f])) for f in range(F))
    assert -(-unique // 16) >= 3                    # several chunks at 16

    def two_steps():
        step = sparse.make_field_ffm_adagrad_step(spec, config)
        params = spec.init(jax.random.key(8))
        slots = step.init_opt_state(params)
        for i in range(2):
            params, slots, loss, stats = step(params, slots, jnp.int32(i),
                                              *batch)
        return jax.tree.map(np.asarray, (params, slots, loss, stats))

    monkeypatch.setattr(scatter, "RULE_CHUNK", 1 << 30)     # one pass
    want = two_steps()
    monkeypatch.setattr(scatter, "RULE_CHUNK", chunk)
    for a, b in zip(jax.tree.leaves(two_steps()), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=0)


def test_coalesce_sums_each_unique_row_once():
    ids = jnp.asarray([5, 2, 5, 9, 2, 5], jnp.int32)
    delta = jnp.arange(12, dtype=jnp.float32).reshape(6, 2)
    useg, totals, n = scatter.coalesce(ids, delta)
    assert int(n) == 3 and useg[:3].tolist() == [2, 5, 9]
    np.testing.assert_array_equal(
        np.asarray(totals[:3]), [[2 + 8, 3 + 9], [0 + 4 + 10, 1 + 5 + 11],
                                 [6, 7]])
    assert not np.asarray(totals[3:]).any()
    tail = np.asarray(useg[3:])
    assert (np.diff(np.asarray(useg)) > 0).all() and tail.min() > 2 ** 30
    table = jnp.zeros((16, 4), jnp.float32)
    out = scatter.set_rows_at(table, useg, totals + 1.0)
    assert np.asarray(out).nonzero()[0].tolist() == [2, 2, 5, 5, 9, 9]
    assert not np.asarray(out[:, 2:]).any()          # padding stays zero


def test_untouched_rows_slots_and_the_diagonal_block_keep_their_bits():
    spec, config = spec_and_config(reg_factors=0.0)
    step = sparse.make_field_ffm_adagrad_step(spec, config)
    params = spec.init(jax.random.key(5))
    start = jax.tree.map(np.asarray, params)
    slots = step.init_opt_state(params)
    batch = one_batch(seed=2)
    for i in range(2):
        params, slots, _, _ = step(params, slots, jnp.int32(i), *batch)
    for f in range(F):
        met = np.zeros(BUCKET, bool)
        met[batch[0][:, f]] = True
        table, slot = np.asarray(params["vw"][f]), np.asarray(
            slots["vw"]["n"][f])
        np.testing.assert_array_equal(table[~met], start["vw"][f][~met])
        assert (slot[~met] == np.float32(config.adagrad_init_accumulator)
                ).all()
        own = slice(f * K, (f + 1) * K)      # <v_{i,f}, v_{i,f}>: no pair
        np.testing.assert_array_equal(table[:, own], start["vw"][f][:, own])
        assert (slot[:, own] == np.float32(config.adagrad_init_accumulator)
                ).all()
        assert (table[met] != start["vw"][f][met]).any()


def test_slots_are_held_as_their_tables_and_come_back(monkeypatch):
    """With the CPU answering the layout question as the chip does, the
    walk pads tables and slots alike; two placed steps give what two
    unplaced ones give, and the way back cuts the padding off."""
    monkeypatch.setattr(rows_lib, "default_is_row_major",
                        lambda shape, dtype, device: shape[0] <= shape[1])
    spec, config = spec_and_config()
    canonical = spec.init(jax.random.key(6))
    slots0 = optim.init_field_slots("adagrad", canonical, ("vw",),
                                    config.adagrad_init_accumulator)
    step, params, opt, prep, to_canonical, mesh = cli._place_field_state(
        spec, config, cli._FIELD_CAPS["FieldFFMSpec"],
        jax.tree.map(jnp.copy, canonical), jax.tree.map(jnp.copy, slots0),
        1, 1, False, 1, False)
    opt, to_host = cli._hold_slots(opt)
    assert mesh is None
    assert {t.shape for t in params["vw"]} == {(BUCKET, 128)}
    assert {t.shape for t in opt["vw"]["n"]} == {(BUCKET, 128)}
    assert obs.gauge("train/slot_table_bytes").value == F * BUCKET * 128 * 4
    back = to_host(opt)
    for a, b in zip(back["vw"]["n"], slots0["vw"]["n"]):
        assert isinstance(a, np.ndarray)
        np.testing.assert_array_equal(a, np.asarray(b))
    bare = sparse.make_field_ffm_adagrad_step(spec, config)
    want, want_slots = canonical, slots0
    for i in range(2):
        batch = one_batch(seed=10 + i)
        params, opt, loss, stats = step(params, opt, jnp.int32(i),
                                        *prep(batch))
        want, want_slots, want_loss, _ = bare(want, want_slots, jnp.int32(i),
                                              *batch)
        assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    for got, ref in ((to_canonical(params), want),
                     (to_host(opt), want_slots)):
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
            assert a.shape == b.shape
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-9)
    # The padding never took a value.
    assert not any(np.asarray(t[:, W:]).any()
                   for t in params["vw"] + opt["vw"]["n"])


def test_the_update_runs_under_its_named_scopes():
    spec, config = spec_and_config()
    text = sparse.lower_field_sparse_step(spec, config, BATCH).as_text(
        debug_info=True)
    names = set(re.findall(r'"(jit\([^"]*opt/[^"]*)"', text))
    for scope, op in (("opt/coalesce", "sort"), ("opt/gather", "gather"),
                      ("opt/rule", "sqrt"), ("opt/write", "scatter")):
        assert any(scope in n and op in n for n in names), (
            scope, sorted(names)[:8])
    # The forward's and the backward's row traffic is outside them.
    plain = set(re.findall(r'"(jit\([^"]*)"', text)) - names
    assert any("gather" in n for n in plain)


# ------------------------------------------------ which body takes what


@pytest.mark.parametrize("family,optimizer,accepted", [
    ("ffm", "sgd", True), ("ffm", "adagrad", True), ("ffm", "adam", False),
    ("ffm", "ftrl", False), ("fm", "sgd", True), ("fm", "adagrad", False),
    ("fm", "ftrl", False), ("fm", "adam", False),
])
def test_fused_field_bodies_take_their_optimizers_and_refuse_the_rest(
        family, optimizer, accepted):
    """No silent fallback: a table rule a fused field body does not
    implement is refused by name, with what IS implemented and where."""
    config = TrainConfig(optimizer=optimizer)
    if family == "ffm":
        spec = spec_and_config()[0]
        make = (sparse.make_field_ffm_adagrad_body if optimizer == "adagrad"
                else sparse.make_field_ffm_sparse_sgd_body)
    else:
        spec = models.FieldFMSpec(num_features=F * BUCKET, rank=K,
                                  num_fields=F, bucket=BUCKET)
        make = sparse.make_field_sparse_sgd_body
    if accepted:
        assert make(spec, config) is not None
        return
    with pytest.raises(ValueError, match="SGD") as refused:
        make(spec, config)
    assert optimizer in str(refused.value)
    assert "make_field_ffm_adagrad_body" in str(refused.value)


@pytest.mark.parametrize("lever", [
    dict(sparse_update="dedup_sr"), dict(use_pallas=True),
    dict(host_dedup=True, sparse_update="dedup"),
    dict(compact_device=True, compact_cap=32, sparse_update="dedup"),
    dict(fused_embed="auto"),
])
def test_the_adagrad_body_refuses_the_sgd_writes_levers_by_name(lever):
    spec, config = spec_and_config(**lever)
    with pytest.raises(ValueError, match="adagrad") as refused:
        sparse.make_field_ffm_adagrad_body(spec, config)
    assert any(name in str(refused.value) for name in lever)


def test_the_multistep_roll_and_the_sgd_body_refuse_adagrad():
    spec, config = spec_and_config()
    with pytest.raises(ValueError, match="one step a call"):
        sparse.make_field_sparse_multistep(spec, config, 2)
    with pytest.raises(ValueError, match="one step a call"):
        sparse.lower_field_sparse_step(spec, config, BATCH, steps_per_call=2)
    with pytest.raises(ValueError, match="unknown|adagrad"):
        optim.init_field_slots("ftrl", {"vw": []}, ("vw",))


# --------------------------------------------------- through cli train


@pytest.fixture
def tiny(monkeypatch):
    """The registry's entry at the small size, as a cell's context."""
    small = dataclasses.replace(
        configs_lib.CONFIGS["avazu_ffm_r16_adagrad"],
        name="ffm_adagrad_tiny", **TINY)
    monkeypatch.setitem(configs_lib.CONFIGS, small.name, small)
    monkeypatch.setattr(jax, "device_count", lambda *a: 1)
    written = harness.load_cell("ffm_r16_adagrad.train").config
    config = {**written,
              "model": {**written["model"], "rank": K, "num_fields": F,
                        "bucket": BUCKET},
              "training": {**written["training"], "batch_per_chip": BATCH,
                           "learning_rate": small.learning_rate,
                           "adagrad_init_accumulator":
                               small.adagrad_init_accumulator}}
    cell = harness.Cell(name="tiny", chips=1, config=config, mix=dict(MIX),
                        end_to_end=[], per_layer=[])
    ctx = harness.Context(cell=cell, seed=SEED, seconds=0.0,
                          t_start=time.perf_counter(), trace_dir=None)
    return ctx, small


def through_cli(ctx, cfg):
    uniq, counts, inv, vals, labels, unique_rows = train_adagrad.one_batch(
        ctx, 1)
    late, early = train_adagrad.two_runs(ctx, cfg, 1, uniq)
    want, rows0 = train_adagrad.reference_run(ctx, uniq, inv, vals, labels)
    verdict = train_adagrad.compare(
        late, early, want, rows0, counts, steps=STEPS,
        early_steps=EARLY_STEPS,
        learning_rate=ctx.cell.config["training"]["learning_rate"],
        tol=ctx.cell.mix)
    return verdict, late, unique_rows


def test_cli_train_matches_the_reference_from_its_own_init(tiny):
    ctx, cfg = tiny
    t = time.perf_counter()
    verdict, late, unique_rows = through_cli(ctx, cfg)
    assert verdict["ok"], verdict
    # Well inside every limit, not just under it: the early run element
    # by element, the late run as a whole.
    for run in ("early", "late"):
        assert verdict[run]["loss_rel_err"] < 1e-5
        assert all(d < 1e-4 for d in verdict[run]["rows_distance"].values())
    assert all(b["over_allowed"] < 0.5
               for b in verdict["early"]["rows"].values()), verdict
    # What the step's coalescing counted is the benchmark's own count.
    assert late["unique_rows"] == [float(unique_rows)] * STEPS
    # The loop's hot intervals on the branch that carries the slots: one
    # train/step per step, each with its four parts at the log cadence (1
    # here), and the producer's batches with their placement.
    records = [iv for iv in obs.intervals() if iv.t0 >= t]
    steps = [iv for iv in records if iv.name == "train/step"]
    assert ([iv.attrs["step"] for iv in steps]
            == list(range(STEPS)) + list(range(EARLY_STEPS)))
    for parent in steps:
        kids = [iv.name for iv in records if iv.parent_id == parent.span_id]
        assert kids == ["train/next_batch", "train/prep", "train/dispatch",
                        "train/loss_fetch"], kids
    for name in ("feed/produce", "feed/place"):
        assert (sum(iv.name == name for iv in records)
                >= STEPS + EARLY_STEPS), name
    assert obs.gauge("train/slot_table_bytes").value == F * BUCKET * W * 4


def test_the_whole_check_holds_the_counter_too(tiny, monkeypatch):
    ctx, cfg = tiny
    assert train_adagrad.check_against_reference(ctx, cfg, 1)["ok"]
    # A step that counts another number of unique rows is not correct.
    real = scatter.coalesce
    monkeypatch.setattr(
        scatter, "coalesce",
        lambda ids, delta: (lambda u, t, n: (u, t, n + 1))(
            *real(ids, delta)))
    assert not train_adagrad.check_against_reference(ctx, cfg, 1)["ok"]


def test_a_reference_from_another_seed_fails(tiny):
    ctx, cfg = tiny
    uniq, counts, inv, vals, labels, _ = train_adagrad.one_batch(ctx, 1)
    late, early = train_adagrad.two_runs(ctx, cfg, 1, uniq)
    other = dataclasses.replace(ctx, seed=SEED + 1)
    want, rows0 = train_adagrad.reference_run(other, uniq, inv, vals, labels)
    assert not train_adagrad.compare(
        late, early, want, rows0, counts, steps=STEPS,
        early_steps=EARLY_STEPS, learning_rate=cfg.learning_rate,
        tol=ctx.cell.mix)["ok"]


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_the_check_catches(tiny, fault):
    ctx, cfg = tiny
    with faults.FAULTS[fault](cfg.name):
        verdict, _, _ = through_cli(ctx, configs_lib.CONFIGS[cfg.name])
    assert not verdict["ok"], (fault, verdict)


def test_the_reference_one_precision_lower_fails(tiny):
    ctx, cfg = tiny
    uniq, counts, inv, vals, labels, _ = train_adagrad.one_batch(ctx, 1)
    late, early = train_adagrad.two_runs(ctx, cfg, 1, uniq)
    low, rows0 = train_adagrad.reference_run(ctx, uniq, inv, vals, labels,
                                             compute_dtype="bfloat16")
    assert not train_adagrad.compare(
        late, early, low, rows0, counts, steps=STEPS,
        early_steps=EARLY_STEPS, learning_rate=cfg.learning_rate,
        tol=ctx.cell.mix)["ok"]


def test_the_limits_and_the_update_are_the_files(tiny):
    ctx, _ = tiny
    assert MIX["driver"] == "train_adagrad" and MIX["like"] == "train_fed"
    for key in ("loss_rtol", "rows_rtol", "w0_rates", "rows_distance"):
        assert 0 < MIX[key] <= 1e-3, key
    assert 1 <= MIX["rows_ulps_per_occurrence"] <= 64
    # The late run is held as a whole, far under what another rule reads
    # (a distance of 1, a loss off by its own size).
    for key in ("loss_rtol_late", "rows_distance_late"):
        assert MIX["loss_rtol"] < MIX[key] <= 0.05, key
    assert MIX["rows_distance"] < MIX["rows_distance_late"]
    assert MIX["w0_rates"] < MIX["w0_rates_late"] <= 1e-2
    assert 2 <= EARLY_STEPS < STEPS
    fed = harness.load_mix("train_fed")
    same = set(fed) - {"driver", "what", "rows_rtol", "loss_rtol",
                       "rehearsal"}
    assert all(MIX[k] == fed[k] for k in same)
    assert ctx.cell.config["update"] == train_adagrad.UPDATE
    other = dataclasses.replace(ctx, cell=dataclasses.replace(
        ctx.cell, config={**ctx.cell.config,
                          "update": {**train_adagrad.UPDATE,
                                     "duplicates": "each_occurrence"}}))
    with pytest.raises(SystemExit, match="update"):
        train_adagrad.hold_update(other)
    written = harness.load_cell("ffm_r16_adagrad.train").config
    sgd = harness.load_cell("ffm_r16.train").config
    assert written["model"] == sgd["model"]          # the same tables
    assert written["training"]["adagrad_init_accumulator"] == 2.0 ** -26


def argv(cfg, steps, *more):
    return ["train", "--config", cfg.name, "--synthetic", str(4 * BATCH),
            "--batch-per-chip", str(BATCH), "--seed", str(SEED),
            "--steps", str(steps), "--log-every", "1", "--obs-dir", "none",
            "--test-fraction", "0", *more]


def run_cli(capsys, *args):
    assert cli.main(list(args)) == 0
    return [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith('{"step"')]


def test_save_kill_resume_continues_bit_identically(tiny, tmp_path, capsys):
    _, cfg = tiny
    saved = {}
    real_save = models.save_model

    def keep(path, spec, params):
        saved[os.path.basename(path)] = jax.tree.map(np.asarray, params)

    models.save_model = keep
    try:
        golden = run_cli(capsys, *argv(cfg, 6, "--model-out", "golden"))
        chain = str(tmp_path / "chain")
        first = run_cli(capsys, *argv(
            cfg, 3, "--checkpoint-dir", chain, "--checkpoint-every", "3"))
        rest = run_cli(capsys, *argv(
            cfg, 6, "--checkpoint-dir", chain, "--checkpoint-every", "3",
            "--model-out", "resumed"))
    finally:
        models.save_model = real_save
    assert [d["step"] for d in first + rest] == [1, 2, 3, 4, 5, 6]
    assert ([d["loss"] for d in first + rest]
            == [d["loss"] for d in golden])
    for a, b in zip(jax.tree.leaves(saved["resumed"]),
                    jax.tree.leaves(saved["golden"])):
        np.testing.assert_array_equal(a, b)
    # The chain holds the slots beside the tables, canonical and float32.
    from fm_spark_tpu.checkpoint import Checkpointer

    spec = cfg.spec()
    canonical = jax.eval_shape(spec.init, jax.random.key(0))
    slots = jax.eval_shape(
        lambda p: optim.init_field_slots("adagrad", p, ("vw",), 0.0),
        canonical)
    restored = Checkpointer(chain).restore(canonical, slots)
    assert restored["step"] == 6
    got = restored["opt_state"]["vw"]["n"]
    assert len(got) == F and all(
        t.shape == (BUCKET, W) and t.dtype == np.float32 for t in got)
    assert any((np.asarray(t) > cfg.adagrad_init_accumulator).any()
               for t in got)


@pytest.mark.parametrize("more,why", [
    (("--steps-per-call", "2"), "one step a call"),
    (("--sparse-update", "dedup_sr"), "dedup_sr"),
])
def test_cli_refuses_what_the_slots_cannot_ride(tiny, more, why):
    _, cfg = tiny
    with pytest.raises((SystemExit, ValueError), match=why):
        cli.main(argv(cfg, 2, *more))


def test_cli_refuses_adagrad_on_a_mesh(tiny, monkeypatch):
    _, cfg = tiny
    monkeypatch.setattr(jax, "device_count", lambda *a: 4)
    with pytest.raises(SystemExit, match="runs on one chip"):
        cli.main(argv(cfg, 2))
