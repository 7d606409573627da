"""``criteo1tb_dlrm_mlperf``: the fused FieldDLRM body
(``sparse.make_field_dlrm_sparse_body``, ``models/field_dlrm.py``) against
the benchmark's plain reference (``benchmark/reference/dlrm.py``), at a
small size on the CPU: 3 dense and 5 categorical columns, rows 8 wide, 48
buckets, stacks 3-16-8 and 23-16-16-1, batch 64, ids WITH duplicates.

- ``scores`` against float64 sums written pair by pair in NumPy;
- eight fused steps against ``jax.grad`` of the written-out loss with
  plain SGD on everything; untouched rows keep their bits;
- the same through ``cli train --synthetic``: the driver's own comparison
  (``benchmark/drivers/train_dlrm.py`` ``compare`` under the limits of
  ``traffic/train_fed_dlrm.json``: what decides the cell's ``correct``),
  the loop's six spans, the gauges, the ``dlrm/*`` scopes in the lowered
  text;
- ``cli eval`` / ``cli predict`` and ``PredictEngine`` return
  ``spec.scores``; a saved model loads; a Criteo text file loads its
  integer columns as values;
- ``rows.held_form`` answers ``as_is`` at width 128; precision by
  ``compute_dtype``;
- the nine faults the check exists to catch
  (``benchmark/tests/dlrm_faults.py``) and the reference one precision
  lower, each of which must FAIL that comparison;
- what the capability row and the body refuse, by name.
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

from benchmark import dlrm_flops, harness  # noqa: E402
from benchmark.drivers import train_dlrm  # noqa: E402
from benchmark.reference import dlrm, sgd  # noqa: E402
from fm_spark_tpu import cli, models, obs, sparse  # noqa: E402
from fm_spark_tpu import configs as configs_lib  # noqa: E402
from fm_spark_tpu.data import criteo  # noqa: E402
from fm_spark_tpu.models import rows as rows_lib  # noqa: E402
from fm_spark_tpu.train import TrainConfig  # noqa: E402

_spec = importlib.util.spec_from_file_location(
    "dlrm_faults",
    os.path.join(ROOT, "benchmark", "tests", "dlrm_faults.py"))
faults = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(faults)

D, C, K, BUCKET, BATCH = 3, 5, 8, 48, 64
F = D + C
BOTTOM, TOP = (16, 8), (16, 16)
MIX = harness.load_mix("train_fed_dlrm")
STEPS, EARLY_STEPS = int(MIX["check_steps"]), int(MIX["early_steps"])
SEED = 11
TINY = dict(rank=K, num_fields=F, dense_fields=D, bottom_mlp_dims=BOTTOM,
            mlp_dims=TOP, bucket=BUCKET, batch_size=BATCH)
REGISTERED = configs_lib.CONFIGS["criteo1tb_dlrm_mlperf"]


def spec_and_config(**overrides):
    cfg = dataclasses.replace(REGISTERED, **TINY)
    return cfg.spec(), cfg.train_config(**overrides)


def one_batch(seed=0, batch=BATCH, hot=6):
    """``hot`` ids take most of the lanes, so every field has duplicates;
    the dense slots carry counts' logs, zeros among them."""
    rng = np.random.default_rng(seed)
    ids = np.where(rng.random((batch, F)) < 0.7,
                   rng.integers(0, hot, (batch, F)),
                   rng.integers(0, BUCKET, (batch, F))).astype(np.int32)
    vals = np.ones((batch, F), np.float32)
    vals[:, :D] = np.log1p(rng.integers(0, 9, (batch, D)))
    return (ids, vals, rng.integers(0, 2, batch).astype(np.float32),
            np.ones((batch,), np.float32))


# ---------------------------------------------------- the model itself


def test_scores_are_the_float64_sums_pair_by_pair():
    spec, _ = spec_and_config()
    params = spec.init(jax.random.key(1))
    ids, vals, _, _ = one_batch(seed=2)
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params)

    def stack(layers, a, relu_last):
        for li, layer in enumerate(layers):
            a = a @ layer["kernel"] + layer["bias"]
            if relu_last or li < len(layers) - 1:
                a = np.maximum(a, 0.0)
        return a

    z = stack(p["bottom"], vals[:, :D].astype(np.float64), True)
    t = [z] + [p["vw"][j][ids[:, D + j]] for j in range(C)]
    pairs = [(t[i] * t[j]).sum(1) for i in range(1, C + 1) for j in range(i)]
    assert len(pairs) == spec.pairs == 15
    want = stack(p["mlp"], np.concatenate([z, np.stack(pairs, 1)], 1),
                 False)[:, 0]
    got = np.asarray(spec.scores(params, jnp.asarray(ids), jnp.asarray(vals)))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(
        np.asarray(spec.predict(params, jnp.asarray(ids), jnp.asarray(vals))),
        1.0 / (1.0 + np.exp(-want)), rtol=2e-5)
    # The categorical slots' values and the dense slots' ids are not read.
    other = np.asarray(spec.scores(
        params, jnp.asarray(ids).at[:, :D].set(7),
        jnp.asarray(vals).at[:, D:].set(3.0)))
    np.testing.assert_array_equal(other, got)
    with pytest.raises(ValueError, match="slots"):
        spec.scores(params, jnp.asarray(ids[:, 1:]), jnp.asarray(vals[:, 1:]))


def reference_steps(params, config, batch, steps):
    ids, vals, labels, _ = batch
    uniq, counts, inv, _ = sgd.touched(ids[:, D:])
    start = {"rows": np.stack([np.asarray(params["vw"][j])[uniq[j]]
                               for j in range(C)]),
             **{s: jax.tree.map(np.asarray, params[s])
                for s in train_dlrm.STACKS}}
    want = dlrm.train(start["rows"], {s: start[s] for s in train_dlrm.STACKS},
                      inv, vals[:, :D], labels, steps=steps,
                      learning_rate=config.learning_rate)
    return want, start, uniq, counts


def test_eight_fused_steps_match_jax_grad_of_the_written_out_loss():
    spec, config = spec_and_config()
    step = sparse.make_field_dlrm_sparse_step(spec, config)
    params = spec.init(jax.random.key(3))
    first = jax.tree.map(np.asarray, params)
    batch = one_batch()
    want, start, uniq, counts = reference_steps(params, config, batch, 8)
    assert (counts > 1).any(axis=1).all()           # rows that repeat
    opt = step.init_opt_state(params)
    assert jax.tree.leaves(opt) == []               # plain SGD: no state
    for i in range(8):
        params, opt, loss = step(params, opt, jnp.int32(i), *batch)
        assert float(loss) == pytest.approx(want["losses"][i], rel=1e-5)
    got = {**train_dlrm.taken(params, uniq), "losses": want["losses"]}
    verdict = train_dlrm.readings(got, want, start, counts, steps=8,
                                  rows_rtol=MIX["rows_rtol"])
    assert verdict["dense_distance_max"] < 1e-4 > verdict["rows"]["distance"]
    assert verdict["rows"]["over_allowed"] < 0.5
    # Exact laziness: a row the batch did not meet keeps its bits.
    for j in range(C):
        met = np.zeros(BUCKET, bool)
        met[batch[0][:, D + j]] = True
        table = np.asarray(params["vw"][j])
        np.testing.assert_array_equal(table[~met], first["vw"][j][~met])
        assert (table[met] != first["vw"][j][met]).any()


def test_weight_zero_rows_count_for_nothing():
    spec, config = spec_and_config()
    step = sparse.make_field_dlrm_sparse_step(spec, config)
    ids, vals, labels, weights = one_batch(seed=4)
    weights[BATCH // 2:] = 0.0
    params = spec.init(jax.random.key(5))
    half = tuple(a[:BATCH // 2] for a in (ids, vals, labels, weights))
    opt = step.init_opt_state(params)
    a, _, loss_a = step(jax.tree.map(jnp.copy, params), opt, jnp.int32(0),
                        ids, vals, labels, weights)
    b, _, loss_b = jax.jit(sparse.make_field_dlrm_sparse_body(spec, config)[0]
                           )(params, opt, jnp.int32(0), *half)
    assert float(loss_a) == pytest.approx(float(loss_b), rel=1e-6)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-5,
                                   atol=1e-8)


SCOPES = [("jvp(dlrm/bottom)", "dot_general", False),
          ("jvp(dlrm/interact)", "dot_general", False),
          ("jvp(dlrm/interact)", "concatenate", False),
          ("jvp(dlrm/top)", "dot_general", False),
          ("transpose(jvp(dlrm/bottom))", "dot_general", True),
          ("transpose(jvp(dlrm/interact))", "dot_general", True),
          ("transpose(jvp(dlrm/top))", "dot_general", True),
          ("dlrm/sgd", "mul", False)]


@pytest.fixture(scope="module")
def lowered_names():
    spec, config = spec_and_config()
    text = sparse.lower_field_sparse_step(spec, config, BATCH).as_text(
        debug_info=True)
    return set(re.findall(r'"(jit\([^"]*)"', text))


@pytest.mark.parametrize("scope,op,backward", SCOPES)
def test_the_step_runs_under_its_named_scopes(lowered_names, scope, op,
                                              backward):
    hits = [n for n in lowered_names if scope in n and n.endswith(op)
            and ("transpose(" in n) == backward]
    assert hits, (scope, sorted(lowered_names)[:10])
    # The pullback's ops, and only they, stand under dlrm/backward: what
    # tells the six apart in a trace (benchmark/dlrm_trace.py).
    assert all(("dlrm/backward" in n) == backward for n in hits)
    # The row traffic is outside them: the gathers and the writes.
    plain = {n for n in lowered_names if "dlrm/" not in n}
    assert any("gather" in n for n in plain)
    assert any("scatter" in n for n in plain)


@pytest.mark.parametrize("compute_dtype,precision", [
    ("float32", "HIGHEST"), ("bfloat16", "DEFAULT")])
def test_every_product_takes_the_precision_compute_dtype_declares(
        compute_dtype, precision):
    cfg = dataclasses.replace(REGISTERED, **TINY,
                              compute_dtype=compute_dtype)
    text = sparse.lower_field_sparse_step(cfg.spec(), cfg.train_config(),
                                          BATCH).as_text()
    stated = [re.search(r"precision = \[(\w+), (\w+)\]", line)
              for line in text.splitlines() if "dot_general" in line]
    # Five layers: forward, kernel gradient, input gradient but the
    # first layer's; the interaction's three.
    assert len(stated) == 3 * 5 - 1 + 3
    assert all(m and set(m.groups()) == {precision} for m in stated)


def test_a_128_wide_table_is_held_as_it_is(monkeypatch):
    """Whatever the device says of a narrow table, a row that IS a lane
    tile neither pads nor packs, for the loop and for the scorer."""
    monkeypatch.setattr(rows_lib, "default_is_row_major",
                        lambda shape, dtype, device: False)
    device = jax.devices()[0]
    for writes in (True, False):
        assert rows_lib.held_form((1 << 19, 128), jnp.float32, device,
                                  writes) == "as_is"
    assert rows_lib.held_form((1 << 19, 17), jnp.float32, device,
                              True) == "padded"
    spec = REGISTERED.spec()
    assert (spec.table_width, spec.num_tables, spec.pairs) == (128, 26, 351)
    assert spec.bottom_dims == (13, 512, 256, 128)
    assert spec.top_dims == (479, 1024, 1024, 512, 256, 1)
    shapes = jax.eval_shape(spec.init, jax.random.key(0))
    assert set(shapes) == {"vw", "bottom", "mlp"}        # no bias, no linear
    _, _, report = rows_lib.hold(shapes, sparse.FUSED_TABLE_KEYS, writes=True)
    assert report["tables_as_is"] == 26 and report["tables_padded"] == 0
    assert report["resident_table_bytes"] == pytest.approx(
        26 * (1 << 19) * 128 * 4, rel=2e-3)


@pytest.mark.parametrize("batch,bottom,top,tables,rank,want", [
    # 6 x (3x16 + 16x8 + 23x16 + 16x16 + 16) - 2 x 48 + 4 x 36 x 8
    (64, (3, 16, 8), (23, 16, 16, 1), 5, 8,
     64 * (6 * 816 - 96 + 1152)),
    (55296, (13, 512, 256, 128), (479, 1024, 1024, 512, 256, 1), 26, 128,
     55296 * (6 * 2365184 - 2 * 6656 + 4 * 27 * 27 * 128)),
])
def test_the_programs_count_of_its_products_is_the_benchmarks(
        batch, bottom, top, tables, rank, want):
    assert dlrm_flops.step_matmul_flops(batch, bottom, top, tables,
                                        rank) == want
    spec = (spec_and_config()[0] if rank == K else REGISTERED.spec())
    assert spec.mxu_flops_per_step(batch) == want


def test_the_initial_values_are_the_sources():
    spec, _ = spec_and_config()
    params = spec.init(jax.random.key(7))
    bound = (1.0 / BUCKET) ** 0.5
    table = np.asarray(params["vw"][0])
    assert table.shape == (BUCKET, K) and np.abs(table).max() <= bound
    assert np.abs(table).max() > 0.9 * bound
    wide = REGISTERED.spec()
    top = jax.eval_shape(wide.init, jax.random.key(0))["mlp"]
    assert [layer["kernel"].shape for layer in top] == [
        (479, 1024), (1024, 1024), (1024, 512), (512, 256), (256, 1)]
    # The reference starts where the program starts.
    uniq = np.arange(BUCKET)[None].repeat(C, 0)
    np.testing.assert_array_equal(
        np.asarray(dlrm.init_rows(7, uniq, BUCKET, K)),
        np.stack([np.asarray(t) for t in params["vw"]]))
    mirror = dlrm.init_dense(7, spec.bottom_dims, spec.top_dims)
    for stack in train_dlrm.STACKS:
        for a, b in zip(jax.tree.leaves(mirror[stack]),
                        jax.tree.leaves(params[stack])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------- through cli train


@pytest.fixture
def tiny(monkeypatch):
    """The registry's entry at the small size, as a cell's context."""
    small = dataclasses.replace(REGISTERED, name="dlrm_tiny", **TINY)
    monkeypatch.setitem(configs_lib.CONFIGS, small.name, small)
    monkeypatch.setattr(jax, "device_count", lambda *a: 1)
    monkeypatch.setattr(dlrm, "DENSE_COLUMNS", D)
    written = harness.load_cell("dlrm_e128.train").config
    config = {**written, "registry": small.name,
              "model": {**written["model"], "rank": K, "num_fields": F,
                        "dense_fields": D, "bucket": BUCKET},
              "stacks": {**written["stacks"], "bottom_mlp_dims": list(BOTTOM),
                         "mlp_dims": list(TOP), "pairs": 15},
              "training": {**written["training"], "batch_per_chip": BATCH}}
    cell = harness.Cell(name="tiny", chips=1, config=config, mix=dict(MIX),
                        end_to_end=[], per_layer=[])
    ctx = harness.Context(cell=cell, seed=SEED, seconds=0.0,
                          t_start=time.perf_counter(), trace_dir=None)
    return ctx, small


def through_cli(ctx, cfg, precision="float32"):
    uniq, counts, inv, x, labels = train_dlrm.one_batch(ctx, 1)
    runs = train_dlrm.two_runs(ctx, cfg, 1, uniq)
    want, start = train_dlrm.reference_run(ctx, uniq, inv, x, labels,
                                           precision=precision)
    return train_dlrm.compare(*runs, want, start, counts, steps=STEPS,
                              early_steps=EARLY_STEPS, tol=ctx.cell.mix)


def test_cli_train_matches_the_reference_from_its_own_init(tiny):
    ctx, cfg = tiny
    assert train_dlrm.hold_program(ctx).name == cfg.name
    assert train_dlrm.traffic_matches(SEED, F)
    t = time.perf_counter()
    verdict = through_cli(ctx, cfg)
    assert verdict["ok"], verdict
    # Well inside every limit, not just under it.
    for run in ("early", "late"):
        assert verdict[run]["loss_rel_err"] < 1e-5
        assert verdict[run]["dense_distance_max"] < 1e-4
        assert verdict[run]["rows"]["distance"] < 1e-4
    assert verdict["early"]["rows"]["over_allowed"] < 0.1
    assert verdict["early"]["rows"]["row_distance_median"] < (
        0.1 * MIX["row_distance_median"])
    # The loop's hot intervals on this path: one train/step per step, each
    # with its four parts at the log cadence (1 here), and the producer's
    # batches with their placement.
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
    # What the loop said of itself.
    assert obs.gauge("train/dense_fields").value == D
    assert obs.gauge("train/mxu_flops_per_step").value == (
        dlrm_flops.step_matmul_flops(BATCH, (D, *BOTTOM), (K + 15, *TOP, 1),
                                     C, K))
    assert obs.gauge("train/update_lanes_per_field").value == BATCH


def engage_the_coalesced_write(monkeypatch, clause="rows"):
    """``ops/scatter``'s constants cut to the tiny batch and table, so
    that 64 lanes into 48 rows coalesce in chunks of 16 through one of
    ``update_lanes``' clauses: more lanes than the lane clause takes and
    a table with rows enough a lane (the cell's case: 55,296 lanes,
    under an eighth of its table's rows), or the lane clause alone."""
    from fm_spark_tpu.ops import scatter

    monkeypatch.setattr(scatter, "RULE_CHUNK", BATCH // 4)
    monkeypatch.setattr(scatter, "COALESCE_MAX_LANES",
                        BATCH // 2 if clause == "rows" else BATCH)
    monkeypatch.setattr(scatter, "PLAIN_DEAR_ROWS_PER_LANE",
                        BUCKET / BATCH if clause == "rows" else BUCKET)
    assert scatter.update_lanes(BATCH, (BUCKET, K)) == BATCH // 4


@pytest.mark.parametrize("clause", ["rows", "lanes"])
def test_the_coalesced_write_passes_the_cells_check(tiny, monkeypatch,
                                                    clause):
    """The driver's comparison and the gauge read the coalesced write."""
    ctx, cfg = tiny
    engage_the_coalesced_write(monkeypatch, clause)
    obs.gauge("train/update_lanes_per_field").set(-1)
    verdict = through_cli(ctx, cfg)
    assert verdict["ok"], verdict
    assert verdict["early"]["rows"]["over_allowed"] < 0.1
    assert verdict["early"]["rows"]["row_distance_median"] < (
        0.1 * MIX["row_distance_median"])
    assert obs.gauge("train/update_lanes_per_field").value == BATCH // 4


def test_a_reference_from_another_seed_fails(tiny):
    ctx, cfg = tiny
    uniq, counts, inv, x, labels = train_dlrm.one_batch(ctx, 1)
    runs = train_dlrm.two_runs(ctx, cfg, 1, uniq)
    other = dataclasses.replace(ctx, seed=SEED + 1)
    want, start = train_dlrm.reference_run(other, uniq, inv, x, labels)
    assert not train_dlrm.compare(*runs, want, start, counts, steps=STEPS,
                                  early_steps=EARLY_STEPS,
                                  tol=ctx.cell.mix)["ok"]


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_the_check_catches(tiny, fault):
    ctx, cfg = tiny
    with faults.FAULTS[fault](cfg.name):
        verdict = through_cli(ctx, configs_lib.CONFIGS[cfg.name])
    assert not verdict["ok"], (fault, verdict)


def test_the_check_catches_bfloat16_tables_under_the_coalesced_write(
        tiny, monkeypatch):
    """One rounded SUM a row is nearer float32 than a rounded term an
    occurrence, and is still not float32: the fault must read so with the
    write the cell's batch takes."""
    ctx, cfg = tiny
    engage_the_coalesced_write(monkeypatch)
    with faults.bf16_tables(cfg.name):
        verdict = through_cli(ctx, configs_lib.CONFIGS[cfg.name])
    assert obs.gauge("train/update_lanes_per_field").value == BATCH // 4
    assert not verdict["ok"], verdict


def test_the_reference_one_precision_lower_fails(tiny):
    ctx, cfg = tiny
    assert not through_cli(ctx, cfg, precision="bfloat16")["ok"]


def test_a_program_that_is_not_the_files_is_refused_before_any_work(
        tiny, monkeypatch):
    ctx, cfg = tiny
    from fm_spark_tpu.models.field_dlrm import FieldDLRMSpec

    with monkeypatch.context() as m:
        m.setattr(FieldDLRMSpec, "_precision", None)
        with pytest.raises(SystemExit, match="precision HIGHEST"):
            train_dlrm.hold_program(ctx)
    with monkeypatch.context() as m:
        m.setitem(configs_lib.CONFIGS, cfg.name,
                  dataclasses.replace(cfg, mlp_dims=(16, 8)))
        with pytest.raises(SystemExit, match="stacks"):
            train_dlrm.hold_program(ctx)
    with monkeypatch.context() as m:
        m.delitem(configs_lib.CONFIGS, cfg.name)
        with pytest.raises(SystemExit, match="no such configuration"):
            train_dlrm.hold_program(ctx)
    with monkeypatch.context() as m:
        m.setattr(rows_lib, "_pad_lanes",
                  lambda t: jnp.pad(t, ((0, 0), (0, 8))))
        m.setattr(rows_lib, "held_form", lambda *a, **k: "padded")
        m.setitem(rows_lib._FORMERS, "padded", rows_lib._pad_lanes)
        with pytest.raises(SystemExit, match="as they are"):
            train_dlrm.hold_program(ctx)


def test_the_limits_are_the_files():
    assert MIX["driver"] == "train_dlrm" and MIX["like"] == "train_fed"
    assert 0 < MIX["row_distance_median"] <= MIX["loss_rtol"] <= 1e-4
    for key in ("dense_distance", "rows_distance", "rows_rtol"):
        assert 0 < MIX[key] <= 0.05, key
    # The late run is held as a whole, far under what another rule reads.
    for key in ("dense_distance", "rows_distance"):
        assert MIX[key] < MIX[key + "_late"] <= 0.3, key
    assert 2 <= EARLY_STEPS < STEPS
    fed = harness.load_mix("train_fed")
    same = set(fed) - {"driver", "what", "rows_rtol", "loss_rtol",
                       "rehearsal"}
    assert all(MIX[k] == fed[k] for k in same)
    cell = harness.load_cell("dlrm_e128.train")
    assert cell.mix["rows"] == 10 * 55296            # no padded tail
    written = cell.config
    assert written["reduced"] == ["bucket"]
    assert written["model"]["rank"] == 128 and written["stacks"] == {
        **written["stacks"], "bottom_mlp_dims": [512, 256, 128],
        "mlp_dims": [1024, 1024, 512, 256], "pairs": 351}
    assert written["training"]["batch_per_chip"] == 55296
    assert written["training"]["learning_rate"] == REGISTERED.learning_rate


# -------------------------------------------- eval, predict, the scorer


def test_cli_eval_predict_and_the_engine_return_spec_scores(tiny, tmp_path,
                                                            capsys):
    _, cfg = tiny
    model_dir = str(tmp_path / "model")
    assert cli.main(["train", "--config", cfg.name, "--synthetic", "512",
                     "--steps", "5", "--log-every", "5", "--obs-dir", "none",
                     "--test-fraction", "0.25", "--model-out",
                     model_dir]) == 0
    out = capsys.readouterr().out
    trained = json.loads([l for l in out.splitlines() if '"eval"' in l][-1])
    assert np.isfinite(trained["eval"]["logloss"])
    spec, params = models.load_model(model_dir)
    assert spec == cfg.spec() and isinstance(spec.bottom_mlp_dims, tuple)

    from fm_spark_tpu import data as data_lib
    from fm_spark_tpu.train import evaluate_params

    ids, vals, labels = data_lib.synthetic_ctr(
        200, spec.num_features, F, seed=1, dense_fields=D)
    ids = ids - (np.arange(F, dtype=ids.dtype) * BUCKET)[None, :]
    assert cli.main(["eval", "--model", model_dir, "--synthetic", "200",
                     "--batch-size", "64"]) == 0
    said = json.loads(capsys.readouterr().out.splitlines()[-1])
    want = evaluate_params(spec, params, data_lib.iterate_once(
        ids, vals, labels, 64))
    assert said["logloss"] == pytest.approx(want["logloss"], rel=1e-6)
    direct = np.asarray(spec.predict(params, jnp.asarray(ids),
                                     jnp.asarray(vals)))
    assert direct.std() > 0                          # the values are read
    pred_file = tmp_path / "preds.txt"
    assert cli.main(["predict", "--model", model_dir, "--synthetic", "200",
                     "--batch-size", "64", "--out", str(pred_file)]) == 0
    np.testing.assert_allclose(np.loadtxt(pred_file), direct, rtol=1e-5)

    from fm_spark_tpu.serve import PredictEngine

    engine = PredictEngine(spec, params, buckets=(16, 64))
    try:
        engine.warmup()
        assert engine._gen.held["tables_as_is"] == C
        np.testing.assert_allclose(engine.predict(ids[:50], vals[:50]),
                                   direct[:50], rtol=1e-6)
    finally:
        engine.close()


def test_a_criteo_file_loads_its_integer_columns_as_values(tmp_path):
    lines = [b"1\t3\t\t-2\t0\t" + b"\t".join([b"7"] * 9) + b"\t"
             + b"\t".join([b"0a1b2c3d"] * 26),
             b"0\t" + b"\t".join([b"1"] * 13) + b"\t"
             + b"\t".join([b""] * 26),
             b"0\tx\t" + b"\t".join([b"1"] * 12) + b"\t"
             + b"\t".join([b"ff"] * 26)]
    bad = []
    ids, vals, labels = criteo.parse_lines_dense(
        lines, 64, on_error=lambda *a: bad.append(a[1]), start_lineno=1)
    assert bad == [3] and labels.tolist() == [1, 0]
    np.testing.assert_allclose(vals[0, :5], np.log1p([3, 0, 0, 0, 7]))
    np.testing.assert_allclose(vals[1, :13], np.log1p(1.0))
    assert (vals[:, 13:] == 1.0).all() and vals.dtype == np.float32
    hashed, _ = criteo.parse_lines(lines[:2], 64)
    np.testing.assert_array_equal(ids[:, 13:], hashed[:, 13:])
    np.testing.assert_array_equal(ids[:, :13],
                                  np.arange(13)[None].repeat(2, 0) * 64)
    with pytest.raises(ValueError, match="bad criteo field"):
        criteo.parse_lines_dense(lines, 64)
    # cli train loads it so, and only so.
    path = tmp_path / "day.tsv"
    criteo.synthesize_tsv(str(path), 96, seed=3)

    class Args:
        synthetic, data, data_policy = 0, str(path), "strict"
        quarantine_dir = max_bad_frac = None

    small = dataclasses.replace(REGISTERED, bucket=BUCKET)
    got_ids, got_vals, got_labels, _ = cli.load_dataset(small, Args)
    assert got_ids.shape == (96, 39) and got_ids.max() < BUCKET
    assert (got_vals[:, 13:] == 1.0).all() and got_vals[:, :13].max() > 1.0
    assert (got_ids[:, :13] == 0).all() and got_labels.dtype == np.float32
    hashed_cfg = dataclasses.replace(
        configs_lib.CONFIGS["criteo1tb_deepfm"], bucket=BUCKET)
    _, ones, _, _ = cli.load_dataset(hashed_cfg, Args)
    assert (ones == 1.0).all()                       # the default path


# ----------------------------------------------------- what is refused


def argv(cfg, *more, steps=2):
    return ["train", "--config", cfg.name, "--synthetic", str(2 * BATCH),
            "--steps", str(steps), "--obs-dir", "none", "--test-fraction",
            "0", *more]


def test_save_kill_resume_continues_bit_identically(tiny, tmp_path, capsys):
    """The loop's ``carries_opt`` path with plain SGD's EMPTY state: a
    checkpoint holds tables and stacks, and a resume continues as if
    never stopped."""
    _, cfg = tiny

    def losses(steps, *more):
        assert cli.main(argv(cfg, "--log-every", "1", *more,
                             steps=steps)) == 0
        return [json.loads(line)["loss"]
                for line in capsys.readouterr().out.splitlines()
                if line.startswith('{"step"')]

    golden = losses(6)
    chain = ["--checkpoint-dir", str(tmp_path / "chain"),
             "--checkpoint-every", "3"]
    assert losses(3, *chain) + losses(6, *chain) == golden
    assert len(golden) == 6 and len(set(golden)) == 6


def test_the_capability_row_refuses_a_mesh(monkeypatch):
    small = dataclasses.replace(REGISTERED, name="dlrm_tiny", **TINY)
    monkeypatch.setitem(configs_lib.CONFIGS, small.name, small)
    assert jax.device_count() == 8
    with pytest.raises(SystemExit, match="FieldDLRMSpec has no field-sharded"):
        cli.main(argv(small))


@pytest.mark.parametrize("more,message", [
    (["--steps-per-call", "2"], "steps-per-call > 1 is not supported for "
                                "FieldDLRMSpec"),
    (["--optimizer", "adagrad"], "plain SGD only.*'adagrad'"),
    (["--optimizer", "ftrl"], "plain SGD only.*'ftrl'"),
    (["--data", "a.tsv,b.tsv"], "dense columns as values"),
])
def test_the_capability_row_refuses_by_name(tiny, more, message):
    _, cfg = tiny
    with pytest.raises((SystemExit, ValueError), match=message):
        cli.main(argv(cfg, *more))
    row = cli._FIELD_CAPS["FieldDLRMSpec"]
    assert row.carries_opt and row.sharded_step is None
    assert not any(getattr(row, f.name) for f in dataclasses.fields(row)
                   if f.type == "bool" and f.name != "carries_opt")
    assert row.table_rules == ()


@pytest.mark.parametrize("lever", [
    dict(host_dedup=True, sparse_update="dedup"),
    dict(compact_device=True, compact_cap=32, sparse_update="dedup"),
    dict(gfull_fused=True), dict(fused_embed="require"),
    dict(reg_factors=1e-6),
])
def test_the_body_refuses_what_it_does_not_implement(lever):
    spec, config = spec_and_config(**lever)
    with pytest.raises(ValueError) as refused:
        sparse.make_field_dlrm_sparse_body(spec, config)
    assert "FieldDLRM" in str(refused.value)
    with pytest.raises(ValueError, match="one step a call"):
        sparse.lower_field_sparse_step(spec, spec_and_config()[1], BATCH,
                                       steps_per_call=2)


def test_the_spec_refuses_shapes_that_are_not_a_dlrm():
    base = dict(num_features=F * BUCKET, rank=K, num_fields=F, bucket=BUCKET,
                dense_fields=D, bottom_mlp_dims=BOTTOM, mlp_dims=TOP)
    assert models.FieldDLRMSpec(**base).num_tables == C
    for change in (dict(bottom_mlp_dims=(16, 4)), dict(dense_fields=0),
                   dict(dense_fields=F), dict(use_bias=True),
                   dict(num_features=F * BUCKET + 1)):
        with pytest.raises(ValueError):
            models.FieldDLRMSpec(**{**base, **change})
    with pytest.raises(ValueError, match="expected a FieldDLRMSpec"):
        sparse.make_field_dlrm_sparse_body(
            models.FieldFMSpec(num_features=F * BUCKET, rank=K, num_fields=F,
                               bucket=BUCKET), TrainConfig())
