"""Config 5 (``criteo1tb_deepfm``) against the benchmark's plain reference
(``benchmark/reference/deepfm.py``), at a small size on the CPU: 5
fields, rank 4, 64 buckets, a 16-16-16 head, batch 128.

- the reference's score against the sums of Guo et al. eq. 1-4 written
  out in float64;
- eight steps of the fused step against the reference's eight (losses,
  touched rows, head, bias), by the driver's own comparison
  (``benchmark/drivers/train_deep.py`` ``compare`` under the limits of
  ``traffic/train_fed_deep.json``: what decides the cell's ``correct``);
- the same through ``cli train``, which pins the reference's mirror of
  ``FieldDeepFMSpec.init``'s key splits, and the loop's hot intervals on
  the path that carries an optimizer state;
- the five faults the check exists to catch
  (``benchmark/tests/deepfm_faults.py``), each of which must FAIL that
  comparison;
- the head's precision rule and its named scopes, read from the lowered
  step.
"""

import dataclasses
import importlib.util
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
from benchmark.drivers import train_deep  # noqa: E402
from benchmark.reference import deepfm  # noqa: E402
from fm_spark_tpu import configs as configs_lib  # noqa: E402
from fm_spark_tpu import models, obs  # noqa: E402
from fm_spark_tpu.sparse import (  # noqa: E402
    make_field_deepfm_sparse_body,
    make_field_deepfm_sparse_step,
)

_spec = importlib.util.spec_from_file_location(
    "deepfm_faults",
    os.path.join(ROOT, "benchmark", "tests", "deepfm_faults.py"))
faults = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(faults)

F, K, BUCKET, MLP, BATCH, STEPS = 5, 4, 64, (16, 16, 16), 128, 8
MIX = harness.load_mix("train_fed_deep")
HEAD_STEPS = int(MIX["head_steps"])
SEED = 11


@pytest.fixture
def tiny(monkeypatch):
    """The registry's config 5 at the small size, as a cell's context."""
    small = dataclasses.replace(
        configs_lib.CONFIGS["criteo1tb_deepfm"], name="deepfm_reference_tiny",
        bucket=BUCKET, num_fields=F, rank=K, mlp_dims=MLP, batch_size=BATCH)
    monkeypatch.setitem(configs_lib.CONFIGS, small.name, small)
    monkeypatch.setattr(jax, "device_count", lambda *a: 1)
    written = harness.load_cell("deepfm_r16.train").config
    config = {**written,
              "model": {**written["model"], "rank": K, "num_fields": F,
                        "bucket": BUCKET},
              "head": {**written["head"], "mlp_dims": list(MLP)},
              "training": {**written["training"], "batch_per_chip": BATCH}}
    cell = harness.Cell(name="tiny", chips=1, config=config,
                        mix={**MIX, "check_steps": STEPS}, end_to_end=[],
                        per_layer=[])
    ctx = harness.Context(cell=cell, seed=SEED, seconds=0.0,
                          t_start=time.perf_counter(), trace_dir=None)
    return ctx, small


def check(ctx, late, early, uniq, counts, inv, vals, labels):
    want, rows0 = train_deep.reference_run(ctx, uniq, inv, vals, labels)
    return train_deep.compare(
        late, early, want, rows0, counts, steps=STEPS, head_steps=HEAD_STEPS,
        learning_rate=ctx.cell.config["training"]["learning_rate"],
        tol=ctx.cell.mix)


def through_cli(ctx, cfg):
    batch = train_deep.one_batch(ctx, 1)
    late, early = train_deep.two_runs(ctx, cfg, 1, batch[0])
    return check(ctx, late, early, *batch)


# ---------------------------------------------------------- the equations


@pytest.mark.parametrize("seed", [0, 1])
def test_reference_score_is_the_papers_sums(seed):
    rng = np.random.default_rng(seed)
    b = 6
    rows = [rng.normal(size=(b, K + 1)).astype(np.float32) for _ in range(F)]
    vals = rng.uniform(0.5, 1.5, (b, F)).astype(np.float32)
    w0 = np.float32(0.3)
    dims = deepfm.head_dims(F, K, MLP)
    assert dims == (F * K, *MLP, 1)
    head = [{"kernel": rng.normal(size=(i, o)).astype(np.float32) * 0.3,
             "bias": rng.normal(size=(o,)).astype(np.float32) * 0.1}
            for i, o in zip(dims[:-1], dims[1:])]
    got = np.asarray(deepfm.scores(
        [jnp.asarray(r) for r in rows], w0,
        jax.tree_util.tree_map(jnp.asarray, head), jnp.asarray(vals), K))
    for e in range(b):
        y = float(w0)
        for f in range(F):
            y += float(rows[f][e, K]) * float(vals[e, f])
            for g in range(f + 1, F):
                y += (float(np.dot(rows[f][e, :K].astype(np.float64),
                                   rows[g][e, :K].astype(np.float64)))
                      * float(vals[e, f]) * float(vals[e, g]))
        a = np.concatenate([rows[f][e, :K].astype(np.float64) * vals[e, f]
                            for f in range(F)])
        for layer in head[:-1]:
            a = np.maximum(a @ layer["kernel"].astype(np.float64)
                           + layer["bias"], 0.0)
        y += float((a @ head[-1]["kernel"].astype(np.float64)
                    + head[-1]["bias"])[0])
        assert got[e] == pytest.approx(y, rel=2e-5, abs=2e-6)
    assert deepfm.row_width(F, K) == K + 1
    assert deepfm.factor_columns(F, K) == K


# ------------------------------------------------ the step and the loop


def fused_steps(cfg, ctx, n, uniq, inv, vals, labels):
    spec, config = cfg.spec(), cfg.train_config()
    step = make_field_deepfm_sparse_step(spec, config)
    params = spec.init(jax.random.key(ctx.seed))
    opt = step.init_opt_state(params)
    ids = np.take_along_axis(uniq.T, inv, axis=0)          # [B, F] local
    batch = (jnp.asarray(ids), jnp.asarray(vals), jnp.asarray(labels),
             jnp.ones((len(labels),), jnp.float32))
    losses = []
    for i in range(n):
        params, opt, loss = step(params, opt, jnp.int32(i), *batch)
        losses.append(float(loss))
    return {**train_deep.taken(params, uniq), "losses": losses}


def test_eight_fused_steps_match_the_reference(tiny):
    ctx, cfg = tiny
    uniq, counts, inv, vals, labels = train_deep.one_batch(ctx, 1)
    late = fused_steps(cfg, ctx, STEPS, uniq, inv, vals, labels)
    early = fused_steps(cfg, ctx, HEAD_STEPS, uniq, inv, vals, labels)
    verdict = check(ctx, late, early, uniq, counts, inv, vals, labels)
    assert verdict["ok"], verdict
    for run in ("early", "late"):
        assert verdict[run]["loss_rel_err"] < 1e-5
        # Well inside every limit, not just under it.
        assert all(b["over_allowed"] < 0.5 for group in ("rows", "dense")
                   for b in verdict[run][group].values()), verdict
    # SGD at 1e-3 moved the rows by far less than Adam, a rate a step,
    # moved the head: the two optimizers are told apart.
    assert verdict["late"]["rows"]["factors"]["largest_delta"] < 1e-3
    head0 = deepfm.init_head(ctx.seed, deepfm.head_dims(F, K, MLP))
    moved = np.abs(late["head"][0]["kernel"] - np.asarray(head0[0]["kernel"]))
    assert 4e-3 < moved.max() < 9e-3


def test_cli_train_matches_the_reference_from_its_own_init(tiny):
    ctx, cfg = tiny
    t = time.perf_counter()
    verdict = through_cli(ctx, cfg)
    assert verdict["ok"], verdict
    # The loop's hot intervals on the path that carries an optimizer
    # state, in both check runs: one train/step per step, each with its
    # four parts at the log cadence (1 here), and the producer's batches.
    records = [iv for iv in obs.intervals() if iv.t0 >= t]
    steps = [iv for iv in records if iv.name == "train/step"]
    assert ([iv.attrs["step"] for iv in steps]
            == list(range(STEPS)) + list(range(HEAD_STEPS)))
    for parent in steps:
        kids = [iv.name for iv in records if iv.parent_id == parent.span_id]
        assert kids == ["train/next_batch", "train/prep", "train/dispatch",
                        "train/loss_fetch"], kids
    assert (sum(iv.name == "feed/produce" for iv in records)
            >= STEPS + HEAD_STEPS)


def test_a_reference_from_another_seed_fails(tiny):
    """The mirror of ``spec.init`` is held: the same steps from another
    seed's initial values are not this run's."""
    ctx, cfg = tiny
    batch = train_deep.one_batch(ctx, 1)
    late, early = train_deep.two_runs(ctx, cfg, 1, batch[0])
    other = dataclasses.replace(ctx, seed=SEED + 1)
    assert not check(other, late, early, *batch)["ok"]


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_the_check_catches(tiny, fault):
    ctx, cfg = tiny
    with faults.FAULTS[fault](cfg.name):
        verdict = through_cli(ctx, configs_lib.CONFIGS[cfg.name])
    assert not verdict["ok"], (fault, verdict)


def test_the_limits_are_the_mixs():
    assert MIX["driver"] == "train_deep" and MIX["like"] == "train_fed"
    for key in ("loss_rtol", "rows_rtol", "rows_rtol_late",
                "head_mean_rates", "head_mean_rates_late"):
        assert 0 < MIX[key] <= 1.0, key
    assert MIX["rows_ulps_per_root_occurrence"] > 0
    assert 2 <= MIX["head_steps"] < MIX["check_steps"]
    assert MIX["rows_rtol"] < MIX["rows_rtol_late"]
    assert MIX["head_mean_rates"] < MIX["head_mean_rates_late"]
    fed = harness.load_mix("train_fed")
    same = set(fed) - {"driver", "what", "rows_rtol", "loss_rtol"}
    assert all(MIX[k] == fed[k] for k in same)


# ------------------------------------------- the head as the program runs it


def lowered_step(compute_dtype):
    spec = models.FieldDeepFMSpec(
        num_features=F * BUCKET, rank=K, num_fields=F, bucket=BUCKET,
        mlp_dims=MLP, compute_dtype=compute_dtype)
    config = configs_lib.CONFIGS["criteo1tb_deepfm"].train_config()
    body, init_opt = make_field_deepfm_sparse_body(spec, config)
    params = spec.init(jax.random.key(0))
    return jax.jit(body).lower(
        params, init_opt(params), jnp.int32(0), jnp.zeros((BATCH, F), jnp.int32),
        jnp.ones((BATCH, F), jnp.float32), jnp.zeros((BATCH,), jnp.float32),
        jnp.ones((BATCH,), jnp.float32), None)


@pytest.mark.parametrize("compute_dtype,precision",
                         [("float32", "HIGHEST"), ("bfloat16", "DEFAULT")])
def test_head_products_run_at_the_declared_precision(compute_dtype,
                                                     precision):
    text = lowered_step(compute_dtype).as_text()
    dots = re.findall(r"stablehlo\.dot_general.*", text)
    # Four layers forward, and in the pullback four products for the
    # kernels' gradients and four for the inputs'.
    assert len(dots) == 12
    assert all(f"precision = [{precision}, {precision}]" in
               d.replace("#stablehlo<precision ", "").replace(">", "")
               for d in dots), dots[:2]


def test_head_runs_under_its_named_scopes():
    text = lowered_step("float32").as_text(debug_info=True)
    names = set(re.findall(r'"(jit\([^"]*deep/[^"]*)"', text))
    for scope in ("jvp(deep/forward)/dot_general",
                  "deep/backward/transpose(jvp(deep/forward))/dot_general",
                  "deep/adam/"):
        assert any(scope in n for n in names), (scope, sorted(names)[:8])
    # Nothing of the tables' side is inside a head scope.
    assert not any("scatter" in n or "gather" in n for n in names)


def test_deep_scores_float32_is_exact_to_float32():
    spec = models.FieldDeepFMSpec(
        num_features=F * BUCKET, rank=K, num_fields=F, bucket=BUCKET,
        mlp_dims=MLP)
    params = spec.init(jax.random.key(2))
    h = jax.random.normal(jax.random.key(3), (BATCH, F * K), jnp.float32)
    got = np.asarray(spec.deep_scores(params["mlp"], h), np.float64)
    a = np.asarray(h, np.float64)
    for li, layer in enumerate(params["mlp"]):
        a = a @ np.asarray(layer["kernel"], np.float64) + np.asarray(
            layer["bias"], np.float64)
        if li < len(MLP):
            a = np.maximum(a, 0.0)
    np.testing.assert_allclose(got, a[:, 0], rtol=1e-5, atol=1e-6)
