"""COMPACT host-dedup (`TrainConfig.compact_cap`): the cap-lane path —
unique-row gather, inv expansion, cumsum segment sums, one unique+sorted
write per id — must match the scatter_add step up to fp32 reassociation
(the cumsum reorders the additions, so equality is allclose, not
bitwise; everything else in the step is identical math).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fm_spark_tpu import models
from fm_spark_tpu.ops.scatter import compact_aux
from fm_spark_tpu.sparse import (
    make_field_sparse_multistep,
    make_field_sparse_sgd_body,
    make_field_sparse_sgd_step,
)
from fm_spark_tpu.train import TrainConfig

F, BUCKET, K, B, CAP = 5, 64, 4, 48, 48


def _batch(rng, b=B, f=F, bucket=BUCKET):
    ids = rng.integers(0, bucket, size=(b, f)).astype(np.int32)
    ids[:, 0] = rng.integers(0, 3, b)          # heavy duplication
    vals = rng.normal(size=(b, f)).astype(np.float32)
    labels = rng.integers(0, 2, b).astype(np.float32)
    weights = np.ones(b, np.float32)
    weights[::7] = 0.0                          # inert rows
    return ids, vals, labels, weights


def _spec(**kw):
    kw.setdefault("param_dtype", "float32")
    return models.FieldFMSpec(
        num_features=F * BUCKET, rank=K, num_fields=F, bucket=BUCKET,
        init_std=0.1, **kw
    )


def test_compact_aux_semantics(rng):
    ids = rng.integers(0, 17, size=(40, 3)).astype(np.int32)
    cap = 24
    useg, segstart, segend, order, inv = compact_aux(ids, cap)
    assert useg.shape == segstart.shape == segend.shape == (3, cap)
    assert order.shape == inv.shape == (3, 40)
    for f in range(3):
        uniq = np.unique(ids[:, f])
        s = uniq.size
        np.testing.assert_array_equal(useg[f, :s], uniq)
        # Padding: distinct ascending out-of-range sentinels — the whole
        # vector stays sorted and unique (the XLA scatter promises).
        assert (np.diff(useg[f].astype(np.int64)) > 0).all()
        assert (useg[f, s:] >= np.iinfo(np.int32).max - cap).all()
        sid = ids[order[f], f]
        np.testing.assert_array_equal(sid, np.sort(ids[:, f]))
        for seg in range(s):
            lo, hi = segstart[f, seg], segend[f, seg]
            assert (sid[lo : hi + 1] == useg[f, seg]).all()
            if hi + 1 < 40:
                assert sid[hi + 1] != useg[f, seg]
        # inv maps each original lane to its id's segment.
        np.testing.assert_array_equal(useg[f, inv[f]], ids[:, f])


def test_compact_aux_overflow_raises(rng):
    ids = rng.integers(0, 40, size=(64, 2)).astype(np.int32)
    with pytest.raises(ValueError, match="compact cap"):
        compact_aux(ids, 4)


def test_compact_aux_native_matches_numpy(rng):
    from fm_spark_tpu import native

    if not native.available():
        pytest.skip(f"native library unavailable: {native.build_error()}")
    ids = (rng.zipf(1.3, size=(257, 7)) % 50).astype(np.int32)
    ids[:, 3] = 5  # constant field
    got = native.compact_aux_native(ids, 128)
    assert got is not None
    import unittest.mock as mock

    with mock.patch.object(native, "compact_aux_native", lambda *a: None):
        want = compact_aux(ids, 128)
    names = ("useg", "segstart", "segend", "order", "inv")
    for g, w, name in zip(got, want, names):
        np.testing.assert_array_equal(g, w, err_msg=name)
    with pytest.raises(ValueError, match="compact cap"):
        native.compact_aux_native(ids, 4)


def _run_pair(rng, cfg_kw=None, spec_kw=None, step_idx=3):
    ids, vals, labels, weights = _batch(rng)
    spec = _spec(**(spec_kw or {}))
    params = spec.init(jax.random.key(1))
    base = dict(learning_rate=0.05, optimizer="sgd",
                reg_factors=1e-4, reg_linear=1e-4)
    base.update(cfg_kw or {})
    ref_step = make_field_sparse_sgd_step(spec, TrainConfig(**base))
    cmp_step = make_field_sparse_sgd_step(
        spec,
        TrainConfig(**base, sparse_update="dedup", host_dedup=True,
                    compact_cap=CAP),
    )
    aux = tuple(jnp.asarray(a) for a in compact_aux(ids, CAP))
    args = (jnp.int32(step_idx), jnp.asarray(ids), jnp.asarray(vals),
            jnp.asarray(labels), jnp.asarray(weights))
    p_ref, l_ref = ref_step(jax.tree.map(jnp.copy, params), *args)
    p_cmp, l_cmp = cmp_step(params, *args, aux)
    return p_ref, l_ref, p_cmp, l_cmp


def test_compact_step_matches_scatter_add(rng):
    p_ref, l_ref, p_cmp, l_cmp = _run_pair(rng)
    assert float(l_ref) == float(l_cmp)  # same forward math
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-6, atol=2e-7),
        p_ref, p_cmp,
    )


def test_compact_dedup_sr_fp32_matches_dedup(rng):
    """For fp32 tables SR is the identity, and set(urows + sum) must hit
    the same values as add(sum) bitwise — pins the urows plumbing."""
    ids, vals, labels, weights = _batch(rng)
    spec = _spec()
    params = spec.init(jax.random.key(2))
    mk = lambda su: make_field_sparse_sgd_step(
        spec,
        TrainConfig(learning_rate=0.05, optimizer="sgd", sparse_update=su,
                    host_dedup=True, compact_cap=CAP),
    )
    aux = tuple(jnp.asarray(a) for a in compact_aux(ids, CAP))
    args = (jnp.int32(0), jnp.asarray(ids), jnp.asarray(vals),
            jnp.asarray(labels), jnp.asarray(weights))
    p_a, _ = mk("dedup")(jax.tree.map(jnp.copy, params), *args, aux)
    p_b, _ = mk("dedup_sr")(params, *args, aux)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(a, b), p_a, p_b
    )


def test_compact_bf16_sr_learns(rng):
    """bf16 + compact dedup_sr: loss decreases over a few steps (the
    quality envelope itself is pinned by bench_quality/QUALITY.md)."""
    ids, vals, labels, weights = _batch(rng, b=256)
    spec = _spec(param_dtype="bfloat16")
    params = spec.init(jax.random.key(3))
    step = make_field_sparse_sgd_step(
        spec,
        TrainConfig(learning_rate=0.3, lr_schedule="constant",
                    optimizer="sgd", sparse_update="dedup_sr",
                    host_dedup=True, compact_cap=B_CAP256),
    )
    aux = tuple(jnp.asarray(a) for a in compact_aux(ids, B_CAP256))
    losses = []
    for i in range(25):
        params, loss = step(params, jnp.int32(i), jnp.asarray(ids),
                            jnp.asarray(vals), jnp.asarray(labels),
                            jnp.asarray(weights), aux)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.01


B_CAP256 = 128


def test_compact_multistep_matches_single(rng):
    """compact aux stacks on the leading axis like every other batch
    leaf; N fori_loop steps == N separate calls."""
    spec = _spec()
    cfg = TrainConfig(learning_rate=0.05, optimizer="sgd",
                      sparse_update="dedup", host_dedup=True,
                      compact_cap=CAP)
    params = spec.init(jax.random.key(4))
    batches = []
    for _ in range(3):
        ids, vals, labels, weights = _batch(rng)
        aux = compact_aux(ids, CAP)
        batches.append((ids, vals, labels, weights, aux))

    single = make_field_sparse_sgd_step(spec, cfg)
    p1 = jax.tree.map(jnp.copy, params)
    for j, (ids, vals, labels, weights, aux) in enumerate(batches):
        p1, _ = single(p1, jnp.int32(j), jnp.asarray(ids),
                       jnp.asarray(vals), jnp.asarray(labels),
                       jnp.asarray(weights),
                       tuple(jnp.asarray(a) for a in aux))

    mstep = make_field_sparse_multistep(spec, cfg, 3)
    stack = lambda xs: jnp.asarray(np.stack(xs))
    ids_s = stack([b[0] for b in batches])
    vals_s = stack([b[1] for b in batches])
    labels_s = stack([b[2] for b in batches])
    weights_s = stack([b[3] for b in batches])
    aux_s = tuple(
        stack([b[4][i] for b in batches]) for i in range(5)
    )
    p2, _ = mstep(params, jnp.int32(0), jnp.int32(3), ids_s, vals_s,
                  labels_s, weights_s, aux_s)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(a, b), p1, p2
    )


def test_compact_validation():
    spec = _spec()
    with pytest.raises(ValueError, match="host_dedup"):
        make_field_sparse_sgd_body(
            spec, TrainConfig(optimizer="sgd", sparse_update="dedup",
                              compact_cap=8)
        )
    # The field-sharded body supports COMPACT aux (1-D mesh) but must
    # still reject plain full-B host_dedup rather than silently ignore
    # it (it consumes only the compact aux format).
    from fm_spark_tpu.parallel.field_step import (
        make_field_mesh,
        make_field_sharded_sgd_body,
    )

    mesh = make_field_mesh(1)
    with pytest.raises(ValueError, match="not supported"):
        make_field_sharded_sgd_body(
            spec,
            TrainConfig(optimizer="sgd", sparse_update="dedup",
                        host_dedup=True),
            mesh,
        )


@pytest.mark.slow
def test_cli_measured_best_flags_smoke(tmp_path):
    """End-to-end: the full measured-best flag set (PERF.md headline —
    bf16 tables, bf16 compute, compact host-dedup, dedup_sr) trains,
    evals, and saves through the CLI. Subprocess with ONE cpu device so
    field_sparse routes to the single-chip fused step."""
    import os
    import subprocess
    import sys

    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = (
        os.path.dirname(os.path.dirname(__file__))
        + os.pathsep + env.get("PYTHONPATH", "")
    )
    proc = subprocess.run(
        [sys.executable, "-m", "fm_spark_tpu.cli",
         "train", "--config", "criteo1tb_fm_r64", "--synthetic", "4096",
         "--steps", "15", "--batch-size", "512",
         "--strategy", "field_sparse",
         "--param-dtype", "bfloat16", "--compute-dtype", "bfloat16",
         "--sparse-update", "dedup_sr", "--host-dedup",
         "--compact-cap", "512", "--prefetch", "2",
         "--test-fraction", "0.2", "--log-every", "5",
         "--model-out", str(tmp_path / "m")],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert '"eval"' in proc.stdout or "auc" in proc.stdout
    from fm_spark_tpu.models.io import load_model

    spec2, params2 = load_model(str(tmp_path / "m"))
    assert spec2.param_dtype == "bfloat16"


@pytest.mark.parametrize("mode", ["dedup", "dedup_sr"])
@pytest.mark.parametrize("n_feat,num_fields", [(4, 5), (2, 5), (4, 4)])
def test_sharded_compact_matches_single(rng, mode, n_feat, num_fields):
    """Field-sharded compact (1-D feat mesh, incl. padded fields) must
    match the single-chip compact step exactly: same aux, same SR key
    stream (global field offsets), single-owner cap-lane writes."""
    import jax.numpy as jnp

    from fm_spark_tpu.parallel.field_step import (
        make_field_mesh,
        make_field_sharded_sgd_step,
        pad_field_batch,
        shard_compact_aux,
        shard_field_batch,
        shard_field_params,
        stack_field_params,
        unstack_field_params,
    )

    bucket, rank, b, cap = 32, 4, 64, 64
    spec = models.FieldFMSpec(
        num_features=num_fields * bucket, rank=rank,
        num_fields=num_fields, bucket=bucket, init_std=0.1,
    )
    config = TrainConfig(learning_rate=0.3, lr_schedule="inv_sqrt",
                         optimizer="sgd", reg_factors=1e-3,
                         reg_linear=1e-4, reg_bias=1e-4,
                         sparse_update=mode, host_dedup=True,
                         compact_cap=cap)
    mesh = make_field_mesh(n_feat)
    params = spec.init(jax.random.key(0))
    ref_params = jax.tree.map(jnp.copy, params)
    sharded = shard_field_params(
        stack_field_params(spec, params, n_feat), mesh
    )
    step_sharded = make_field_sharded_sgd_step(spec, config, mesh)
    step_single = make_field_sparse_sgd_step(spec, config)

    for i in range(3):
        ids = rng.integers(0, bucket, size=(b, num_fields)).astype(np.int32)
        ids[:, 0] = rng.integers(0, 3, b)
        vals = rng.normal(size=(b, num_fields)).astype(np.float32)
        labels = rng.integers(0, 2, b).astype(np.float32)
        weights = np.ones(b, np.float32)
        weights[::5] = 0.0
        batch = (ids, vals, labels, weights)
        aux = compact_aux(ids, cap)
        paux = shard_compact_aux(aux, mesh, n_feat)
        sb = shard_field_batch(
            pad_field_batch(batch, num_fields, n_feat), mesh
        )
        sharded, loss_sh = step_sharded(sharded, jnp.int32(i), *sb, paux)
        ref_params, loss_ref = step_single(
            ref_params, jnp.int32(i), *map(jnp.asarray, batch),
            tuple(jnp.asarray(a) for a in aux),
        )
        np.testing.assert_allclose(
            float(loss_sh), float(loss_ref), rtol=1e-6
        )
    got = unstack_field_params(spec, jax.device_get(sharded))
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7
        ),
        got, jax.device_get(ref_params),
    )


def test_sharded_compact_rejects_2d_mesh():
    from fm_spark_tpu.parallel.field_step import (
        make_field_mesh,
        make_field_sharded_sgd_body,
    )

    spec = _spec()
    mesh = make_field_mesh(4, n_row=2)
    with pytest.raises(ValueError, match="1-D"):
        make_field_sharded_sgd_body(
            spec,
            TrainConfig(optimizer="sgd", sparse_update="dedup",
                        host_dedup=True, compact_cap=8),
            mesh,
        )


@pytest.mark.parametrize("mode", ["dedup", "dedup_sr"])
def test_ffm_compact_matches_plain(rng, mode):
    """FieldFFM fused step: compact aux path == plain path (fp32; SR is
    the identity there so dedup_sr pins the urows plumbing too)."""
    from fm_spark_tpu.sparse import make_field_ffm_sparse_sgd_step

    spec = models.FieldFFMSpec(
        num_features=F * BUCKET, rank=3, num_fields=F, bucket=BUCKET,
        init_std=0.1,
    )
    ids_np = rng.integers(0, 8, size=(B, F)).astype(np.int32)
    batch = (jnp.asarray(ids_np),
             jnp.asarray(rng.normal(size=(B, F)).astype(np.float32)),
             jnp.asarray(rng.integers(0, 2, B).astype(np.float32)),
             jnp.ones((B,)))
    cfg = dict(learning_rate=0.2, optimizer="sgd", sparse_update=mode)
    params = spec.init(jax.random.key(1))
    params_c = jax.tree.map(jnp.copy, params)
    step_p = make_field_ffm_sparse_sgd_step(spec, TrainConfig(**cfg))
    step_c = make_field_ffm_sparse_sgd_step(
        spec, TrainConfig(host_dedup=True, compact_cap=CAP, **cfg)
    )
    aux = tuple(jnp.asarray(a) for a in compact_aux(ids_np, CAP))
    for i in range(2):
        params, _ = step_p(params, jnp.int32(i), *batch)
        params_c, _ = step_c(params_c, jnp.int32(i), *batch, aux)
    for f in range(F):
        np.testing.assert_allclose(
            np.asarray(params_c["vw"][f]), np.asarray(params["vw"][f]),
            rtol=1e-5, atol=1e-7,
        )


@pytest.mark.parametrize("mode", ["dedup", "dedup_sr"])
@pytest.mark.slow
def test_deepfm_compact_matches_plain(rng, mode):
    """FieldDeepFM hybrid step: compact embedding updates == plain; the
    dense MLP/w0 side (optax) must be bitwise-unaffected."""
    from fm_spark_tpu.sparse import make_field_deepfm_sparse_step

    spec = models.FieldDeepFMSpec(
        num_features=F * BUCKET, rank=K, num_fields=F, bucket=BUCKET,
        init_std=0.1, mlp_dims=(8, 8),
    )
    ids_np = rng.integers(0, 8, size=(B, F)).astype(np.int32)
    batch = (jnp.asarray(ids_np),
             jnp.asarray(rng.normal(size=(B, F)).astype(np.float32)),
             jnp.asarray(rng.integers(0, 2, B).astype(np.float32)),
             jnp.ones((B,)))
    cfg = dict(learning_rate=0.05, optimizer="adam", sparse_update=mode)
    params = spec.init(jax.random.key(2))
    params_c = jax.tree.map(jnp.copy, params)
    step_p = make_field_deepfm_sparse_step(spec, TrainConfig(**cfg))
    step_c = make_field_deepfm_sparse_step(
        spec, TrainConfig(host_dedup=True, compact_cap=CAP, **cfg)
    )
    opt_p = step_p.init_opt_state(params)
    opt_c = step_c.init_opt_state(params_c)
    aux = tuple(jnp.asarray(a) for a in compact_aux(ids_np, CAP))
    for i in range(2):
        params, opt_p, _ = step_p(params, opt_p, jnp.int32(i), *batch)
        params_c, opt_c, _ = step_c(params_c, opt_c, jnp.int32(i), *batch,
                                    aux)
    for f in range(F):
        np.testing.assert_allclose(
            np.asarray(params_c["vw"][f]), np.asarray(params["vw"][f]),
            rtol=1e-5, atol=1e-7,
        )
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-8),
        {"w0": params_c["w0"], "mlp": params_c["mlp"]},
        {"w0": params["w0"], "mlp": params["mlp"]},
    )
