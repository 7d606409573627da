"""FieldFFM: flat-FFM equivalence, fused-step gradients, save/load."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fm_spark_tpu import models
from fm_spark_tpu.sparse import make_field_ffm_sparse_sgd_step
from fm_spark_tpu.train import TrainConfig, make_train_step, make_optimizer


def _spec(F=4, bucket=16, k=3, **kw):
    return models.FieldFFMSpec(
        num_features=F * bucket, rank=k, num_fields=F, bucket=bucket,
        init_std=0.2, **kw,
    )


def _batch(rng, b, F, bucket):
    return (
        rng.integers(0, bucket, size=(b, F)).astype(np.int32),
        rng.uniform(0.5, 1.5, size=(b, F)).astype(np.float32),
        rng.integers(0, 2, b).astype(np.float32),
        np.ones((b,), np.float32),
    )


def test_init_waits_for_each_table_and_keeps_its_values(monkeypatch):
    """init makes the tables one at a time, each waited for before the
    next is queued (where they lie on the chip must not follow how far
    ahead the host got), with the values the plain expression gives,
    and still traces (eval_shape: what restores and lowerings build
    their shapes from)."""
    spec = _spec(F=5, bucket=8, k=2)
    waited = []
    real = jax.block_until_ready
    monkeypatch.setattr(
        jax, "block_until_ready",
        lambda x: (waited.append(x.shape), real(x))[1])
    params = spec.init(jax.random.key(7))
    monkeypatch.undo()
    assert waited == [(8, 11)] * 5
    assert all(t.is_ready() for t in params["vw"])
    keys = jax.random.split(jax.random.key(7), 5)
    for key, table in zip(keys, params["vw"]):
        v = jax.random.normal(key, (8, 10), jnp.float32) * spec.init_std
        np.testing.assert_array_equal(np.asarray(table[:, :10]),
                                      np.asarray(v))
        assert not np.asarray(table[:, 10]).any()
    shapes = jax.eval_shape(spec.init, jax.random.key(7))
    assert [s.shape for s in shapes["vw"]] == [(8, 11)] * 5


def test_scores_match_flat_ffm():
    rng = np.random.default_rng(0)
    spec = _spec()
    params = spec.init(jax.random.key(0))
    # Randomize linear weights too (init is zero).
    params["vw"] = [
        t.at[:, -1].set(jnp.asarray(rng.normal(size=t.shape[0]), t.dtype))
        for t in params["vw"]
    ]
    ids, vals, _, _ = _batch(rng, 32, 4, 16)
    ids, vals = jnp.asarray(ids), jnp.asarray(vals)
    want = spec.flat_spec().scores(
        spec.to_flat_params(params), spec.to_global_ids(ids), vals
    )
    got = spec.scores(params, ids, vals)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_scores_match_bruteforce_oracle():
    from fm_spark_tpu.ops.ffm import ffm_scores_dense

    rng = np.random.default_rng(1)
    spec = _spec(F=3, bucket=8, k=2)
    params = spec.init(jax.random.key(1))
    flat = spec.to_flat_params(params)
    ids, vals, _, _ = _batch(rng, 16, 3, 8)
    ids_j, vals_j = jnp.asarray(ids), jnp.asarray(vals)
    want = ffm_scores_dense(
        flat["w0"], flat["w"], flat["v"], spec.to_global_ids(ids_j), vals_j
    )
    got = spec.scores(params, ids_j, vals_j)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_fused_step_matches_autodiff_dense_path():
    """The analytic fused backward ≡ jax.grad through scores + SGD."""
    rng = np.random.default_rng(2)
    spec = _spec()
    config = TrainConfig(learning_rate=0.3, lr_schedule="inv_sqrt",
                         optimizer="sgd")
    fused = make_field_ffm_sparse_sgd_step(spec, config)
    dense = make_train_step(spec, config, make_optimizer(config))

    pa = spec.init(jax.random.key(2))
    pb = jax.tree_util.tree_map(jnp.copy, pa)
    opt_state = make_optimizer(config).init(pb)
    for i in range(3):
        ids, vals, labels, w = map(jnp.asarray, _batch(rng, 32, 4, 16))
        pa, loss_a = fused(pa, jnp.int32(i), ids, vals, labels, w)
        pb, opt_state, m = dense(pb, opt_state, ids, vals, labels, w)
        np.testing.assert_allclose(float(loss_a), float(m["loss"]), rtol=1e-5)
    for f in range(4):
        np.testing.assert_allclose(
            np.asarray(pa["vw"][f]), np.asarray(pb["vw"][f]),
            rtol=5e-4, atol=1e-6,
        )
    np.testing.assert_allclose(float(pa["w0"]), float(pb["w0"]), rtol=1e-4)


def test_fused_step_learns_planted_structure():
    rng = np.random.default_rng(3)
    F, bucket = 4, 32
    spec = _spec(F=F, bucket=bucket, k=4)
    config = TrainConfig(learning_rate=0.2, lr_schedule="constant",
                         optimizer="sgd")
    step = make_field_ffm_sparse_sgd_step(spec, config)
    params = spec.init(jax.random.key(3))
    from fm_spark_tpu.data import synthetic_ctr

    ids_g, vals, labels = synthetic_ctr(4096, F * bucket, F, seed=3)
    ids = ids_g - (np.arange(F) * bucket)[None, :].astype(np.int32)
    losses = []
    for i in range(16):
        sl = slice(i * 256, (i + 1) * 256)
        params, loss = step(
            params, jnp.int32(i), jnp.asarray(ids[sl]), jnp.asarray(vals[sl]),
            jnp.asarray(labels[sl]), jnp.ones((256,), jnp.float32),
        )
        losses.append(float(loss))
    assert np.mean(losses[-4:]) < np.mean(losses[:4]), losses


def test_dedup_mode_matches_scatter_add():
    rng = np.random.default_rng(4)
    spec = _spec(F=3, bucket=8, k=2)
    base = TrainConfig(learning_rate=0.3, optimizer="sgd", reg_factors=1e-3,
                       reg_linear=1e-4)
    step_a = make_field_ffm_sparse_sgd_step(spec, base)
    step_b = make_field_ffm_sparse_sgd_step(
        spec, dataclasses.replace(base, sparse_update="dedup")
    )
    pa = spec.init(jax.random.key(4))
    pb = jax.tree_util.tree_map(jnp.copy, pa)
    for i in range(2):
        batch = tuple(map(jnp.asarray, _batch(rng, 64, 3, 8)))
        pa, la = step_a(pa, jnp.int32(i), *batch)
        pb, lb = step_b(pb, jnp.int32(i), *batch)
        np.testing.assert_allclose(float(la), float(lb), rtol=1e-6)
    for f in range(3):
        np.testing.assert_allclose(
            np.asarray(pa["vw"][f]), np.asarray(pb["vw"][f]),
            rtol=1e-4, atol=1e-6,
        )


def test_save_load_roundtrip(tmp_path):
    spec = _spec()
    params = spec.init(jax.random.key(5))
    models.save_model(str(tmp_path / "m"), spec, params)
    spec2, params2 = models.load_model(str(tmp_path / "m"))
    assert spec2 == spec
    rng = np.random.default_rng(5)
    ids, vals, _, _ = _batch(rng, 8, 4, 16)
    np.testing.assert_allclose(
        np.asarray(spec2.predict(params2, jnp.asarray(ids), jnp.asarray(vals))),
        np.asarray(spec.predict(params, jnp.asarray(ids), jnp.asarray(vals))),
        rtol=1e-6,
    )
