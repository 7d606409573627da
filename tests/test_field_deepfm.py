"""FieldDeepFM: fused hybrid step ≡ autodiff+optax; sharded ≡ single.

Config 5 (BASELINE.json:11) on the CTR layout: embedding tables update
via the analytic sparse scatter rule (FM part = the reference's
computeGradient rule, deep part through one vjp of the MLP wrt its
input), the MLP + bias via dense Adam. The references here are fully
independent: plain ``jax.grad`` through ``spec.scores`` plus an optax
update, with per-lane lazy L2 (the framework's sparse-reg semantics,
sparse.py module docstring).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from fm_spark_tpu import models
from fm_spark_tpu.ops import losses as losses_lib
from fm_spark_tpu.sparse import make_field_deepfm_sparse_step
from fm_spark_tpu.train import TrainConfig, make_optimizer


def _spec(F=4, bucket=32, k=4, mlp=(16, 16, 16), **kw):
    return models.FieldDeepFMSpec(
        num_features=F * bucket, rank=k, num_fields=F, bucket=bucket,
        mlp_dims=mlp, init_std=0.1, **kw,
    )


def _batch(rng, b, F, bucket):
    return (
        jnp.asarray(rng.integers(0, bucket, (b, F)), jnp.int32),
        jnp.asarray(rng.uniform(0.5, 1.5, (b, F)), jnp.float32),
        jnp.asarray(rng.integers(0, 2, b), jnp.float32),
        jnp.ones((b,), jnp.float32),
    )


def _reference_step(spec, config, dense_opt, ref, ref_opt, i, batch):
    """Autodiff + optax oracle with per-lane lazy L2 on the tables."""
    ids, vals, labels, w = batch
    per_loss = losses_lib.loss_fn(spec.loss)

    def loss_f(p):
        sc = spec.scores(p, ids, vals)
        return jnp.sum(per_loss(sc, labels) * w) / jnp.maximum(
            jnp.sum(w), 1.0
        )

    lref, g = jax.value_and_grad(loss_f)(ref)
    lr = config.learning_rate
    k = spec.rank
    new_vw = []
    for f in range(spec.num_fields):
        counts = np.zeros(spec.bucket, np.float32)
        np.add.at(counts, np.asarray(ids[:, f]), np.asarray(w > 0,
                                                            np.float32))
        cm = jnp.asarray(counts)[:, None]
        reg_col = jnp.concatenate([
            jnp.full((k,), config.reg_factors),
            jnp.full((1,), config.reg_linear),
        ])
        new_vw.append(
            ref["vw"][f]
            - lr * (g["vw"][f] + cm * reg_col[None, :] * ref["vw"][f])
        )
    gd = {
        "w0": g["w0"] + config.reg_bias * ref["w0"],
        "mlp": jax.tree_util.tree_map(
            lambda gg, pp: gg + config.reg_factors * pp,
            g["mlp"], ref["mlp"],
        ),
    }
    upd, ref_opt = dense_opt.update(gd, ref_opt,
                                    {"w0": ref["w0"], "mlp": ref["mlp"]})
    nd = optax.apply_updates({"w0": ref["w0"], "mlp": ref["mlp"]}, upd)
    return {"w0": nd["w0"], "vw": new_vw, "mlp": nd["mlp"]}, ref_opt, lref


def _assert_params_close(got, ref, F):
    np.testing.assert_allclose(float(got["w0"]), float(ref["w0"]),
                               rtol=1e-4, atol=1e-7)
    for f in range(F):
        np.testing.assert_allclose(
            np.asarray(got["vw"][f]), np.asarray(ref["vw"][f]),
            rtol=2e-4, atol=1e-6,
        )
    for la, lb in zip(got["mlp"], ref["mlp"]):
        np.testing.assert_allclose(np.asarray(la["kernel"]),
                                   np.asarray(lb["kernel"]),
                                   rtol=2e-4, atol=1e-6)
        np.testing.assert_allclose(np.asarray(la["bias"]),
                                   np.asarray(lb["bias"]),
                                   rtol=2e-4, atol=1e-6)


def test_fused_step_matches_autodiff_optax():
    F, bucket = 4, 32
    spec = _spec(F, bucket)
    config = TrainConfig(learning_rate=0.05, lr_schedule="constant",
                         optimizer="adam", reg_factors=1e-3,
                         reg_linear=1e-4, reg_bias=1e-4)
    step = make_field_deepfm_sparse_step(spec, config)
    params = spec.init(jax.random.key(0))
    ref = jax.tree_util.tree_map(jnp.copy, params)
    opt_state = step.init_opt_state(params)
    dense_opt = make_optimizer(config)
    ref_opt = dense_opt.init({"w0": ref["w0"], "mlp": ref["mlp"]})
    rng = np.random.default_rng(0)
    for i in range(3):
        batch = _batch(rng, 64, F, bucket)
        params, opt_state, loss = step(params, opt_state, jnp.int32(i),
                                       *batch)
        ref, ref_opt, lref = _reference_step(spec, config, dense_opt, ref,
                                             ref_opt, i, batch)
        np.testing.assert_allclose(float(loss), float(lref), rtol=1e-5)
    _assert_params_close(params, ref, F)


@pytest.mark.slow
def test_fused_step_weighted_rows():
    # Zero-weight (epoch-padding) rows must not touch tables or head.
    F, bucket = 3, 16
    spec = _spec(F, bucket, mlp=(8, 8, 8))
    config = TrainConfig(learning_rate=0.1, lr_schedule="constant",
                         optimizer="adam", reg_factors=1e-3)
    step = make_field_deepfm_sparse_step(spec, config)
    params = spec.init(jax.random.key(1))
    ref = jax.tree_util.tree_map(jnp.copy, params)
    opt_state = step.init_opt_state(params)
    dense_opt = make_optimizer(config)
    ref_opt = dense_opt.init({"w0": ref["w0"], "mlp": ref["mlp"]})
    rng = np.random.default_rng(2)
    ids, vals, labels, w = _batch(rng, 32, F, bucket)
    w = w.at[16:].set(0.0)
    batch = (ids, vals, labels, w)
    params, opt_state, loss = step(params, opt_state, jnp.int32(0), *batch)
    ref, ref_opt, lref = _reference_step(spec, config, dense_opt, ref,
                                         ref_opt, 0, batch)
    np.testing.assert_allclose(float(loss), float(lref), rtol=1e-5)
    _assert_params_close(params, ref, F)


@pytest.mark.slow
@pytest.mark.parametrize("n_feat,num_fields", [(4, 6), (8, 5), (2, 4)])
def test_sharded_matches_single_chip(eight_devices, n_feat, num_fields):
    from fm_spark_tpu.parallel import (
        make_field_deepfm_sharded_step,
        make_field_mesh,
        pad_field_batch,
        shard_field_batch,
        shard_field_deepfm_params,
        stack_field_deepfm_params,
        unstack_field_deepfm_params,
    )

    bucket, b = 32, 64
    spec = _spec(num_fields, bucket, k=4, mlp=(16, 16, 16))
    config = TrainConfig(learning_rate=0.05, lr_schedule="inv_sqrt",
                         optimizer="adam", reg_factors=1e-3,
                         reg_linear=1e-4, reg_bias=1e-4)
    mesh = make_field_mesh(n_feat, devices=eight_devices)
    params = spec.init(jax.random.key(0))
    ref_params = jax.tree_util.tree_map(jnp.copy, params)

    step_sh = make_field_deepfm_sharded_step(spec, config, mesh)
    sharded = shard_field_deepfm_params(
        stack_field_deepfm_params(spec, params, n_feat), mesh
    )
    opt_sh = step_sh.init_opt_state(sharded)

    step_single = make_field_deepfm_sparse_step(spec, config)
    opt_single = step_single.init_opt_state(ref_params)

    rng = np.random.default_rng(0)
    for i in range(3):
        ids = np.asarray(rng.integers(0, bucket, (b, num_fields)),
                         np.int32)
        vals = np.asarray(rng.uniform(0.5, 1.5, (b, num_fields)),
                          np.float32)
        labels = np.asarray(rng.integers(0, 2, b), np.float32)
        w = np.ones((b,), np.float32)
        sb = shard_field_batch(
            pad_field_batch((ids, vals, labels, w), num_fields, n_feat),
            mesh,
        )
        sharded, opt_sh, loss_sh = step_sh(sharded, opt_sh, jnp.int32(i),
                                           *sb)
        ref_params, opt_single, loss_ref = step_single(
            ref_params, opt_single, jnp.int32(i),
            *map(jnp.asarray, (ids, vals, labels, w)),
        )
        np.testing.assert_allclose(float(loss_sh), float(loss_ref),
                                   rtol=1e-5)
    got = unstack_field_deepfm_params(spec, jax.device_get(sharded))
    _assert_params_close(got, jax.device_get(ref_params), num_fields)


def test_fused_deepfm_learns_synthetic():
    from fm_spark_tpu.data import synthetic_ctr

    F, bucket, b = 4, 64, 256
    spec = _spec(F, bucket, k=4, mlp=(32, 32, 32))
    config = TrainConfig(learning_rate=1e-2, lr_schedule="constant",
                         optimizer="adam")
    step = make_field_deepfm_sparse_step(spec, config)
    params = spec.init(jax.random.key(0))
    opt_state = step.init_opt_state(params)
    ids_g, vals, labels = synthetic_ctr(b * 30, F * bucket, F, seed=0)
    offs = (np.arange(F) * bucket).astype(np.int32)
    ids_l = ids_g - offs[None, :]
    losses = []
    for i in range(30):
        sl = slice(i * b, (i + 1) * b)
        params, opt_state, loss = step(
            params, opt_state, jnp.int32(i),
            jnp.asarray(ids_l[sl]), jnp.asarray(vals[sl]),
            jnp.asarray(labels[sl]), jnp.ones((b,), jnp.float32),
        )
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.01, losses


def test_spec_validation_and_io_roundtrip(tmp_path):
    with pytest.raises(ValueError, match="num_fields"):
        models.FieldDeepFMSpec(num_features=10, rank=2, num_fields=0,
                               bucket=5)
    with pytest.raises(ValueError, match="num_features"):
        models.FieldDeepFMSpec(num_features=11, rank=2, num_fields=2,
                               bucket=5)
    spec = _spec(3, 8, k=2, mlp=(4, 4, 4))
    params = spec.init(jax.random.key(3))
    models.save_model(str(tmp_path / "m"), spec, params)
    spec2, params2 = models.load_model(str(tmp_path / "m"))
    assert dataclasses.asdict(spec2) == dataclasses.asdict(spec)
    rng = np.random.default_rng(1)
    ids = jnp.asarray(rng.integers(0, 8, (16, 3)), jnp.int32)
    vals = jnp.ones((16, 3), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(spec.predict(params, ids, vals)),
        np.asarray(spec2.predict(params2, ids, vals)),
        rtol=1e-6,
    )
