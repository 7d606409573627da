"""``sel_blocked`` (round-5 staged FFM lever): the per-owner-field
blocked interaction must agree with the default [B, F, F, k] body up to
fp reassociation of the pair sums, on every composition it ships with
(plain/compact aux, fp32/bf16 compute), and every non-FFM factory must
reject the flag (no-silent-fallback rule)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fm_spark_tpu import models
from fm_spark_tpu.ops.scatter import compact_aux
from fm_spark_tpu.sparse import (
    make_field_ffm_sparse_sgd_step,
    make_field_sparse_sgd_step,
)
from fm_spark_tpu.train import TrainConfig


def _spec(F=4, bucket=16, k=3, **kw):
    return models.FieldFFMSpec(
        num_features=F * bucket, rank=k, num_fields=F, bucket=bucket,
        init_std=0.2, **kw,
    )


def _batch(rng, b, F, bucket):
    return (
        jnp.asarray(rng.integers(0, bucket, size=(b, F)).astype(np.int32)),
        jnp.asarray(rng.uniform(0.5, 1.5, size=(b, F)).astype(np.float32)),
        jnp.asarray(rng.integers(0, 2, b).astype(np.float32)),
        jnp.ones((b,), jnp.float32),
    )


def _run(spec, config, n_steps=3, seed=2, aux_for=None):
    rng = np.random.default_rng(seed)
    step = make_field_ffm_sparse_sgd_step(spec, config)
    params = spec.init(jax.random.key(0))
    params["vw"] = [
        t.at[:, -1].set(jnp.asarray(rng.normal(size=t.shape[0]), t.dtype))
        for t in params["vw"]
    ]
    loss = None
    for i in range(n_steps):
        ids, vals, labels, w = _batch(rng, 64, spec.num_fields, spec.bucket)
        aux = aux_for(ids) if aux_for else None
        params, loss = step(params, jnp.int32(i), ids, vals, labels, w, aux)
    return params, float(loss)


def _assert_close(pa, pb, rtol, atol):
    np.testing.assert_allclose(np.asarray(pa["w0"]), np.asarray(pb["w0"]),
                               rtol=rtol, atol=atol)
    for ta, tb in zip(pa["vw"], pb["vw"]):
        np.testing.assert_allclose(
            np.asarray(ta, np.float32), np.asarray(tb, np.float32),
            rtol=rtol, atol=atol,
        )


@pytest.mark.parametrize("use_linear,use_bias", [(True, True),
                                                 (False, False)])
def test_blocked_matches_default_fp32(use_linear, use_bias):
    spec = _spec(use_linear=use_linear, use_bias=use_bias)
    base = TrainConfig(learning_rate=0.1, lr_schedule="constant",
                       optimizer="sgd", reg_factors=1e-3, reg_linear=1e-4,
                       reg_bias=1e-4)
    pa, la = _run(spec, base)
    pb, lb = _run(spec, dataclasses.replace(base, sel_blocked=True))
    # Same math, different pair-sum association order.
    _assert_close(pa, pb, rtol=2e-5, atol=2e-6)
    assert abs(la - lb) < 1e-5


def test_blocked_matches_default_bf16_compute():
    spec = _spec(compute_dtype="bfloat16")
    base = TrainConfig(learning_rate=0.1, lr_schedule="constant",
                       optimizer="sgd")
    pa, _ = _run(spec, base)
    pb, _ = _run(spec, dataclasses.replace(base, sel_blocked=True))
    _assert_close(pa, pb, rtol=3e-2, atol=3e-3)


def test_blocked_composes_with_compact_host_aux():
    spec = _spec(param_dtype="bfloat16", compute_dtype="bfloat16")
    base = TrainConfig(learning_rate=0.1, lr_schedule="constant",
                       optimizer="sgd", sparse_update="dedup_sr",
                       host_dedup=True, compact_cap=64)
    aux_for = lambda ids: jax.device_put(compact_aux(np.asarray(ids), 64))
    pa, _ = _run(spec, base, aux_for=aux_for)
    pb, _ = _run(spec, dataclasses.replace(base, sel_blocked=True),
                 aux_for=aux_for)
    _assert_close(pa, pb, rtol=3e-2, atol=3e-3)


def test_blocked_composes_with_compact_device():
    spec = _spec()
    base = TrainConfig(learning_rate=0.1, lr_schedule="constant",
                       optimizer="sgd", sparse_update="dedup",
                       compact_device=True, compact_cap=64)
    pa, _ = _run(spec, base)
    pb, _ = _run(spec, dataclasses.replace(base, sel_blocked=True))
    _assert_close(pa, pb, rtol=2e-5, atol=2e-6)


def test_non_ffm_factories_reject_sel_blocked():
    cfg = TrainConfig(learning_rate=0.1, lr_schedule="constant",
                      optimizer="sgd", sel_blocked=True)
    fm = models.FieldFMSpec(num_features=64, rank=3, num_fields=4,
                            bucket=16, init_std=0.1)
    with pytest.raises(ValueError, match="sel_blocked"):
        make_field_sparse_sgd_step(fm, cfg)


def test_sharded_ffm_step_rejects_sel_blocked():
    from fm_spark_tpu.parallel import (
        make_field_ffm_sharded_step,
        make_field_mesh,
    )

    mesh = make_field_mesh(len(jax.devices()))
    with pytest.raises(ValueError, match="sel_blocked"):
        make_field_ffm_sharded_step(
            _spec(),
            TrainConfig(learning_rate=0.1, lr_schedule="constant",
                        optimizer="sgd", sel_blocked=True),
            mesh,
        )


def test_cli_lever_rejects_non_ffm():
    from fm_spark_tpu import cli

    fm = models.FieldFMSpec(num_features=64, rank=3, num_fields=4,
                            bucket=16, init_std=0.1)
    tc = TrainConfig(learning_rate=0.1, lr_schedule="constant",
                     optimizer="sgd", sel_blocked=True)

    def refusal(spec, n):
        try:
            cli._validate_field_caps(
                spec, tc, cli._FIELD_CAPS[type(spec).__name__], n, 1,
                n > 1, 1, 1, False)
        except SystemExit as refused:
            return str(refused)
        return None

    assert "sel-blocked" in refusal(fm, 1)
    assert refusal(_spec(), 1) is None
    assert "sel-blocked" in refusal(_spec(), 8)


def test_dense_and_sharded_fm_factories_reject_sel_blocked():
    from fm_spark_tpu.parallel import make_field_mesh
    from fm_spark_tpu.parallel.field_step import (
        make_field_sharded_sgd_step,
    )
    from fm_spark_tpu.train import FMTrainer, TrainConfig as TC

    cfg = TC(learning_rate=0.1, lr_schedule="constant", optimizer="sgd",
             sel_blocked=True)
    with pytest.raises(ValueError, match="sel_blocked"):
        FMTrainer(_spec(), cfg).fit  # noqa: B018 — ctor builds the step
    fm = models.FieldFMSpec(num_features=64, rank=3, num_fields=4,
                            bucket=16, init_std=0.1)
    with pytest.raises(ValueError, match="sel_blocked"):
        make_field_sharded_sgd_step(
            fm, cfg, make_field_mesh(len(jax.devices()))
        )
