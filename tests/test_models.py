"""Model-family tests: init semantics, task switch, DeepFM head, save/load."""

import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fm_spark_tpu import models
from fm_spark_tpu.models import base


def _batch(rng, n, b=8, nnz=4):
    ids = np.stack([rng.choice(n, size=nnz, replace=False) for _ in range(b)])
    vals = np.ones((b, nnz), np.float32)
    return jnp.asarray(ids, jnp.int32), jnp.asarray(vals)


def test_fm_init_matches_reference_semantics():
    spec = models.FMSpec(num_features=100, rank=8, init_std=0.02)
    params = spec.init(jax.random.key(0))
    assert float(params["w0"]) == 0.0
    assert not params["w"].any()
    std = float(jnp.std(params["v"]))
    assert 0.01 < std < 0.03  # ~N(0, 0.02²)


def test_fm_dim_gating(rng):
    n = 40
    ids, vals = _batch(rng, n)
    base_spec = models.FMSpec(num_features=n, rank=4)
    params = base_spec.init(jax.random.key(1))
    params["w0"] = jnp.float32(2.0)
    params["w"] = params["w"] + 1.0
    full = base_spec.scores(params, ids, vals)
    no_bias = models.FMSpec(num_features=n, rank=4, use_bias=False)
    np.testing.assert_allclose(no_bias.scores(params, ids, vals), full - 2.0, rtol=1e-5)
    no_lin = models.FMSpec(num_features=n, rank=4, use_linear=False)
    # w == 1 everywhere, vals == 1, nnz = 4 → linear term = 4.
    np.testing.assert_allclose(no_lin.scores(params, ids, vals), full - 4.0, rtol=1e-5)
    # Gradients of disabled terms are exactly zero.
    g = jax.grad(lambda p: jnp.sum(no_lin.scores(p, ids, vals)))(params)
    assert not np.asarray(g["w"]).any()


def test_regression_clip():
    spec = models.FMSpec(
        num_features=10, rank=2, task="regression", min_target=1.0, max_target=5.0
    )
    scores = jnp.asarray([-3.0, 2.0, 9.0])
    out = base.predict_from_scores(spec, scores)
    np.testing.assert_allclose(out, [1.0, 2.0, 5.0])


def test_classification_sigmoid():
    spec = models.FMSpec(num_features=10, rank=2)
    out = base.predict_from_scores(spec, jnp.asarray([0.0]))
    np.testing.assert_allclose(out, [0.5])


def test_deepfm_reduces_to_fm_plus_head(rng):
    n = 60
    ids, vals = _batch(rng, n, nnz=5)
    spec = models.DeepFMSpec(num_features=n, rank=4, num_fields=5, mlp_dims=(8, 8, 8))
    params = spec.init(jax.random.key(2))
    full = spec.scores(params, ids, vals)
    assert full.shape == (8,)
    # Zeroing the MLP output layer must recover the pure FM score.
    params_z = jax.tree_util.tree_map(lambda x: x, params)
    params_z["mlp"] = [dict(l) for l in params["mlp"]]
    params_z["mlp"][-1] = {
        "kernel": jnp.zeros_like(params["mlp"][-1]["kernel"]),
        "bias": jnp.zeros_like(params["mlp"][-1]["bias"]),
    }
    fm_spec = models.FMSpec(num_features=n, rank=4)
    fm_params = {k: params[k] for k in ("w0", "w", "v")}
    np.testing.assert_allclose(
        spec.scores(params_z, ids, vals),
        fm_spec.scores(fm_params, ids, vals),
        rtol=1e-5, atol=1e-5,
    )


def test_deepfm_padded_slots(rng):
    n = 60
    spec = models.DeepFMSpec(num_features=n, rank=4, num_fields=5, mlp_dims=(8, 8, 8))
    params = spec.init(jax.random.key(3))
    ids, vals = _batch(rng, n, nnz=5)
    vals = vals.at[:, -1].set(0.0)
    s1 = spec.scores(params, ids, vals)
    ids2 = ids.at[:, -1].set(0)
    s2 = spec.scores(params, ids2, vals)
    np.testing.assert_allclose(s1, s2, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("family", ["fm", "ffm", "deepfm"])
def test_save_load_roundtrip(tmp_path, rng, family):
    n = 30
    if family == "fm":
        spec = models.FMSpec(num_features=n, rank=4, task="regression",
                             min_target=1.0, max_target=5.0)
    elif family == "ffm":
        spec = models.FFMSpec(num_features=n, rank=4, num_fields=5)
    else:
        spec = models.DeepFMSpec(num_features=n, rank=4, num_fields=5,
                                 mlp_dims=(8, 8, 8))
    params = spec.init(jax.random.key(4))
    models.save_model(str(tmp_path / "m"), spec, params)
    spec2, params2 = models.load_model(str(tmp_path / "m"))
    assert spec2 == spec
    ids, vals = _batch(rng, n, nnz=5)
    np.testing.assert_allclose(
        spec.scores(params, ids, vals), spec2.scores(params2, ids, vals),
        rtol=1e-6, atol=1e-6,
    )
    if family == "fm":
        assert math.isfinite(spec2.min_target)


def test_bf16_save_load_roundtrip(tmp_path, rng):
    # Regression: bf16 tables used to serialize as raw '|V2' and fail to load.
    spec = models.FMSpec(num_features=20, rank=4, param_dtype="bfloat16")
    params = spec.init(jax.random.key(5))
    assert params["v"].dtype == jnp.bfloat16
    models.save_model(str(tmp_path / "m"), spec, params)
    spec2, params2 = models.load_model(str(tmp_path / "m"))
    assert params2["v"].dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(params["v"], np.float32), np.asarray(params2["v"], np.float32)
    )


def test_save_model_writes_no_file_over_the_limit(tmp_path, monkeypatch):
    # A chip machine with a file-size limit refused config 3's 2.7 GB in
    # one params.npz; every leaf is cut into bounded files instead.
    from fm_spark_tpu.models import io as model_io

    monkeypatch.setattr(model_io, "MAX_FILE_BYTES", 4096 + 256)
    spec = models.DeepFMSpec(num_features=30, rank=4, num_fields=5,
                             mlp_dims=(8, 8), param_dtype="bfloat16")
    params = spec.init(jax.random.key(6))
    model_io.save_model(str(tmp_path / "m"), spec, params)
    files = os.listdir(tmp_path / "m" / "params")
    assert len(files) > len(jax.tree_util.tree_leaves(params))
    assert all(os.path.getsize(tmp_path / "m" / "params" / f) <= 4096 + 256
               for f in files)
    spec2, params2 = model_io.load_model(str(tmp_path / "m"))
    assert spec2 == spec
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(params2)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    np.testing.assert_array_equal(
        model_io.load_array(str(tmp_path / "m"), "w0"),
        np.asarray(params["w0"], np.float32))


def test_bad_loss_fails_at_construction():
    with pytest.raises(ValueError):
        models.FMSpec(num_features=10, rank=2, loss="logloss")


def test_regression_derives_squared_loss():
    spec = models.FMSpec(num_features=10, rank=2, task="regression")
    assert spec.loss == "squared"
    assert models.FMSpec(num_features=10, rank=2).loss == "logistic"
    with pytest.raises(ValueError, match="squared"):
        models.FMSpec(num_features=10, rank=2, task="regression", loss="logistic")


def test_deepfm_slot_mismatch_raises(rng):
    spec = models.DeepFMSpec(num_features=30, rank=2, num_fields=5, mlp_dims=(4, 4, 4))
    params = spec.init(jax.random.key(0))
    ids, vals = _batch(rng, 30, nnz=6)
    with pytest.raises(ValueError, match="num_fields"):
        spec.scores(params, ids, vals)
