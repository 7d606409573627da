"""Field-sharded multi-chip step ≡ single-chip fused step (8-dev CPU mesh).

The Spark-idiom simulation strategy (SURVEY.md §4): the identical
shard_map/psum/all_to_all code path a real v5e-8 would run, on fake CPU
devices.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fm_spark_tpu import models
from fm_spark_tpu.parallel import (
    make_field_mesh,
    make_field_sharded_sgd_body,
    make_field_sharded_sgd_step,
    pad_field_batch,
    shard_field_batch,
    shard_field_params,
    stack_field_params,
    unstack_field_params,
)
from fm_spark_tpu.sparse import make_field_sparse_sgd_step
from fm_spark_tpu.train import TrainConfig


def _make_batch(rng, b, f, bucket):
    return (
        rng.integers(0, bucket, size=(b, f)).astype(np.int32),
        rng.uniform(0.5, 1.5, size=(b, f)).astype(np.float32),
        rng.integers(0, 2, b).astype(np.float32),
        np.ones((b,), np.float32),
    )


@pytest.mark.parametrize("n_feat,num_fields", [
    (8, 5),   # fields pad 5 → 8, three chips own only padding
    (4, 6),   # fields pad 6 → 8, uneven split of real fields
    (2, 6),   # even split
])
def test_field_sharded_matches_single_chip(eight_devices, n_feat, num_fields):
    bucket, rank, b = 32, 4, 64
    spec = models.FieldFMSpec(
        num_features=num_fields * bucket, rank=rank,
        num_fields=num_fields, bucket=bucket, init_std=0.1,
    )
    config = TrainConfig(learning_rate=0.3, lr_schedule="inv_sqrt",
                         optimizer="sgd", reg_factors=1e-3, reg_linear=1e-4,
                         reg_bias=1e-4)
    mesh = make_field_mesh(n_feat, devices=eight_devices)

    params = spec.init(jax.random.key(0))
    ref_params = jax.tree_util.tree_map(jnp.copy, params)

    sharded = shard_field_params(
        stack_field_params(spec, params, n_feat), mesh
    )
    step_sharded = make_field_sharded_sgd_step(spec, config, mesh)
    step_single = make_field_sparse_sgd_step(spec, config)

    rng = np.random.default_rng(0)
    for i in range(3):
        batch = _make_batch(rng, b, num_fields, bucket)
        sb = shard_field_batch(
            pad_field_batch(batch, num_fields, n_feat), mesh
        )
        sharded, loss_sh = step_sharded(sharded, jnp.int32(i), *sb)
        ref_params, loss_ref = step_single(
            ref_params, jnp.int32(i), *map(jnp.asarray, batch)
        )
        np.testing.assert_allclose(
            float(loss_sh), float(loss_ref), rtol=1e-5
        )

    got = unstack_field_params(spec, jax.device_get(sharded))
    np.testing.assert_allclose(
        float(got["w0"]), float(ref_params["w0"]), rtol=1e-5
    )
    for f in range(num_fields):
        np.testing.assert_allclose(
            np.asarray(got["vw"][f]), np.asarray(ref_params["vw"][f]),
            rtol=2e-4, atol=1e-6,
        )


def test_weighted_batch_matches(eight_devices):
    # Weight-0 padding rows (epoch tails) must behave identically sharded.
    num_fields, bucket, rank, n_feat, b = 6, 16, 2, 4, 32
    spec = models.FieldFMSpec(
        num_features=num_fields * bucket, rank=rank,
        num_fields=num_fields, bucket=bucket, init_std=0.1,
    )
    config = TrainConfig(learning_rate=0.2, optimizer="sgd")
    mesh = make_field_mesh(n_feat, devices=eight_devices)
    params = spec.init(jax.random.key(2))
    ref_params = jax.tree_util.tree_map(jnp.copy, params)
    sharded = shard_field_params(
        stack_field_params(spec, params, n_feat), mesh
    )
    step_sharded = make_field_sharded_sgd_step(spec, config, mesh)
    step_single = make_field_sparse_sgd_step(spec, config)
    rng = np.random.default_rng(3)
    ids, vals, labels, weights = _make_batch(rng, b, num_fields, bucket)
    weights[b // 2:] = 0.0
    batch = (ids, vals, labels, weights)
    sb = shard_field_batch(pad_field_batch(batch, num_fields, n_feat), mesh)
    sharded, loss_sh = step_sharded(sharded, jnp.int32(0), *sb)
    ref_params, loss_ref = step_single(
        ref_params, jnp.int32(0), *map(jnp.asarray, batch)
    )
    np.testing.assert_allclose(float(loss_sh), float(loss_ref), rtol=1e-5)
    got = unstack_field_params(spec, jax.device_get(sharded))
    for f in range(num_fields):
        np.testing.assert_allclose(
            np.asarray(got["vw"][f]), np.asarray(ref_params["vw"][f]),
            rtol=2e-4, atol=1e-6,
        )


def test_padded_fields_stay_zero(eight_devices):
    num_fields, bucket, rank, n_feat = 5, 16, 2, 4
    spec = models.FieldFMSpec(
        num_features=num_fields * bucket, rank=rank,
        num_fields=num_fields, bucket=bucket, init_std=0.1,
    )
    config = TrainConfig(learning_rate=0.5, optimizer="sgd",
                         reg_factors=1e-2, reg_linear=1e-2)
    mesh = make_field_mesh(n_feat, devices=eight_devices)
    sharded = shard_field_params(
        stack_field_params(spec, spec.init(jax.random.key(1)), n_feat), mesh
    )
    step = make_field_sharded_sgd_step(spec, config, mesh)
    rng = np.random.default_rng(1)
    for i in range(3):
        batch = pad_field_batch(
            _make_batch(rng, 32, num_fields, bucket), num_fields, n_feat
        )
        sharded, _ = step(sharded, jnp.int32(i), *shard_field_batch(batch, mesh))
    vw = np.asarray(jax.device_get(sharded["vw"]))
    assert vw.shape[0] == 8  # 5 → padded to 8
    np.testing.assert_array_equal(vw[num_fields:], 0.0)


def test_stack_roundtrip():
    spec = models.FieldFMSpec(
        num_features=3 * 8, rank=2, num_fields=3, bucket=8
    )
    params = spec.init(jax.random.key(0))
    stacked = stack_field_params(spec, params, n_feat=2)
    assert stacked["vw"].shape == (4, 8, 3)
    back = unstack_field_params(spec, stacked)
    for f in range(3):
        np.testing.assert_array_equal(
            np.asarray(back["vw"][f]), np.asarray(params["vw"][f])
        )


def test_requires_feat_mesh(eight_devices):
    from fm_spark_tpu.parallel import make_mesh
    from fm_spark_tpu.parallel.field_step import make_field_sharded_sgd_body

    spec = models.FieldFMSpec(num_features=2 * 8, rank=2, num_fields=2,
                              bucket=8)
    mesh2d = make_mesh(2, 4, devices=eight_devices)
    with pytest.raises(ValueError, match="'feat'"):
        make_field_sharded_sgd_body(spec, TrainConfig(optimizer="sgd"), mesh2d)


# ------------------------------------------------- 2-D (feat, row) mesh


@pytest.mark.parametrize("n_feat,n_row,num_fields,mode", [
    (4, 2, 6, "scatter_add"),   # fields pad 6 → 8, bucket split in 2
    (2, 4, 5, "scatter_add"),   # uneven fields + deep row split
    (1, 8, 3, "scatter_add"),   # PURE row sharding (capacity only)
    (4, 2, 6, "dedup"),         # dedup's drop-lane path + sentinel rows
    # scatter_add with the coalesced write forced on (ops/scatter
    # .update_lanes at small constants): non-owned lanes' sentinel rows
    # sort behind the owned ones and are dropped chunk by chunk.
    (4, 2, 6, "coalesced"),
    (2, 4, 5, "coalesced"),
])
def test_field_sharded_2d_matches_single_chip(eight_devices, monkeypatch,
                                              n_feat, n_row, num_fields,
                                              mode):
    bucket, rank, b = 32, 4, 64
    oracle_mode = "scatter_add"
    if mode == "coalesced":
        from fm_spark_tpu.ops import scatter

        monkeypatch.setattr(scatter, "RULE_CHUNK", 16)
        monkeypatch.setattr(scatter, "COALESCE_MAX_LANES", b)
        mode, oracle_mode = "scatter_add", "dedup"   # an oracle that masks
    spec = models.FieldFMSpec(
        num_features=num_fields * bucket, rank=rank,
        num_fields=num_fields, bucket=bucket, init_std=0.1,
    )
    config = TrainConfig(learning_rate=0.3, lr_schedule="inv_sqrt",
                         optimizer="sgd", reg_factors=1e-3, reg_linear=1e-4,
                         reg_bias=1e-4, sparse_update=mode)
    mesh = make_field_mesh(n_feat * n_row, devices=eight_devices,
                           n_row=n_row)
    assert dict(mesh.shape) == {"feat": n_feat, "row": n_row}

    params = spec.init(jax.random.key(0))
    ref_params = jax.tree_util.tree_map(jnp.copy, params)
    sharded = shard_field_params(
        stack_field_params(spec, params, n_feat), mesh
    )
    import dataclasses

    step_sharded = make_field_sharded_sgd_step(spec, config, mesh)
    # dedup ≡ scatter_add up to reassociation, so one single-chip oracle
    # serves both parametrizations.
    step_single = make_field_sparse_sgd_step(
        spec, dataclasses.replace(config, sparse_update=oracle_mode)
    )
    if oracle_mode == "dedup":
        from fm_spark_tpu.parallel import lower_field_sharded_step

        assert "sgd/write" in lower_field_sharded_step(
            spec, config, mesh, b).as_text(debug_info=True)

    rng = np.random.default_rng(0)
    for i in range(3):
        batch = _make_batch(rng, b, num_fields, bucket)
        sb = shard_field_batch(
            pad_field_batch(batch, num_fields, n_feat), mesh
        )
        sharded, loss_sh = step_sharded(sharded, jnp.int32(i), *sb)
        ref_params, loss_ref = step_single(
            ref_params, jnp.int32(i), *map(jnp.asarray, batch)
        )
        np.testing.assert_allclose(
            float(loss_sh), float(loss_ref), rtol=1e-5
        )

    got = unstack_field_params(spec, jax.device_get(sharded))
    np.testing.assert_allclose(
        float(got["w0"]), float(ref_params["w0"]), rtol=1e-5
    )
    for f in range(num_fields):
        np.testing.assert_allclose(
            np.asarray(got["vw"][f]), np.asarray(ref_params["vw"][f]),
            rtol=2e-4, atol=1e-6,
        )


def test_field_sharded_2d_weighted_and_padded(eight_devices):
    # Zero-weight tail rows + padded field slots, on the 2-D mesh.
    num_fields, bucket, rank, n_feat, n_row, b = 5, 16, 2, 2, 4, 32
    spec = models.FieldFMSpec(
        num_features=num_fields * bucket, rank=rank,
        num_fields=num_fields, bucket=bucket, init_std=0.1,
    )
    config = TrainConfig(learning_rate=0.2, optimizer="sgd")
    mesh = make_field_mesh(8, devices=eight_devices, n_row=n_row)
    params = spec.init(jax.random.key(2))
    ref_params = jax.tree_util.tree_map(jnp.copy, params)
    sharded = shard_field_params(
        stack_field_params(spec, params, n_feat), mesh
    )
    step_sharded = make_field_sharded_sgd_step(spec, config, mesh)
    step_single = make_field_sparse_sgd_step(spec, config)
    rng = np.random.default_rng(3)
    ids, vals, labels, weights = _make_batch(rng, b, num_fields, bucket)
    weights[b // 2:] = 0.0
    batch = (ids, vals, labels, weights)
    sb = shard_field_batch(pad_field_batch(batch, num_fields, n_feat), mesh)
    sharded, loss_sh = step_sharded(sharded, jnp.int32(0), *sb)
    ref_params, loss_ref = step_single(
        ref_params, jnp.int32(0), *map(jnp.asarray, batch)
    )
    np.testing.assert_allclose(float(loss_sh), float(loss_ref), rtol=1e-5)
    got = unstack_field_params(spec, jax.device_get(sharded))
    for f in range(num_fields):
        np.testing.assert_allclose(
            np.asarray(got["vw"][f]), np.asarray(ref_params["vw"][f]),
            rtol=2e-4, atol=1e-6,
        )
    vw = np.asarray(jax.device_get(sharded["vw"]))
    np.testing.assert_array_equal(vw[num_fields:], 0.0)  # padding inert


def test_field_sharded_2d_bucket_divisibility(eight_devices):
    spec = models.FieldFMSpec(num_features=2 * 12, rank=2, num_fields=2,
                              bucket=12)
    mesh = make_field_mesh(8, devices=eight_devices, n_row=8)
    with pytest.raises(ValueError, match="divide evenly"):
        make_field_sharded_sgd_body(
            spec, TrainConfig(optimizer="sgd"), mesh
        )


def test_field_sharded_2d_dedup_sr_learns(eight_devices):
    # bf16 + stochastic rounding through the 2-D sentinel path: per
    # (field, row-shard) SR keys, loss must fall, padding stays zero.
    num_fields, bucket, rank, n_feat, n_row, b = 3, 32, 4, 2, 4, 64
    spec = models.FieldFMSpec(
        num_features=num_fields * bucket, rank=rank, num_fields=num_fields,
        bucket=bucket, init_std=0.1, param_dtype="bfloat16",
    )
    config = TrainConfig(learning_rate=0.3, lr_schedule="constant",
                         optimizer="sgd", sparse_update="dedup_sr")
    mesh = make_field_mesh(8, devices=eight_devices, n_row=n_row)
    sharded = shard_field_params(
        stack_field_params(spec, spec.init(jax.random.key(0)), n_feat), mesh
    )
    step = make_field_sharded_sgd_step(spec, config, mesh)
    from fm_spark_tpu.data import synthetic_ctr

    ids_g, vals, labels = synthetic_ctr(b * 20, num_fields * bucket,
                                        num_fields, seed=0)
    offs = (np.arange(num_fields) * bucket).astype(np.int32)
    ids_l = ids_g - offs[None, :]
    losses = []
    for i in range(20):
        sl = slice(i * b, (i + 1) * b)
        batch = pad_field_batch(
            (ids_l[sl], vals[sl], labels[sl], np.ones((b,), np.float32)),
            num_fields, n_feat,
        )
        sharded, loss = step(sharded, jnp.int32(i),
                             *shard_field_batch(batch, mesh))
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]), losses


def test_field_sharded_dedup_sr_runs_and_learns(eight_devices):
    # dedup_sr inside shard_map (per-chip SR keys via axis_index): loss
    # must fall and tables must move; exact equality is not expected
    # (SR noise), so this is a smoke + learning check.
    num_fields, bucket, rank, n_feat, b = 6, 32, 4, 4, 64
    spec = models.FieldFMSpec(
        num_features=num_fields * bucket, rank=rank, num_fields=num_fields,
        bucket=bucket, init_std=0.1, param_dtype="bfloat16",
    )
    config = TrainConfig(learning_rate=0.3, lr_schedule="constant",
                         optimizer="sgd", sparse_update="dedup_sr")
    mesh = make_field_mesh(n_feat, devices=eight_devices)
    sharded = shard_field_params(
        stack_field_params(spec, spec.init(jax.random.key(0)), n_feat), mesh
    )
    step = make_field_sharded_sgd_step(spec, config, mesh)
    rng = np.random.default_rng(0)
    from fm_spark_tpu.data import synthetic_ctr

    ids_g, vals, labels = synthetic_ctr(b * 20, num_fields * bucket,
                                        num_fields, seed=0)
    offs = (np.arange(num_fields) * bucket).astype(np.int32)
    ids_l = ids_g - offs[None, :]
    losses = []
    for i in range(20):
        sl = slice(i * b, (i + 1) * b)
        batch = pad_field_batch(
            (ids_l[sl], vals[sl], labels[sl], np.ones((b,), np.float32)),
            num_fields, n_feat,
        )
        sharded, loss = step(sharded, jnp.int32(i),
                             *shard_field_batch(batch, mesh))
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]), losses


@pytest.mark.parametrize("n_row", [1, 2], ids=["feat4", "feat2xrow2"])
@pytest.mark.slow
def test_sharded_eval_matches_canonical(rng, n_row):
    """evaluate_field_sharded must equal evaluate_params on the canonical
    params — same histogram-AUC metric, no table gather."""
    from fm_spark_tpu.data import iterate_once
    from fm_spark_tpu.parallel.field_step import (
        evaluate_field_sharded,
        make_field_mesh,
        shard_field_params,
        stack_field_params,
    )
    from fm_spark_tpu.train import evaluate_params

    if len(jax.devices()) < 4:
        pytest.skip("needs >=4 devices")
    F, bucket, k, n = 5, 32, 4, 300
    spec = models.FieldFMSpec(
        num_features=F * bucket, rank=k, num_fields=F, bucket=bucket,
        init_std=0.3,
    )
    params = spec.init(jax.random.key(5))
    mesh = make_field_mesh(4, n_row=n_row)
    sharded = shard_field_params(
        stack_field_params(spec, params, mesh.shape["feat"]), mesh
    )
    ids = rng.integers(0, bucket, size=(n, F)).astype(np.int32)
    vals = rng.normal(size=(n, F)).astype(np.float32)
    labels = rng.integers(0, 2, n).astype(np.float32)

    want = evaluate_params(spec, params, iterate_once(ids, vals, labels, 64))
    got = evaluate_field_sharded(
        spec, mesh, sharded, iterate_once(ids, vals, labels, 64)
    )
    for key in ("auc", "logloss", "rmse", "count"):
        np.testing.assert_allclose(got[key], want[key], rtol=1e-5,
                                   atol=1e-6, err_msg=key)


def test_deepfm_sharded_eval_matches_canonical(rng):
    from fm_spark_tpu.data import iterate_once
    from fm_spark_tpu.parallel.field_step import (
        evaluate_field_sharded,
        make_field_mesh,
        shard_field_deepfm_params,
        stack_field_deepfm_params,
    )
    from fm_spark_tpu.train import evaluate_params

    if len(jax.devices()) < 4:
        pytest.skip("needs >=4 devices")
    F, bucket, k, n = 5, 32, 4, 300
    spec = models.FieldDeepFMSpec(
        num_features=F * bucket, rank=k, num_fields=F, bucket=bucket,
        init_std=0.3, mlp_dims=(8, 8),
    )
    params = spec.init(jax.random.key(6))
    mesh = make_field_mesh(4)
    sharded = shard_field_deepfm_params(
        stack_field_deepfm_params(spec, params, mesh.shape["feat"]), mesh
    )
    ids = rng.integers(0, bucket, size=(n, F)).astype(np.int32)
    vals = rng.normal(size=(n, F)).astype(np.float32)
    labels = rng.integers(0, 2, n).astype(np.float32)

    want = evaluate_params(spec, params, iterate_once(ids, vals, labels, 64))
    got = evaluate_field_sharded(
        spec, mesh, sharded, iterate_once(ids, vals, labels, 64)
    )
    for key in ("auc", "logloss", "rmse", "count"):
        np.testing.assert_allclose(got[key], want[key], rtol=1e-5,
                                   atol=1e-6, err_msg=key)


# ------------------------------------------- PR 33: the feed places batches
#
# Placement moved from the loop's thread into the feed; no device program
# moved with it. Two pins: the steps lower to the text they had, and a
# batch the feed placed lowers a step exactly as one the loop placed.

_PIN_FIELDS, _PIN_BUCKET, _PIN_BATCH = 6, 4096, 256
_PIN_CONFIG = TrainConfig(learning_rate=0.05, lr_schedule="constant",
                          optimizer="sgd", reg_factors=1e-6)
# sha256 of ``Lowered.as_text()`` at the parent of PR 33 (commit 80133fb;
# jax 0.9.0), CPU devices. A PR that MEANS to change a step's program
# re-pins its line and says so; any other PR must leave these alone.
_LOWERED_AT_PARENT = {
    ("fm", 1): "16ddeda5d213e482d9b8b6a64759fe0922d9344ab5b89990fb4fa1a8fe969916",
    ("fm", 4): "decc58193a7aacd359914542dd49c9866df3a719f7bdb306aeb4fb6c8a9be57d",
    ("ffm", 1): "cb8a46468237aa7f82ef13d929222f166b66a3d1625374c2a06b7cd31150e1a9",
    ("ffm", 4): "6f788a6918c6fb831577e61b343110f6c029f0287432e1826a3f1cb595088077",
    ("deepfm", 1): "443ef79d5a6f6d907744ece4636c7362bdcbc3f6e7c207e00d27e202f8d89f76",
    ("deepfm", 4): "f29e8a63f69cffdfb82aa9f62b231eee58d436080fdc93e0982a369e018e59ac",
}


# The families of the newer cells, each under the optimizer its registered
# configuration trains with; sha256 at commit 8c90096 (jax 0.9.0), CPU
# devices, taken before the guards that build them were rewritten.
_LOWERED_AT_PARENT.update({
    ("dcn", 1):
        "a6c69b8f01a087ab991e5fa8382d65aaef7da5866607cff8a6cc6116fb4127d1",
    ("dlrm", 1):
        "5f6bf469a5fed4c9641fe8a6480980ef8e3746881ff7bb32c4314e31cc618fe5",
    ("ffm_adagrad", 1):
        "f0f43ddbb0888fc49dcc22c485a478bcf4ea8eeca6add56d25f272b52c8f92da",
    ("xdeepfm", 1):
        "2e774be1025d3b26fdd1ed7d9c75b55f30074900ed5b853cae1cbd5ce7394103",
})
_PIN_HOTS = (3, 1, 2)
_PIN_CONFIGS = {
    "ffm_adagrad": TrainConfig(learning_rate=0.2, lr_schedule="constant",
                               optimizer="adagrad", reg_factors=2e-5,
                               adagrad_init_accumulator=2.0 ** -16),
    "dlrm": TrainConfig(learning_rate=1.0, lr_schedule="constant",
                        optimizer="sgd", reg_factors=0.0),
    "dcn": TrainConfig(learning_rate=0.004, lr_schedule="constant",
                       optimizer="adagrad", reg_factors=0.0,
                       adagrad_init_accumulator=0.0),
    "xdeepfm": TrainConfig(learning_rate=1e-3, lr_schedule="constant",
                           optimizer="adam", reg_factors=1e-4),
}


def _pin_spec(family):
    common = dict(num_features=_PIN_FIELDS * _PIN_BUCKET,
                  num_fields=_PIN_FIELDS, bucket=_PIN_BUCKET)
    if family == "fm":
        return models.FieldFMSpec(rank=64, **common)
    if family in ("ffm", "ffm_adagrad"):
        return models.FieldFFMSpec(rank=16, **common)
    if family == "dlrm":
        return models.FieldDLRMSpec(rank=8, dense_fields=2,
                                    bottom_mlp_dims=(16, 8),
                                    mlp_dims=(16, 16), **common)
    if family == "dcn":
        return models.FieldDCNSpec(
            num_features=(2 + len(_PIN_HOTS)) * _PIN_BUCKET, rank=8,
            num_fields=2 + sum(_PIN_HOTS), bucket=_PIN_BUCKET,
            dense_fields=2, hots=_PIN_HOTS, bottom_mlp_dims=(16, 8),
            cross_layers=2, cross_rank=4, mlp_dims=(16, 16))
    if family == "xdeepfm":
        return models.FieldXDeepFMSpec(rank=10, cin_layers=(8, 6, 4),
                                       mlp_dims=(16, 16), **common)
    return models.FieldDeepFMSpec(rank=16, mlp_dims=(32, 16), **common)


@pytest.mark.parametrize("family,chips", sorted(_LOWERED_AT_PARENT))
def test_step_lowers_to_the_parents_text(family, chips):
    import hashlib

    from fm_spark_tpu import sparse
    from fm_spark_tpu.parallel import lower_field_sharded_step

    spec = _pin_spec(family)
    config = _PIN_CONFIGS.get(family, _PIN_CONFIG)
    lowered = (
        sparse.lower_field_sparse_step(spec, config, _PIN_BATCH)
        if chips == 1 else
        lower_field_sharded_step(spec, config, make_field_mesh(chips),
                                 _PIN_BATCH))
    text = lowered.as_text()
    assert (hashlib.sha256(text.encode()).hexdigest()
            == _LOWERED_AT_PARENT[family, chips])


@pytest.mark.parametrize("chips", [4, 1])
def test_feed_placed_batch_lowers_the_step_as_a_loop_placed_one(chips):
    """The loop's own state (cli._place_field_state) and the batch both
    ways: placed as the parent's loop placed it (``jnp.asarray`` of the
    whole batch, then the re-sharding ``device_put`` on a mesh), and
    taken from the feed. Same avals, same shardings, same program."""
    from jax.sharding import NamedSharding

    from fm_spark_tpu import cli, sparse
    from fm_spark_tpu.data import Batches, wrap_prefetch
    from fm_spark_tpu.parallel import field_batch_specs

    spec = _pin_spec("fm")
    _, params, _, prep, _, mesh = cli._place_field_state(
        spec, _PIN_CONFIG, cli._FIELD_CAPS["FieldFMSpec"],
        spec.init(jax.random.key(3)), {}, chips, 1, chips > 1, 1, False,
        devices=jax.devices()[:chips])
    rng = np.random.default_rng(0)
    ids, vals, labels, _ = _make_batch(rng, 4 * _PIN_BATCH, _PIN_FIELDS,
                                       _PIN_BUCKET)
    by_loop = Batches(ids, vals, labels, _PIN_BATCH, seed=1).next_batch()
    if mesh is None:
        step = make_field_sparse_sgd_step(spec, _PIN_CONFIG)
        by_loop = tuple(map(jnp.asarray, by_loop))
    else:
        step = make_field_sharded_sgd_step(spec, _PIN_CONFIG, mesh)
        by_loop = tuple(
            jax.device_put(jnp.asarray(x), NamedSharding(mesh, s))
            for x, s in zip(pad_field_batch(by_loop, _PIN_FIELDS, chips),
                            field_batch_specs(mesh)))
    source, close = wrap_prefetch(
        Batches(ids, vals, labels, _PIN_BATCH, seed=1), 2, place=prep)
    try:
        by_feed = source.next_batch()
    finally:
        close()
    for a, b in zip(by_feed, by_loop):
        assert (a.shape, a.dtype, a.sharding) == (b.shape, b.dtype,
                                                  b.sharding)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    i = jnp.int32(0)
    assert (step.lower(params, i, *by_feed).as_text()
            == step.lower(params, i, *by_loop).as_text())
