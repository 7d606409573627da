"""FieldFMSpec: layout equivalence with the flat FM and fused-step parity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fm_spark_tpu import models
from fm_spark_tpu.sparse import make_field_sparse_sgd_step, make_sparse_sgd_step
from fm_spark_tpu.train import TrainConfig


F, BUCKET, K, B = 5, 32, 4, 16


@pytest.fixture(params=[True, False], ids=["fused", "split"])
def field_spec(request):
    return models.FieldFMSpec(
        num_features=F * BUCKET, rank=K, num_fields=F, bucket=BUCKET,
        init_std=0.1, fused_linear=request.param,
    )


@pytest.fixture
def batch(rng):
    ids = rng.integers(0, BUCKET, size=(B, F)).astype(np.int32)
    vals = rng.normal(size=(B, F)).astype(np.float32)
    labels = rng.integers(0, 2, B).astype(np.float32)
    return jnp.asarray(ids), jnp.asarray(vals), jnp.asarray(labels)


def test_scores_match_flat_fm(field_spec, batch):
    ids, vals, _ = batch
    params = field_spec.init(jax.random.key(0))
    flat = field_spec.flat_spec()
    flat_params = field_spec.to_flat_params(params)
    gids = field_spec.to_global_ids(ids)
    np.testing.assert_allclose(
        field_spec.scores(params, ids, vals),
        flat.scores(flat_params, gids, vals),
        rtol=1e-5, atol=1e-6,
    )


def test_field_sparse_step_matches_flat_sparse_step(field_spec, batch):
    ids, vals, labels = batch
    config = TrainConfig(learning_rate=0.2, lr_schedule="inv_sqrt",
                         optimizer="sgd")
    params = field_spec.init(jax.random.key(1))
    # Deep copy: both steps donate their inputs, and to_flat_params shares
    # the w0 buffer with the field params.
    flat_params = jax.tree_util.tree_map(
        jnp.copy, field_spec.to_flat_params(params)
    )
    fstep = make_field_sparse_sgd_step(field_spec, config)
    sstep = make_sparse_sgd_step(field_spec.flat_spec(), config)
    w = jnp.ones((B,))
    gids = field_spec.to_global_ids(ids)
    for i in range(3):
        params, loss_f = fstep(params, jnp.int32(i), ids, vals, labels, w)
        flat_params, loss_s = sstep(flat_params, jnp.int32(i), gids, vals, labels, w)
        np.testing.assert_allclose(float(loss_f), float(loss_s), rtol=1e-6)
    merged = field_spec.to_flat_params(params)
    for key in ("w0", "w", "v"):
        np.testing.assert_allclose(
            np.asarray(merged[key]), np.asarray(flat_params[key]),
            rtol=1e-5, atol=1e-6, err_msg=key,
        )


def test_field_fm_wrong_slots_raises(field_spec, rng):
    params = field_spec.init(jax.random.key(0))
    ids = jnp.zeros((4, F + 1), jnp.int32)
    vals = jnp.ones((4, F + 1))
    with pytest.raises(ValueError, match="fields"):
        field_spec.scores(params, ids, vals)


def test_field_fm_save_load(tmp_path, field_spec, batch):
    ids, vals, _ = batch
    params = field_spec.init(jax.random.key(2))
    models.save_model(str(tmp_path / "m"), field_spec, params)
    spec2, params2 = models.load_model(str(tmp_path / "m"))
    assert spec2 == field_spec
    np.testing.assert_allclose(
        field_spec.scores(params, ids, vals), spec2.scores(params2, ids, vals),
        rtol=1e-6,
    )


@pytest.mark.parametrize("stored,loads", [("row", True), ("col", False)])
def test_field_fm_loads_spec_json_of_before_table_layout_went(
        tmp_path, field_spec, batch, stored, loads):
    """Every FieldFM model saved until PR 31 carries ``"table_layout":
    "row"`` in its ``spec.json`` and must load and score as it did; the
    transposed storage went, and a ``"col"`` model is refused by the
    key's name rather than by a dataclass's TypeError."""
    import json

    ids, vals, _ = batch
    params = field_spec.init(jax.random.key(2))
    path = tmp_path / "m"
    models.save_model(str(path), field_spec, params)
    meta = json.loads((path / "spec.json").read_text())
    assert "table_layout" not in meta["spec"]
    meta["spec"]["table_layout"] = stored
    (path / "spec.json").write_text(json.dumps(meta))
    if not loads:
        with pytest.raises(ValueError, match="table_layout.*'col'"):
            models.load_model(str(path))
        return
    spec2, params2 = models.load_model(str(path))
    assert spec2 == field_spec
    np.testing.assert_array_equal(
        np.asarray(field_spec.scores(params, ids, vals)),
        np.asarray(spec2.scores(params2, ids, vals)))


def test_field_fm_validation():
    with pytest.raises(ValueError, match="num_fields"):
        models.FieldFMSpec(num_features=100, rank=2, num_fields=0, bucket=10)
    with pytest.raises(ValueError, match="must equal"):
        models.FieldFMSpec(num_features=99, rank=2, num_fields=5, bucket=10)
