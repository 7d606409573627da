"""Which levers a step serves is declared once, by its factory
(``sparse.declares``), and read by the factory itself and by the CLI
(``cli._validate_field_caps``) through ``sparse.refuse_unserved``. This
holds both to one answer: for every family of the capability table,
each layout it has and each lever set to a value that demands service,
the CLI refuses exactly when the factory it would build refuses."""

import jax
import pytest

from fm_spark_tpu import cli, models, sparse
from fm_spark_tpu.parallel import make_field_mesh
from fm_spark_tpu.train import TrainConfig

BUCKET, CAP, BATCH, CHIPS = 64, 32, 64, 4
COMMON = dict(num_features=4 * BUCKET, num_fields=4, bucket=BUCKET, rank=4)

# A small spec of each family and the optimizer its registered
# configuration trains with.
FAMILIES = {
    "FieldFMSpec": (lambda: models.FieldFMSpec(**COMMON), "sgd"),
    "FieldFFMSpec": (lambda: models.FieldFFMSpec(**COMMON), "sgd"),
    "FieldDeepFMSpec": (
        lambda: models.FieldDeepFMSpec(mlp_dims=(8, 8), **COMMON), "adam"),
    "FieldXDeepFMSpec": (
        lambda: models.FieldXDeepFMSpec(cin_layers=(4, 3), mlp_dims=(8,),
                                        **COMMON), "adam"),
    "FieldDLRMSpec": (
        lambda: models.FieldDLRMSpec(
            num_features=6 * BUCKET, num_fields=6, bucket=BUCKET, rank=4,
            dense_fields=2, bottom_mlp_dims=(8, 4), mlp_dims=(8,)), "sgd"),
    "FieldDCNSpec": (
        lambda: models.FieldDCNSpec(
            num_features=4 * BUCKET, num_fields=5, bucket=BUCKET, rank=4,
            dense_fields=2, hots=(2, 1), bottom_mlp_dims=(8, 4),
            cross_layers=1, cross_rank=2, mlp_dims=(8,)), "adagrad"),
}

# Each lever at a value that demands service, with what the lever needs
# beside it to be a coherent request (sparse._refuse_incoherent).
DEVICE_COMPACT = dict(compact_device=True, compact_cap=CAP,
                      sparse_update="dedup")
REQUESTS = {
    "host_dedup": dict(host_dedup=True, sparse_update="dedup"),
    "compact_cap": dict(host_dedup=True, compact_cap=CAP,
                        sparse_update="dedup"),
    "compact_device": DEVICE_COMPACT,
    "segtotal_pallas": dict(segtotal_pallas=True, **DEVICE_COMPACT),
    "use_pallas": dict(use_pallas=True),
    "gfull_fused": dict(gfull_fused=True),
    "sel_blocked": dict(sel_blocked=True),
    # The fused FM backward rides the compact update; the FFM kernels
    # mirror the sel-blocked body (sparse.fused_embed_plan).
    "fused_embed": dict(fused_embed="require", **DEVICE_COMPACT),
    "embed_tier": dict(embed_tier="require"),
    "collective_dtype": dict(collective_dtype="bfloat16"),
    "score_sharded": dict(score_sharded=True),
    "deep_sharded": dict(deep_sharded=True),
}

CASES = [
    (family, chips, lever)
    for family, cap in cli._FIELD_CAPS.items()
    for chips in ((1, CHIPS) if cap.sharded_step is not None else (1,))
    for lever in sorted(REQUESTS)
]


def test_every_lever_has_a_request_and_every_family_a_spec():
    assert sorted(REQUESTS) == sorted(sparse.LEVERS)
    assert sorted(FAMILIES) == sorted(cli._FIELD_CAPS)


@pytest.mark.parametrize("family,chips,lever", CASES)
def test_the_cli_refuses_what_the_factory_refuses(family, chips, lever):
    make_spec, optimizer = FAMILIES[family]
    spec = make_spec()
    request = dict(REQUESTS[lever])
    if lever == "fused_embed" and family == "FieldFFMSpec":
        request["sel_blocked"] = True
    config = TrainConfig(optimizer=optimizer, batch_size=BATCH,
                         learning_rate=0.01, lr_schedule="constant",
                         **request)
    cap = cli._FIELD_CAPS[family]
    sharded = chips > 1

    try:
        cli._validate_field_caps(spec, config, cap, chips, 1, sharded, 1, 1,
                                 False)
        cli_refusal = None
    except SystemExit as refused:
        cli_refusal = str(refused)
    try:
        if sharded:
            mesh = make_field_mesh(chips, devices=jax.devices()[:chips])
            cap.sharded_step(spec, config, mesh)
        else:
            cap.single_step(spec, config)
        factory_refusal = None
    except ValueError as refused:
        factory_refusal = str(refused)
    assert (cli_refusal is None) == (factory_refusal is None), (
        cli_refusal, factory_refusal)


@pytest.mark.parametrize("family", ["FieldFMSpec", "FieldFFMSpec",
                                    "FieldDeepFMSpec"])
def test_a_mesh_factory_refuses_a_required_tier(family):
    """The mesh factories keep their tables in HBM: asked to require the
    tiered store, they refuse, as the CLI did for them."""
    make_spec, optimizer = FAMILIES[family]
    config = TrainConfig(optimizer=optimizer, batch_size=BATCH,
                         embed_tier="require")
    mesh = make_field_mesh(CHIPS, devices=jax.devices()[:CHIPS])
    with pytest.raises(ValueError, match="TieredTrainer"):
        cli._FIELD_CAPS[family].sharded_step(make_spec(), config, mesh)
