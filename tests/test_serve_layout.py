"""The form a scorer holds its tables in (models/rows.py).

On the TPU a default-placed tall narrow table is dimension-0-minor and a
predict program copies all of it per dispatch; ``PredictEngine`` installs
a generation packed (65 -> two rows a 128-lane line, 17 -> eight), padded
(369 -> 384) or as it is, by what the device's compiler says of the
table's shape. The CPU lays every table out row-major, so the engine
packs nothing here: these tests make the CPU answer the layout question
as the chip does (``chip_defaults``) and hold the served tree to the
canonical one BITWISE. What the chip's compiler makes of the served
programs is read in tests/test_table_layout.py, beside the other
compiles for a described v5e (one file: one worker loads libtpu).
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fm_spark_tpu import models, obs, sparse
from fm_spark_tpu.checkpoint import Checkpointer
from fm_spark_tpu.models import rows as rows_lib
from fm_spark_tpu.models.rows import PackedTable
from fm_spark_tpu.serve import PredictEngine, ReloadFollower
from fm_spark_tpu.train import TrainConfig

BUCKET = 512

# name -> (spec, the form of each leaf under spec.row_tables, in order)
CASES = {
    "fm_65_fused": (
        lambda: models.FieldFMSpec(num_features=4 * BUCKET, num_fields=4,
                                   bucket=BUCKET, rank=64, init_std=0.1),
        ["packed"] * 4),
    "fm_64_and_w": (
        lambda: models.FieldFMSpec(num_features=4 * BUCKET, num_fields=4,
                                   bucket=BUCKET, rank=64, init_std=0.1,
                                   fused_linear=False),
        ["packed"] * 4 + ["as_is"] * 4),
    "ffm_369": (
        lambda: models.FieldFFMSpec(num_features=23 * BUCKET, num_fields=23,
                                    bucket=BUCKET, rank=16, init_std=0.1),
        ["padded"] * 23),
    "deepfm_17": (
        lambda: models.FieldDeepFMSpec(num_features=4 * BUCKET, num_fields=4,
                                       bucket=BUCKET, rank=16, init_std=0.1,
                                       mlp_dims=(32, 16)),
        ["packed"] * 4),
}


def chip_row_major(shape, dtype, device):
    """The v5e's answer: a table is row-major by default if it is a
    vector, wider than tall, or a whole number of lanes wide."""
    return (len(shape) < 2 or shape[0] <= shape[1]
            or shape[1] % rows_lib.LANES == 0)


@pytest.fixture
def chip_defaults(monkeypatch):
    monkeypatch.setattr(rows_lib, "default_is_row_major", chip_row_major)


def install(spec, params):
    """The scorer's call of the walk (PredictEngine._install)."""
    return rows_lib.hold(params, spec.row_tables, writes=False)


def _params(spec, seed=0):
    """``spec.init`` with the linear weights drawn too (they start at
    zero, and a zero column read from the wrong lane is still zero)."""
    params = spec.init(jax.random.key(seed))
    key = jax.random.key(seed + 100)
    if "w" in params:
        params["w"] = [jax.random.normal(jax.random.fold_in(key, f), w.shape)
                       for f, w in enumerate(params["w"])]
    else:
        params["vw"] = [
            t.at[:, -1].set(jax.random.normal(jax.random.fold_in(key, f),
                                              t.shape[:1]))
            for f, t in enumerate(params["vw"])]
    return params


def _batch(spec, n=64, seed=0):
    """Ids with the edges in them: 0, 1, the last row, odd and even
    neighbours (the two halves of one line), a repeated id."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, spec.bucket, (n, spec.num_fields)).astype(np.int32)
    ids[0], ids[1], ids[2] = 0, 1, spec.bucket - 1
    ids[3], ids[4], ids[5] = 6, 7, 8
    ids[6] = ids[7] = ids[8]
    vals = rng.random((n, spec.num_fields)).astype(np.float32)
    return ids, vals


def _predict(spec, params, ids, vals):
    return np.asarray(jax.jit(spec.predict)(params, ids, vals))


def _assert_same_scores(case, got, want):
    """BITWISE, but for the DeepFM case compiled on the CPU: with the
    reader's select fused into its consumers XLA's CPU backend contracts
    one multiply-add of the interaction otherwise and a score moves by
    an ulp (the rows read are bitwise the same, and so is the model run
    op by op: test_served_tree_predicts_bitwise)."""
    if case == "deepfm_17":
        np.testing.assert_array_max_ulp(got, want, maxulp=2)
    else:
        assert np.array_equal(got, want)


def _forms(spec, served, params):
    out = []
    for key in spec.row_tables:
        for leaf, was in zip(served[key], params[key]):
            out.append("packed" if isinstance(leaf, PackedTable)
                       else "padded" if leaf.shape != was.shape
                       else "as_is")
    return out


@pytest.mark.parametrize("case", CASES)
def test_served_tree_predicts_bitwise(chip_defaults, case):
    make, forms = CASES[case]
    spec = make()
    params = _params(spec)
    served, shapes, held = install(spec, params)
    assert _forms(spec, served, params) == forms
    assert [held[f"tables_{f}"] for f in rows_lib.FORMS] == [
        forms.count(f) for f in rows_lib.FORMS]
    ids, vals = _batch(spec)
    # The reader hands the model the same rows ...
    width = spec.table_width
    for got, want in zip(jax.jit(spec.gather_rows)(served, ids),
                         jax.jit(spec.gather_rows)(params, ids)):
        assert np.array_equal(np.asarray(got[:, :width]), np.asarray(want))
    # ... so the model's own arithmetic, op by op, gives the same bits,
    with jax.disable_jit():
        want = np.asarray(spec.predict(params, ids, vals))
        assert np.array_equal(np.asarray(spec.predict(served, ids, vals)),
                              want)
    assert want.std() > 1e-3                      # the model says something
    # ... and so does the compiled program.
    _assert_same_scores(case, _predict(spec, served, ids, vals),
                        _predict(spec, params, ids, vals))


@pytest.mark.parametrize("holder", ["scorer", "training_loop"])
@pytest.mark.parametrize("case", CASES)
def test_unpack_is_the_way_back(chip_defaults, case, holder):
    """A tree held by the reader's walk (packed, padded) and one held by
    the writer's (padded only, and only the fused bodies' ``vw``, its
    sources consumed) come back bit-identical through the one way
    back."""
    spec = CASES[case][0]()
    params = _params(spec)
    host = [np.asarray(leaf).copy() for leaf in jax.tree.leaves(params)]
    if holder == "scorer":
        held, shapes, report = install(spec, params)
        # The caller's arrays are not consumed.
        assert not any(leaf.is_deleted()
                       for leaf in jax.tree.leaves(params))
    else:
        held, shapes, report = rows_lib.hold(
            params, sparse.FUSED_TABLE_KEYS, writes=True, consume=True)
        assert report["tables_packed"] == 0
        assert report["tables_padded"] == len(params.get("vw", ()))
        # Each table that changed form is gone; nothing else is.
        assert {key for key, leaves in params.items()
                if any(leaf.is_deleted() for leaf in jax.tree.leaves(leaves))
                } == ({"vw"} if "vw" in params else set())
    assert report["resident_table_bytes"] == sum(
        leaf.nbytes for leaf in jax.tree.leaves(held))
    assert jax.tree.structure(shapes) == jax.tree.structure(params)
    for release in (False, True):
        back = rows_lib.canonical(held, shapes, release=release)
        assert jax.tree.structure(back) == jax.tree.structure(params)
        for got, shape, want in zip(jax.tree.leaves(back),
                                    jax.tree.leaves(shapes), host):
            assert (shape.shape, shape.dtype) == (want.shape, want.dtype)
            assert np.array_equal(np.asarray(got), want)
    # Released: every table that had changed form went as it came back.
    changed = report["tables_packed"] + report["tables_padded"]
    assert sum(any(part.is_deleted() for part in jax.tree.leaves(leaf))
               for leaf in jax.tree.leaves(
                   held, is_leaf=lambda x: isinstance(x, PackedTable))
               ) == changed


@pytest.mark.parametrize("width,p", [
    (65, 64), (64, 64), (17, 16), (16, 16), (33, 32), (9, 8),
    (369, 0), (66, 0), (24, 0), (128, 0), (129, 0), (200, 0)])
def test_packed_columns(width, p):
    assert rows_lib.packed_columns(width) == p


@pytest.mark.parametrize("shape,form,writes", [
    ((4096, 65), "packed", False),     # config 3
    ((4096, 64), "packed", False),     # its factors alone
    ((4096, 17), "packed", False),     # config 5
    ((4096, 369), "padded", False),    # avazu: 384 / 369 - 1 = 4%
    ((4096, 120), "padded", False),    # 128 / 120 - 1 = 6.7%
    ((4096, 113), "as_is", False),     # 13.3% over, 49 columns left over
    ((4096, 66), "as_is", False),      # two columns left over
    ((4100, 17), "as_is", False),      # 4100 * 16: no whole number of lines
    ((4096, 128), "as_is", False),     # whole lanes
    ((65, 4096), "as_is", False),      # wider than tall
    ((4096,), "as_is", False),         # a linear weight vector
    # The same question from a holder that WRITES its tables (the
    # training loop): whole lanes whatever they cost, never packed.
    ((4096, 65), "padded", True),      # fm_r64.train
    ((4096, 369), "padded", True),     # ffm_r16.train
    ((4096, 17), "padded", True),      # deepfm_r16.train: 7.5x the bytes
    ((4096, 113), "padded", True),
    ((4096, 128), "as_is", True),
    ((65, 4096), "as_is", True),
    ((4096,), "as_is", True),
])
def test_serving_form_by_shape(monkeypatch, shape, form, writes):
    # Where the device lays everything out row-major, nothing changes.
    assert rows_lib.held_form(shape, jnp.float32, jax.devices()[0],
                              writes) == "as_is"
    monkeypatch.setattr(rows_lib, "default_is_row_major", chip_row_major)
    assert rows_lib.held_form(shape, jnp.float32, None, writes) == form


def test_unpackable_width_keeps_its_array(chip_defaults):
    spec = models.FieldFMSpec(num_features=4 * BUCKET, num_fields=4,
                              bucket=BUCKET, rank=65, init_std=0.1)
    params = _params(spec)
    served, shapes, held = install(spec, params)
    assert held["tables_as_is"] == 4 and held["tables_packed"] == 0
    for got, want in zip(served["vw"], params["vw"]):
        assert isinstance(got, jax.Array) and got.shape == (BUCKET, 66)
        assert got.unsafe_buffer_pointer() == want.unsafe_buffer_pointer()
    with pytest.raises(ValueError, match="does not pack"):
        PackedTable.pack(params["vw"][0])


def test_flat_tables_keep_todays_path(chip_defaults):
    """A flat ``[N, k]`` table is read through ops/fm: not a row table
    of this sense, whatever its shape."""
    spec = models.FMSpec(num_features=4 * BUCKET, rank=64)
    assert spec.row_tables == ()
    params = spec.init(jax.random.key(0))
    served, shapes, held = install(spec, params)
    assert [held[f"tables_{f}"] for f in rows_lib.FORMS] == [0, 0, 0]
    assert not any(isinstance(leaf, PackedTable) for leaf in
                   jax.tree.leaves(served, is_leaf=lambda x:
                                   isinstance(x, PackedTable)))
    for got, want in zip(jax.tree.leaves(served), jax.tree.leaves(params)):
        assert got.shape == want.shape


def test_out_of_range_ids_clamp_to_the_table_edge():
    table = jnp.arange(BUCKET * 65, dtype=jnp.float32).reshape(BUCKET, 65)
    ids = jnp.asarray([BUCKET, BUCKET + 1, 2 ** 30, BUCKET - 1], jnp.int32)
    got = rows_lib.gather(PackedTable.pack(table), ids)
    assert np.array_equal(np.asarray(got), np.asarray(table[ids]))


@pytest.mark.parametrize("case", ["fm_65_fused", "deepfm_17"])
def test_packed_read_is_traced_once_for_all_fields(chip_defaults, case):
    """Warm-up traces ``predict`` once a bucket; with the packed read
    written out per field that took twice the canonical program's time
    (PERF.md §6, PR 30). It sits under one inner ``jax.jit``: F calls
    of ONE traced body, which XLA inlines (tests/test_table_layout.py
    reads the compiled program)."""
    spec = CASES[case][0]()
    served, _, _ = install(spec, _params(spec))
    ids, vals = _batch(spec, 16)
    eqns = jax.make_jaxpr(spec.predict)(served, ids, vals).jaxpr.eqns
    reads = [e for e in eqns if e.params.get("name") == "gather"]
    assert len(reads) == spec.num_fields
    assert len({id(e.params["jaxpr"]) for e in reads}) == 1


# ------------------------------------------------------------- the engine


def _gauges():
    return [int(obs.registry().gauge(f"serve/tables_{f}").value)
            for f in rows_lib.FORMS]


@pytest.mark.parametrize("case", CASES)
def test_engine_installs_the_serving_form(chip_defaults, case):
    make, forms = CASES[case]
    spec = make()
    params = _params(spec)
    before = [np.asarray(leaf).copy() for leaf in jax.tree.leaves(params)]
    eng = PredictEngine(spec, params, buckets=(8, 64))
    try:
        eng.warmup()
        gen = eng.generation()
        assert _forms(spec, gen.params, params) == forms
        assert _gauges() == [forms.count(f) for f in rows_lib.FORMS]
        assert (obs.registry().gauge("serve/resident_table_bytes").value
                == gen.held["resident_table_bytes"] > 0)
        ids, vals = _batch(spec, 64)
        for n in (1, 8, 50):
            _assert_same_scores(case, eng.score(ids[:n], vals[:n]),
                                _predict(spec, params, ids[:n], vals[:n]))
        # The caller's arrays are alive and unchanged.
        for leaf, was in zip(jax.tree.leaves(params), before):
            assert not leaf.is_deleted()
            assert np.array_equal(np.asarray(leaf), was)
    finally:
        eng.close()


def test_engine_on_the_cpu_holds_tables_as_they_are():
    spec = CASES["fm_65_fused"][0]()
    params = _params(spec)
    eng = PredictEngine(spec, params, buckets=(8,))
    try:
        assert _gauges() == [0, 0, 4]
        for got, want in zip(eng.generation().params["vw"], params["vw"]):
            assert got.unsafe_buffer_pointer() == want.unsafe_buffer_pointer()
    finally:
        eng.close()


def test_swap_installs_the_serving_form_while_a_batch_finishes_on_the_old(
        chip_defaults):
    """A batch in flight holds the old generation's reference and is
    answered from it; the swap puts the new one into serving form first
    and the next batch reads it."""
    spec = CASES["fm_65_fused"][0]()
    old, new = _params(spec, 0), _params(spec, 1)
    eng = PredictEngine(spec, old, buckets=(64,), latency_budget_ms=0.0)
    eng.warmup()
    ids, vals = _batch(spec)
    gen0 = eng.generation()
    entered, release = threading.Event(), threading.Event()
    compiled = eng._compiled[64]

    def slow(params, i, v):
        entered.set()
        assert release.wait(30)
        return compiled(params, i, v)

    try:
        eng._compiled[64] = slow
        fut = eng.submit(ids, vals)
        assert entered.wait(30)                   # the batch is in flight
        gen1 = eng.swap_generation(new, step=9)
        assert eng.generation() is gen1 and gen1.gen_id == gen0.gen_id + 1
        assert all(isinstance(t, PackedTable) for t in gen1.params["vw"])
        assert _gauges() == [4, 0, 0]
        release.set()
        assert np.array_equal(fut.result(30), _predict(spec, old, ids, vals))
        eng._compiled[64] = compiled
        assert np.array_equal(eng.predict(ids, vals),
                              _predict(spec, new, ids, vals))
        assert not any(leaf.is_deleted() for leaf in
                       jax.tree.leaves((old, new)))
    finally:
        release.set()
        eng.close()


def test_reload_follower_restores_into_canonical_shapes(chip_defaults,
                                                        tmp_path):
    """The follower's ``params_example`` is the canonical tree's shapes,
    not the packed tree the engine serves: a chain generation restores
    into it and is installed packed."""
    spec = CASES["fm_65_fused"][0]()
    params, newer = _params(spec, 0), _params(spec, 1)
    ck = Checkpointer(str(tmp_path / "chain"), save_every=1,
                      async_save=False)
    ck.save(7, newer, {}, None, force=True)
    ck.close()
    eng = PredictEngine(spec, params, buckets=(64,), latency_budget_ms=0.0)
    eng.warmup()
    fol = ReloadFollower(eng, str(tmp_path / "chain"), poll_s=0.05,
                         opt_state_example={})
    try:
        example = fol._params_example
        assert jax.tree.structure(example) == jax.tree.structure(params)
        for shape, leaf in zip(jax.tree.leaves(example),
                               jax.tree.leaves(params)):
            assert isinstance(shape, jax.ShapeDtypeStruct)
            assert (shape.shape, shape.dtype) == (leaf.shape, leaf.dtype)
        assert fol.poll_once() == "swapped"
        gen = eng.generation()
        assert gen.step == 7
        assert all(isinstance(t, PackedTable) for t in gen.params["vw"])
        ids, vals = _batch(spec)
        assert np.array_equal(eng.score(ids, vals),
                              _predict(spec, newer, ids, vals))
    finally:
        fol.stop()
        eng.close()


# ---------------------------------------------------- training is untouched


@pytest.mark.parametrize("case", CASES)
def test_training_step_lowers_to_the_text_it_had(monkeypatch, case):
    """The specs' row reader chooses by the leaf's type while tracing:
    a one-chip training step lowered with plain tables is, op for op,
    the step lowered with ``table[ids]`` written out."""
    spec = CASES[case][0]()
    config = TrainConfig(learning_rate=0.05, lr_schedule="constant",
                         optimizer="sgd", reg_factors=1e-6)

    def text():
        return sparse.lower_field_sparse_step(spec, config, 64, 1).as_text()

    with_reader = text()
    monkeypatch.setattr(rows_lib, "gather", lambda table, ids: table[ids])
    assert with_reader == text()
