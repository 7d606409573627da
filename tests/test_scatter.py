"""ops/scatter.py: dedup ≡ scatter_add, SR unbiasedness, bf16+SR quality."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fm_spark_tpu import models, sparse
from fm_spark_tpu.ops import scatter
from fm_spark_tpu.ops.scatter import apply_row_updates, stochastic_round
from fm_spark_tpu.sparse import make_field_sparse_sgd_step
from fm_spark_tpu.train import TrainConfig


def test_dedup_matches_scatter_add_fp32():
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(size=(50, 8)), jnp.float32)
    # Heavy duplication, including ids unseen in the batch.
    ids = jnp.asarray(rng.integers(0, 20, size=200), jnp.int32)
    delta = jnp.asarray(rng.normal(size=(200, 8)) * 0.1, jnp.float32)
    a = apply_row_updates(table, ids, delta, mode="scatter_add")
    b = apply_row_updates(table, ids, delta, mode="dedup")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-6)


def test_dedup_sr_exact_in_fp32():
    # With an fp32 table SR is the identity, so dedup_sr must equal
    # scatter_add exactly up to reassociation.
    rng = np.random.default_rng(1)
    table = jnp.asarray(rng.normal(size=(30, 4)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, 30, size=100), jnp.int32)
    delta = jnp.asarray(rng.normal(size=(100, 4)) * 0.05, jnp.float32)
    old_rows = table[ids]
    a = apply_row_updates(table, ids, delta, mode="scatter_add")
    c = apply_row_updates(table, ids, delta, mode="dedup_sr",
                          key=jax.random.key(0), old_rows=old_rows)
    np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                               rtol=1e-5, atol=1e-6)


def test_stochastic_round_unbiased_and_lands_small_updates():
    # A delta far below bf16 ulp of 1.0 must land in expectation.
    x = jnp.full((20000,), 1.0 + 1e-4, jnp.float32)  # ulp(1.0)=2^-8
    out = stochastic_round(x, jnp.bfloat16, jax.random.key(0))
    mean = float(jnp.mean(out.astype(jnp.float32)))
    # P(round up) = 1e-4 / 2^-8 ≈ 0.0256 → mean ≈ 1.0 + 1e-4.
    assert abs(mean - (1.0 + 1e-4)) < 3e-5, mean
    # Deterministic rounding would give exactly 1.0.
    assert mean > 1.0


def test_stochastic_round_fp32_identity():
    x = jnp.asarray(np.random.default_rng(0).normal(size=64), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(stochastic_round(x, jnp.float32, jax.random.key(0))),
        np.asarray(x),
    )


def test_stochastic_round_nonfinite_and_near_max():
    # Non-finite inputs must propagate unchanged (the raw bit-add would
    # corrupt NaN payloads / inf encodings), and finite values near bf16
    # max must saturate instead of carrying over into inf.
    key = jax.random.key(0)
    bf_max = float(jnp.finfo(jnp.bfloat16).max)
    x = jnp.asarray([np.inf, -np.inf, np.nan, bf_max, -bf_max, 1.0],
                    jnp.float32)
    out = stochastic_round(x, jnp.bfloat16, key)
    o = np.asarray(out, np.float32)
    assert o[0] == np.inf and o[1] == -np.inf and np.isnan(o[2])
    assert np.isfinite(o[3]) and np.isfinite(o[4]), o
    assert o[3] == bf_max and o[4] == -bf_max
    # Bulk check: f32 values strictly between bf16-max and the next
    # exponent (the mantissa carry range) never round to inf under any
    # noise draw — they saturate.
    big = jnp.full((4096,), np.float32(bf_max) * np.float32(1.001),
                   jnp.float32)
    assert float(big[0]) > bf_max and np.isfinite(float(big[0]))
    outs = stochastic_round(big, jnp.bfloat16, jax.random.key(7))
    assert np.isfinite(np.asarray(outs, np.float32)).all()


def test_unknown_mode_raises():
    t = jnp.zeros((4, 2))
    with pytest.raises(ValueError, match="unknown sparse_update"):
        apply_row_updates(t, jnp.zeros(3, jnp.int32), jnp.zeros((3, 2)),
                          mode="nope")
    with pytest.raises(ValueError, match="needs key"):
        apply_row_updates(t, jnp.zeros(3, jnp.int32), jnp.zeros((3, 2)),
                          mode="dedup_sr")


def test_fused_step_dedup_matches_scatter_add():
    num_fields, bucket, rank = 4, 32, 4
    spec = models.FieldFMSpec(
        num_features=num_fields * bucket, rank=rank, num_fields=num_fields,
        bucket=bucket, init_std=0.1,
    )
    base = TrainConfig(learning_rate=0.3, optimizer="sgd",
                       reg_factors=1e-3, reg_linear=1e-4)
    import dataclasses

    step_a = make_field_sparse_sgd_step(spec, base)
    step_b = make_field_sparse_sgd_step(
        spec, dataclasses.replace(base, sparse_update="dedup")
    )
    pa = spec.init(jax.random.key(0))
    pb = jax.tree_util.tree_map(jnp.copy, pa)
    rng = np.random.default_rng(2)
    for i in range(3):
        ids = jnp.asarray(rng.integers(0, bucket, size=(64, num_fields)),
                          jnp.int32)
        vals = jnp.asarray(rng.uniform(0.5, 1.5, (64, num_fields)),
                           jnp.float32)
        labels = jnp.asarray(rng.integers(0, 2, 64), jnp.float32)
        w = jnp.ones((64,), jnp.float32)
        pa, la = step_a(pa, jnp.int32(i), ids, vals, labels, w)
        pb, lb = step_b(pb, jnp.int32(i), ids, vals, labels, w)
        np.testing.assert_allclose(float(la), float(lb), rtol=1e-6)
    for f in range(num_fields):
        np.testing.assert_allclose(
            np.asarray(pa["vw"][f]), np.asarray(pb["vw"][f]),
            rtol=1e-4, atol=1e-6,
        )


def test_update_rows_add_matches_scatter_add_on_duplicate_ids():
    """ISSUE 8 property test: the Pallas unique-row RMW
    (ops/pallas_fm.update_rows_add), fed the deduped per-segment sums a
    fused step would feed it, writes EXACTLY the table the plain
    scatter-add reference produces — on duplicate-heavy batches, the
    dedup/dedup_sr variants' exact aliasing case. Integer-valued deltas
    make both paths' sums exact, so equality is bitwise, not tolerance
    (any aliasing bug — a duplicate id written twice, a dropped
    segment — shifts a row by >= 1.0)."""
    from fm_spark_tpu.ops import pallas_fm
    from fm_spark_tpu.ops.scatter import _dedup

    for seed in range(5):
        rng = np.random.default_rng(seed)
        b = 256
        n_rows = int(rng.integers(8, 64))
        w = int(rng.integers(2, 10))
        table = jnp.asarray(
            rng.integers(-50, 50, size=(n_rows, w)).astype(np.float32))
        # Zipf-heavy duplication: many batch lanes alias few rows.
        ids = jnp.asarray(rng.zipf(1.2, size=b) % n_rows, jnp.int32)
        delta = jnp.asarray(
            rng.integers(-8, 8, size=(b, w)).astype(np.float32))

        want = apply_row_updates(table, ids, delta, mode="scatter_add")

        # The fused-step feed: segment-sum duplicates, then one
        # unique-lane Pallas RMW (bench_kernels' update family).
        sid, summed, run_start, _order = jax.jit(_dedup)(ids, delta)
        uids = jnp.where(run_start, sid, 0)
        valid = run_start.astype(jnp.int32)
        got = pallas_fm.update_rows_add(
            jnp.copy(table), uids, valid, summed, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(want),
            err_msg=f"seed={seed} rows={n_rows} w={w}")


def test_compact_apply_totals_matches_compact_apply_write():
    """The fused backward's write half (compact_apply_totals) against
    compact_apply fed the same totals through its own segment-sum: the
    two entrances to _compact_write must land identical tables (dedup)
    and identical SR draws (dedup_sr), or the fused path would fork the
    update semantics."""
    from fm_spark_tpu.ops.scatter import (
        compact_apply,
        compact_apply_totals,
        compact_aux,
        compact_gather,
        sr_key,
    )

    rng = np.random.default_rng(7)
    b, n_rows, w, cap = 512, 40, 6, 48
    ids = rng.integers(0, n_rows, size=(b, 1)).astype(np.int32)
    aux = compact_aux(ids, cap)
    caux = tuple(jnp.asarray(a[0]) for a in aux)
    useg, _, _, order, inv = caux
    table = jnp.asarray(
        rng.integers(-20, 20, size=(n_rows, w)).astype(np.float32))
    delta = jnp.asarray(
        rng.integers(-4, 4, size=(b, w)).astype(np.float32))
    urows = compact_gather(table, useg)

    # Totals exactly as the fused backward emits them: per-segment sums
    # of the sorted deltas (integer-valued, so the sum path is exact).
    sdelta = np.asarray(delta)[np.asarray(order)]
    seg = np.asarray(inv)[np.asarray(order)]
    totals = np.zeros((cap, w), np.float32)
    np.add.at(totals, seg, sdelta)
    totals = jnp.asarray(totals)

    a = compact_apply(table, delta, caux, "dedup", None, urows)
    t = compact_apply_totals(table, totals, caux, "dedup", None, urows)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(t))

    key = sr_key(jax.random.key(3), 0, 0)
    a = compact_apply(table, delta, caux, "dedup_sr", key, urows)
    t = compact_apply_totals(table, totals, caux, "dedup_sr", key, urows)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(t))


# --------------------------------------------- the coalesced scatter_add
#
# apply_row_updates' default write coalesces a field's rows before it
# adds them, where ops/scatter.update_lanes says the lanes pay for it.


def _coalesced_case(name):
    """``(table, ids, delta)`` of 64 lanes; with RULE_CHUNK at 16 that is
    four chunks at most."""
    rng = np.random.default_rng(5)
    n_rows, w, b = 200, 8, 64
    table = rng.normal(size=(n_rows, w)).astype(np.float32)
    delta = (rng.normal(size=(b, w)) * 0.1).astype(np.float32)
    ids = rng.integers(0, 6, size=b)                    # heavy duplicates
    if name == "all_unique":
        ids = rng.permutation(n_rows)[:b]               # four whole chunks
    elif name == "drop_sentinels":
        # The 2-D mesh's: non-owned lanes at the table's edge (and one
        # far past it), among real duplicates.
        ids = np.where(rng.random(b) < 0.4, n_rows, ids)
        ids[7] = 2**31 - 1 - b                          # coalesce's first
    elif name == "lane_padded":
        table = np.pad(table, ((0, 0), (0, 120)))       # 8 columns in 128
    elif name == "bfloat16":
        table = np.asarray(jnp.asarray(table, jnp.bfloat16))
    elif name == "as_is":
        # DLRM's: a row IS a lane tile and the delta is as wide, so
        # _to_table_width pads nothing.
        table = rng.normal(size=(n_rows, 128)).astype(np.float32)
        delta = (rng.normal(size=(b, 128)) * 0.1).astype(np.float32)
    return jnp.asarray(table), jnp.asarray(ids, jnp.int32), jnp.asarray(delta)


@pytest.mark.parametrize("name", ["duplicates", "all_unique",
                                  "drop_sentinels", "lane_padded",
                                  "bfloat16", "as_is"])
def test_coalesced_add_is_the_plain_add(monkeypatch, name):
    monkeypatch.setattr(scatter, "RULE_CHUNK", 16)
    # as_is engages through the ROWS clause: more lanes than the lane
    # clause takes, and no more than a third of the table's rows.
    monkeypatch.setattr(scatter, "COALESCE_MAX_LANES",
                        32 if name == "as_is" else 64)
    monkeypatch.setattr(scatter, "PLAIN_DEAR_ROWS_PER_LANE",
                        3 if name == "as_is" else 4)
    table, ids, delta = _coalesced_case(name)
    assert scatter.update_lanes(ids.shape[0], table.shape) == 16
    got = np.asarray(
        apply_row_updates(table, ids, delta, mode="scatter_add"),
        np.float32)
    padded = jnp.pad(delta, ((0, 0), (0, table.shape[1] - delta.shape[1])))
    plain = np.asarray(
        table.at[ids].add(padded.astype(table.dtype), mode="drop"),
        np.float32)
    if name == "bfloat16":
        # Rounded once a row where the plain add rounds once an
        # occurrence: at least as near the float32 answer.
        exact = np.asarray(
            table.astype(jnp.float32).at[ids].add(delta, mode="drop"))
        assert np.abs(got - exact).sum() <= np.abs(plain - exact).sum()
        np.testing.assert_allclose(got, exact, rtol=2**-7, atol=1e-3)
    else:
        np.testing.assert_allclose(got, plain, rtol=1e-5, atol=1e-6)
    if name == "lane_padded":
        assert not got[:, delta.shape[1]:].any()    # padding: exactly zero
    untouched = np.setdiff1d(np.arange(table.shape[0]), np.asarray(ids))
    np.testing.assert_array_equal(
        got[untouched], np.asarray(table, np.float32)[untouched])


def _argsort_then_gather(ids):
    """What ``scatter._sorted_ids`` replaced (until PR 39), as it stood
    in ``coalesce``, ``_dedup`` and ``device_compact_aux``."""
    order = jnp.argsort(ids)
    sid = ids[order]
    return sid, order


_SORTED_IDS_LANES, _SORTED_IDS_ROWS = 4096, 1 << 15
_SORTED_IDS_DRAWS = {
    # synthetic_ctr's ids: a few rows take most of the lanes.
    "zipf": lambda rng, b: rng.zipf(1.5, size=b) % _SORTED_IDS_ROWS,
    "all_equal": lambda rng, b: np.full(b, 7),
    "all_distinct": lambda rng, b: rng.permutation(_SORTED_IDS_ROWS)[:b],
    # Past the table's edge (the 2-D mesh's drop sentinel among them),
    # negative, and in range, each many times over.
    "out_of_range": lambda rng, b: rng.choice(
        [-5, -1, 0, 3, _SORTED_IDS_ROWS - 1, _SORTED_IDS_ROWS,
         _SORTED_IDS_ROWS + 9, 2**31 - 1 - 2 * b], size=b),
}


@pytest.mark.parametrize("fn,draw,width", [
    (fn, draw, width) for fn in ("coalesce", "_dedup")
    for draw in _SORTED_IDS_DRAWS for width in (17, 128, 369)
] + [("device_compact_aux", draw, None) for draw in _SORTED_IDS_DRAWS])
def test_one_sort_gives_what_argsort_then_gather_gave(monkeypatch, fn, draw,
                                                      width):
    """BIT-equal: the stable two-operand sort returns ``jnp.argsort``'s
    ``order``, so every float32 sum keeps its order of terms."""
    b = _SORTED_IDS_LANES
    rng = np.random.default_rng(39)
    ids = jnp.asarray(_SORTED_IDS_DRAWS[draw](rng, b), jnp.int32)
    if fn == "device_compact_aux":
        call = lambda: jax.jit(
            lambda i: scatter.device_compact_aux(i, b // 4))(ids)
    else:
        delta = jnp.asarray(rng.normal(size=(b, width)) * 0.1, jnp.float32)
        call = lambda: jax.jit(getattr(scatter, fn))(ids, delta)
    got = jax.tree_util.tree_leaves(call())
    sid, order = scatter._sorted_ids(ids)
    assert order.dtype == sid.dtype == jnp.int32
    monkeypatch.setattr(scatter, "_sorted_ids", _argsort_then_gather)
    want = jax.tree_util.tree_leaves(call())
    assert len(got) == len(want) >= 3
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        assert np.array_equal(np.asarray(g), np.asarray(w))
    for g, w in zip((sid, order), _argsort_then_gather(ids)):
        assert np.array_equal(np.asarray(g), np.asarray(w))


FFM_TABLE, DEEPFM_TABLE = (1 << 17, 384), (1 << 18, 128)
DLRM_TABLE = (1 << 19, 128)
# The mesh's form of config 3's tables: 65 columns, nothing padded on.
MESH_TABLE = (1 << 18, 65)
BIG_TABLE = (1 << 20, 128)


@pytest.mark.parametrize("table,lanes,want", [
    *[(table, lanes, want) for table in (FFM_TABLE, DEEPFM_TABLE)
      for lanes, want in [
          (1024, 1024),         # one chunk: nothing to save
          (2048, 1024), (8192, 1024), (16384, 1024), (32768, 1024),
          (8192 + 512, 8192 + 512),       # not whole chunks
          (65536, 65536), (131072, 131072), (524288, 524288)]],
    # DLRM's table: XLA sorts the plain add (its cheap lowering) from
    # one lane over an eighth of the rows, 65,536 here.
    (DLRM_TABLE, 32768, 1024), (DLRM_TABLE, 49152, 1024),
    (DLRM_TABLE, 55296, 1024), (DLRM_TABLE, 61440, 1024),
    (DLRM_TABLE, 65536, 1024), (DLRM_TABLE, 66560, 66560),
    (DLRM_TABLE, 131072, 131072),
    (DLRM_TABLE, 55296 + 512, 55296 + 512),     # ragged: plain
    (DLRM_TABLE, 1024, 1024),
    # The same lanes are over an eighth of the smaller tables' rows:
    # cheap already, and more than coalescing ever beat that at.
    (FFM_TABLE, 49152, 49152), (FFM_TABLE, 55296, 55296),
    (FFM_TABLE, 20480, 1024), (FFM_TABLE, 22528, 1024),
    (DEEPFM_TABLE, 49152, 49152), (DEEPFM_TABLE, 55296, 55296),
    (DEEPFM_TABLE, 61440, 61440),
    # The columns decide nothing: the mesh's 65 as they come.
    (MESH_TABLE, 32768, 1024), (MESH_TABLE, 65536, 65536),
    (MESH_TABLE, 131072, 131072), (MESH_TABLE, 524288, 524288),
    # Config 3's batch into a table of 2^20 rows and more (Reach 2).
    (BIG_TABLE, 131072, 1024), (BIG_TABLE, 132096, 132096),
    (BIG_TABLE, 524288, 524288),
])
def test_update_lanes_by_the_real_constants(table, lanes, want):
    assert scatter.update_lanes(lanes, table) == want


def _lowered(family, batch, **config):
    common = dict(num_features=4 * 4096, num_fields=4, bucket=4096)
    spec = (models.FieldFMSpec(rank=64, **common) if family == "fm"
            else models.FieldFFMSpec(rank=16, **common))
    tconfig = TrainConfig(learning_rate=0.05, lr_schedule="constant",
                          reg_factors=1e-6, **config)
    return sparse.lower_field_sparse_step(spec, tconfig, batch).as_text(
        debug_info=True)


def _table_scatter_lanes(text, rows=4096):
    """Lane counts of every scatter into a ``[rows, w]`` table."""
    return sorted({int(m) for m in re.findall(
        rf"\(tensor<{rows}x\d+xf32>, tensor<(\d+)x1xi32>, "
        rf"tensor<\d+x\d+xf32>\) -> tensor<{rows}x", text)})


def test_the_write_coalesces_by_shape_alone():
    """The real constants, lowered on the CPU: config 4's lanes coalesce,
    config 3's do not, and the AdaGrad body keeps its own write."""
    ffm = _lowered("ffm", 8192, optimizer="sgd")
    assert "sgd/coalesce" in ffm and "sgd/write" in ffm
    assert _table_scatter_lanes(ffm) == [1024]
    fm = _lowered("fm", 131072, optimizer="sgd")
    assert "sgd/coalesce" not in fm and "sgd/write" not in fm
    assert _table_scatter_lanes(fm) == [131072]
    adagrad = _lowered("ffm", 8192, optimizer="adagrad")
    assert "sgd/coalesce" not in adagrad and "sgd/write" not in adagrad
    for scope in ("opt/coalesce", "opt/gather", "opt/rule", "opt/write"):
        assert scope in adagrad
    assert _table_scatter_lanes(adagrad) == [1024]


def _dlrm(rank, bucket=4096):
    """A small DLRM: 2 value slots and 3 tables of ``rank``-wide rows."""
    import dataclasses

    from fm_spark_tpu import configs

    cfg = dataclasses.replace(
        configs.CONFIGS["criteo1tb_dlrm_mlperf"], rank=rank, num_fields=5,
        dense_fields=2, bottom_mlp_dims=(8, rank), mlp_dims=(8,),
        bucket=bucket)
    return cfg.spec(), cfg.train_config()


@pytest.mark.parametrize("batch,lanes", [
    # The cell's write: 55,296 lanes are more than the lane clause takes
    # and under an eighth of the table's 524,288 rows, where the plain
    # add misses XLA's cheap lowering.
    (55296, 1024),
    (65536, 1024),                  # the eighth itself: still dear
    (66560, 66560),                 # one chunk over it: plain
    (55296 + 512, 55296 + 512),     # ragged: plain
    (32768, 1024),                  # the lane clause, as before
])
def test_a_dlrm_step_scatters_what_the_tables_rows_say(batch, lanes):
    """The real constants, lowered on the CPU at the cell's table shape:
    every scatter into a DLRM step's ``[524288, 128]`` tables takes
    ``update_lanes``' lanes."""
    spec, config = _dlrm(128, bucket=1 << 19)
    assert scatter.update_lanes(batch, (spec.bucket, 128)) == lanes
    text = sparse.lower_field_sparse_step(spec, config, batch).as_text(
        debug_info=True)
    assert _table_scatter_lanes(text, rows=spec.bucket) == [lanes]
    assert ("sgd/coalesce" in text) == ("sgd/write" in text) == (
        lanes != batch)


def test_a_coalescing_dlrm_step_is_the_plain_step(monkeypatch):
    """Three fused DLRM steps on one batch with duplicates, the write
    engaged through the rows clause at a tiny size (64 lanes, an eighth
    of 512 rows), against the same steps with the write left plain:
    equal to float32 reassociation."""
    batch, bucket, steps = 64, 512, 3
    spec, config = _dlrm(8, bucket=bucket)
    rng = np.random.default_rng(13)
    ids = np.where(rng.random((batch, 5)) < 0.7,
                   rng.integers(0, 6, (batch, 5)),
                   rng.integers(0, bucket, (batch, 5))).astype(np.int32)
    vals = np.ones((batch, 5), np.float32)
    vals[:, :2] = np.log1p(rng.integers(0, 9, (batch, 2)))
    labels = rng.integers(0, 2, batch).astype(np.float32)
    weights = np.ones((batch,), np.float32)
    monkeypatch.setattr(scatter, "RULE_CHUNK", 16)
    monkeypatch.setattr(scatter, "COALESCE_MAX_LANES", 32)

    def run(rows_per_lane):
        monkeypatch.setattr(scatter, "PLAIN_DEAR_ROWS_PER_LANE",
                            rows_per_lane)
        text = sparse.lower_field_sparse_step(spec, config, batch).as_text(
            debug_info=True)
        step = sparse.make_field_dlrm_sparse_step(spec, config)
        params = spec.init(jax.random.key(3))
        opt, losses = step.init_opt_state(params), []
        for i in range(steps):
            params, opt, loss = step(params, opt, jnp.int32(i), ids, vals,
                                     labels, weights)
            losses.append(float(loss))
        return text, jax.tree.map(np.asarray, params), losses

    text, got, got_losses = run(scatter.PLAIN_DEAR_ROWS_PER_LANE)
    assert "sgd/write" in text
    assert _table_scatter_lanes(text, rows=bucket) == [16]
    text, want, want_losses = run(scatter.PLAIN_DEAR_ROWS_PER_LANE + 1)
    assert "sgd/write" not in text
    assert _table_scatter_lanes(text, rows=bucket) == [batch]
    np.testing.assert_allclose(got_losses, want_losses, rtol=1e-6)
    start = jax.tree.map(np.asarray, spec.init(jax.random.key(3)))
    for g, w, s0 in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                        jax.tree.leaves(start)):
        np.testing.assert_allclose(g, w, rtol=1e-5,
                                   atol=1e-5 * np.abs(w - s0).max())
    assert any((w != s0).any() for w, s0 in zip(want["vw"], start["vw"]))


def test_coalescing_ffm_steps_match_the_plain_reference(monkeypatch):
    """Three fused FFM SGD steps on one batch, the coalesced write forced
    on at a tiny size, against ``benchmark/reference/sgd.py`` under the
    limits ``ffm_r16.train`` holds its check run to."""
    from benchmark.reference import ffm, sgd

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark/traffic/train_fed.json")) as fh:
        mix = json.load(fh)
    monkeypatch.setattr(scatter, "RULE_CHUNK", 16)
    monkeypatch.setattr(scatter, "COALESCE_MAX_LANES", 128)
    fields, rank, bucket, batch, steps = 5, 4, 64, 128, 3
    spec = models.FieldFFMSpec(num_features=fields * bucket, rank=rank,
                               num_fields=fields, bucket=bucket)
    config = TrainConfig(learning_rate=0.05, lr_schedule="constant",
                         optimizer="sgd", reg_factors=1e-4, reg_linear=1e-4)
    assert "sgd/write" in sparse.lower_field_sparse_step(
        spec, config, batch).as_text(debug_info=True)
    rng = np.random.default_rng(9)
    ids = np.where(rng.random((batch, fields)) < 0.7,
                   rng.integers(0, 8, (batch, fields)),
                   rng.integers(0, bucket, (batch, fields))).astype(np.int32)
    vals = rng.uniform(0.5, 1.5, (batch, fields)).astype(np.float32)
    labels = rng.integers(0, 2, batch).astype(np.float32)
    params = spec.init(jax.random.key(3))
    uniq, counts, inv, _ = sgd.touched(ids)
    rows0 = np.stack([np.asarray(params["vw"][f])[uniq[f]]
                      for f in range(fields)])
    factor_cols = ffm.factor_columns(fields, rank)
    want_losses, want_rows, want_w0 = sgd.train(
        ffm.scores, rank, factor_cols, rows0, inv, vals, labels, steps=steps,
        learning_rate=0.05, lr_schedule="constant", reg_factors=1e-4,
        reg_linear=1e-4, reg_bias=config.reg_bias, chunk=batch)
    step = sparse.make_field_ffm_sparse_sgd_step(spec, config)
    losses = []
    for i in range(steps):
        params, loss = step(params, jnp.int32(i), ids, vals, labels,
                            np.ones((batch,), np.float32))
        losses.append(float(loss))
    np.testing.assert_allclose(losses, want_losses, rtol=mix["loss_rtol"])
    got_rows = np.stack([np.asarray(params["vw"][f])[uniq[f]]
                         for f in range(fields)])
    live = counts > 0
    err, delta = np.abs(got_rows - want_rows), np.abs(want_rows - rows0)
    ulp = np.spacing(np.maximum(np.abs(want_rows), np.abs(rows0)))
    for cols in (slice(0, factor_cols), slice(factor_cols, None)):
        allowed = (mix["rows_rtol"] * delta[..., cols][live].max()
                   + steps * counts[..., None] * ulp[..., cols])
        assert (err[..., cols] <= allowed)[live].all()
    assert float(params["w0"]) == pytest.approx(want_w0,
                                                rel=mix["loss_rtol"])


@pytest.mark.parametrize("devices,batch,lanes", [
    (1, 2048, 1024), (1, 256, 256), (8, 2048, 1024)])
def test_the_loop_reports_the_lanes_its_write_takes(monkeypatch, capsys,
                                                    devices, batch, lanes):
    """``train/update_lanes_per_field`` after ``cli train``, on one chip
    and on the tests' eight-device mesh (whose fields' owners each take
    the whole batch's lanes and coalesce them by the same rule)."""
    import dataclasses

    from fm_spark_tpu import cli, obs
    from fm_spark_tpu import configs as configs_lib

    small = dataclasses.replace(
        configs_lib.CONFIGS["avazu_ffm_r16"], name="ffm_lanes_tiny",
        bucket=64, num_fields=5, rank=4)
    monkeypatch.setitem(configs_lib.CONFIGS, small.name, small)
    if devices == 1:
        monkeypatch.setattr(jax, "device_count", lambda *a: 1)
    obs.gauge("train/update_lanes_per_field").set(-1)
    assert cli.main(["train", "--config", small.name, "--synthetic",
                     str(2 * batch), "--steps", "2", "--batch-size",
                     str(batch), "--test-fraction", "0",
                     "--log-every", "1"]) == 0
    losses = [json.loads(line)["loss"]
              for line in capsys.readouterr().out.splitlines()
              if line.startswith("{") and '"loss"' in line]
    assert len(losses) == 2 and all(np.isfinite(losses))
    assert obs.gauge("train/update_lanes_per_field").value == lanes
