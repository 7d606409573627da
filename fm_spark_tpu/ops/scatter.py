"""Sparse row-update strategies: scatter-add, dedup, stochastic rounding.

The FieldFM hot path updates ``B`` gathered rows per field per step
(sparse.py). Three write strategies, selected by ``TrainConfig
.sparse_update``:

- ``"scatter_add"`` — the batch's deltas ADDED to their rows, every
  occurrence of a row counted. How many lanes go into the table at a
  time is chosen from the shapes (:func:`update_lanes`): the ``B`` lanes
  as they come (``.at[ids].add``; duplicates accumulate in XLA's
  scatter) where XLA gives that add its cheap lowering and the batch is
  large, else COALESCED first (:func:`coalesced_add`: each unique row's
  float32 sum, then as many chunks of ``RULE_CHUNK`` lanes as hold the
  unique rows). The measured default (PERF.md §6, PR 35 and PR 37). Why
  by the lanes and the table's rows: on the v5e a plain add costs
  75-115 ns a LANE, written or dropped, while XLA scatters lane by
  lane, and 14-54 ns once it sorts the update first, which it does from
  one lane over AN EIGHTH OF THE TABLE'S ROWS, whatever the columns
  (read from the compiled add, ``tests/test_table_layout.py``; not from
  the update's elements: 65,536 lanes x 128 columns are cheap into
  262,144 rows and dear into 524,288); coalescing costs 20-46 ns a lane
  of the batch (7.1 less since PR 39) and a chunk of 1,024 lanes
  0.06-0.09 ms. One field, Zipf(1.5) ids, ms plain / coalesced
  (``bench_micro.py ladder``). At
  2,048, 4,096, 8,192, 16,384, 32,768, 65,536, 131,072 lanes (PR 35):
  ``[131072, 384]`` 0.235 / 0.182, 0.449 / 0.233, 0.865 / 0.342, 1.747
  / 0.590, 1.341 / 1.200, 2.168 / 2.381, 3.800 / 4.768; ``[262144,
  128]`` 0.176 / 0.141, 0.328 / 0.181, 0.631 / 0.261, 1.233 / 0.411,
  2.440 / 0.801, 1.171 / 1.580, 2.385 / 3.409. At 32,768, 49,152,
  55,296, 61,440, 65,536, 131,072 lanes (PR 37): ``[524288, 128]``
  (DLRM's; its eighth is 65,536) 2.516 / 0.807, 3.721 / 1.195, 4.184 /
  1.344, 4.715 / 1.506, 5.044 / 1.581, 2.326 / 3.418; ``[262144,
  128]`` (eighth 32,768) 2.536 / 0.803, 0.993 / 1.195, 1.063 / 1.339,
  1.132 / 1.505, 1.173 / 1.581, 2.387 / 3.412; ``[131072, 384]``
  (eighth 16,384) at 20,480 and 22,528 lanes 1.106 / 0.813 and 1.148 /
  0.876, at 49,152, 55,296, 61,440 1.817 / 1.794, 1.947 / 2.030, 2.077
  / 2.254. Those coalesced prices are PR 35's and PR 37's; since PR 39
  (:func:`_sorted_ids`) coalescing costs 7.1 ns a lane less, 17-34 ns
  all told (parent -> change in one run): 8,192 lanes into ``[131072,
  384]`` 0.337 -> 0.282; 16,384, 65,536 and 131,072 into ``[262144,
  128]`` 0.412 -> 0.292, 1.577 -> 1.106 (plain 1.165) and 3.410 ->
  2.466 (plain 2.383); 55,296 into ``[524288, 128]`` 1.343 -> 0.959
  (plain 4.190).
- ``"dedup"`` — in-batch segment-sum first: sort ids, sum duplicate rows'
  deltas with a fixed-shape ``segment_sum``, then ONE add per unique id
  (duplicate lanes write out-of-bounds and are dropped — XLA scatter
  drop-semantics, the jnp ``mode="drop"``). Same result as scatter_add
  up to float reassociation, and never faster on the chip: it keeps all
  ``B`` lanes and only masks them, and a dropped lane costs what a
  written one does.
- ``"dedup_sr"`` — dedup, then write back ``old + Σdelta`` with
  STOCHASTIC ROUNDING via set-semantics. This is the bf16-storage
  quality fix: plain bf16 scatter-add loses updates smaller than half an
  ulp of the stored weight (measured ~0.014 AUC, tests/test_bf16_quality
  .py); SR makes the rounding unbiased so tiny updates land in
  expectation. Requires dedup because ``set`` with duplicate ids would
  drop all but one lane's contribution.

The compact levers (:func:`compact_aux`, :func:`_compact_write`) cut the
lanes too, to a static cap, but PROMISE the scatter sorted and unique
indices, and ``indices_are_sorted=True`` alone costs 0.6-0.7 ms a table
whatever its lanes (PERF.md §6, PR 34): at these sizes they lose to the
plain add they were built to beat.

All three are fixed-shape and jit/shard_map-safe.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

SPARSE_UPDATE_MODES = ("scatter_add", "dedup", "dedup_sr")


class CompactCapOverflow(ValueError):
    """A field's per-batch unique-id count exceeded ``compact_cap``.

    Dedicated type so the pipeline's ``compact_overflow='split'`` policy
    (data/pipeline.DedupAuxBatches) can catch exactly this condition and
    split the batch, while any other aux-builder error still propagates.
    """


def sr_key(base: jax.Array, step_idx, field: jax.Array | int) -> jax.Array:
    """The SR noise key schedule: one stream per (step, field).

    Single definition shared by the single-chip and field-sharded steps
    so their noise streams can never silently diverge; ``field`` is the
    GLOBAL field index (sharded callers pass
    ``axis_index * f_local + f``).
    """
    return jax.random.fold_in(jax.random.fold_in(base, step_idx), field)


def stochastic_round(x: jax.Array, dtype, key: jax.Array) -> jax.Array:
    """Round fp32 ``x`` to ``dtype`` stochastically (unbiased).

    bf16 path: add uniform-random low 16 bits, truncate. For fp32 targets
    this is the identity.
    """
    dtype = jnp.dtype(dtype)
    if dtype == jnp.float32:
        return x
    if dtype != jnp.bfloat16:
        raise ValueError(f"stochastic_round supports bf16/fp32, not {dtype}")
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    noise = jax.random.bits(key, x.shape, jnp.uint32) & jnp.uint32(0xFFFF)
    rounded = (bits + noise) & jnp.uint32(0xFFFF0000)
    out = jax.lax.bitcast_convert_type(rounded, jnp.float32).astype(
        jnp.bfloat16
    )
    # The integer bit-add carries into the exponent field for values whose
    # mantissa is all-ones; near bf16 max that can overflow a FINITE input
    # into inf — saturate to ±max instead. Non-finite inputs bypass the
    # bit-add entirely (it would corrupt NaN payloads / inf encodings).
    finite_in = jnp.isfinite(x)
    maxv = jnp.asarray(jnp.finfo(jnp.bfloat16).max, jnp.bfloat16)
    out = jnp.where(
        jnp.isfinite(out) | ~finite_in, out,
        jnp.sign(x).astype(jnp.bfloat16) * maxv,
    )
    return jnp.where(finite_in, out, x.astype(jnp.bfloat16))


def _sorted_ids(ids: jax.Array):
    """``(sid, order)``: one id column ascending and the int32 stable
    permutation that sorts it (``sid = ids[order]``, ``order`` what
    ``jnp.argsort(ids)`` returns), from ONE two-operand sort.
    ``jnp.argsort`` is that same sort with the sorted keys thrown away,
    and ``ids[order]`` after it a gather of ``B`` scalars: 7.1 ns a lane
    on the v5e, where the sort costs 0.5 ns an element (PERF.md §6,
    PR 39). Stable, so float32 sums taken in ``order`` keep their order
    of terms."""
    lanes = jnp.arange(ids.shape[0], dtype=jnp.int32)
    return jax.lax.sort((ids, lanes), num_keys=1, is_stable=True)


def _dedup(ids: jax.Array, delta: jax.Array):
    """Segment duplicate ids: returns (sorted ids, per-lane summed delta,
    run-start mask, sort order). ``summed[p]`` holds the TOTAL delta of
    the id at lane ``p``'s segment; only run-start lanes should write."""
    sid, order = _sorted_ids(ids)
    sdelta = delta[order]
    run_start = jnp.concatenate(
        [jnp.ones((1,), bool), sid[1:] != sid[:-1]]
    )
    seg = jnp.cumsum(run_start) - 1
    summed = jax.ops.segment_sum(
        sdelta, seg, num_segments=ids.shape[0]
    )
    return sid, summed[seg], run_start, order


def coalesce(ids: jax.Array, delta: jax.Array):
    """One id column's rows coalesced for a READ-MODIFY-WRITE rule (the
    field bodies' per-coordinate optimizers, sparse.py): ``(useg [B],
    totals [B, w] float32, n)``. ``useg[:n]`` are the column's unique
    ids, ascending; ``totals[s]`` is the SUM of ``delta`` over the lanes
    whose id is ``useg[s]``; past ``n`` the totals are zero and ``useg``
    holds distinct ascending ids past any table's edge, so the whole
    vector is sorted and unique (what ``indices_are_sorted`` /
    ``unique_indices`` promise XLA) and a ``mode="drop"`` write leaves
    those lanes out. :func:`_dedup` keeps every lane's total in its
    sorted place, for the add and stochastic-round writes that mask
    lanes; a set-semantics write of a rule's result wants each row once,
    at the front."""
    b = ids.shape[0]
    sid, order = _sorted_ids(ids)
    run_start = jnp.concatenate(
        [jnp.ones((1,), bool), sid[1:] != sid[:-1]]
    )
    seg = (jnp.cumsum(run_start) - 1).astype(jnp.int32)
    n = seg[-1] + 1
    totals = jax.ops.segment_sum(
        delta[order].astype(jnp.float32), seg, num_segments=b,
        indices_are_sorted=True,
    )
    # Each run's first lane keeps its id, every other lane takes a
    # sentinel of its own: sorted, that is the unique ids, then sentinels.
    pos = jnp.arange(b, dtype=jnp.int32)
    useg = jnp.sort(jnp.where(run_start, sid, (2**31 - 1 - b) + pos))
    return useg, totals, n


def dedup_aux(ids):
    """HOST-side dedup precompute for a ``[B, F]`` id batch.

    The device-side ``_dedup`` pays a per-field argsort every step; none
    of that work depends on model state, so a prefetch thread can ship it
    with the batch (PERF.md round-3 "host-assisted dedup" lever). Returns
    ``(order, seg, useg, ord_first)``, each int32 ``[F, B]`` (per-field
    slices contiguous):

    - ``order``     — per-field stable argsort of the ids;
    - ``seg``       — segment index of each SORTED lane (duplicates share
                      a segment);
    - ``useg``      — the unique id segment ``s`` writes to, padded past
                      the segment count with an out-of-range sentinel
                      (int32 max ≥ any table size → dropped);
    - ``ord_first`` — original lane of each segment's first sorted
                      occurrence (the dedup_sr representative row).

    Fast path: the native threaded counting sort (native/fasthash.cpp
    ``fm_dedup_aux``, O(B + bucket) per field); fallback: numpy stable
    argsort (identical output — counting sort and stable argsort agree
    exactly; pinned in tests/test_host_dedup.py).
    """
    import numpy as np

    ids = np.asarray(ids)
    squeeze = ids.ndim == 1
    if squeeze:
        ids = ids[:, None]
    b, f = ids.shape
    if b == 0:
        empty = tuple(np.empty((f, 0), np.int32) for _ in range(4))
        return tuple(a[0] for a in empty) if squeeze else empty
    if ids.min() < 0:
        raise ValueError("dedup_aux requires non-negative ids")
    bucket = int(ids.max()) + 1

    from fm_spark_tpu import native

    out = native.dedup_aux_native(ids, bucket)
    if out is None:
        idsT = np.ascontiguousarray(ids.T)
        order = np.argsort(idsT, axis=1, kind="stable").astype(np.int32)
        sid = np.take_along_axis(idsT, order, axis=1)
        run = np.concatenate(
            [np.ones((f, 1), bool), sid[:, 1:] != sid[:, :-1]], axis=1
        )
        seg = run.cumsum(axis=1).astype(np.int32) - 1
        useg = np.full((f, b), np.iinfo(np.int32).max, np.int32)
        ord_first = np.zeros((f, b), np.int32)
        for j in range(f):  # tiny per-field compactions
            m = run[j]
            u = sid[j, m]
            useg[j, : u.size] = u
            ord_first[j, : u.size] = order[j, m]
        out = (order, seg, useg, ord_first)
    if squeeze:
        return tuple(a[0] for a in out)
    return out


def compact_aux(ids, cap: int):
    """HOST-side aux for the COMPACT sparse-update path on a ``[B, F]``
    id batch: unlike :func:`dedup_aux` (which keeps ``B`` scatter lanes
    and only masks duplicates), this compacts each field's unique ids
    into a STATIC capacity ``cap`` so the device touches the big table
    with ``cap`` lanes instead of ``B``.

    Why it wins (bench_micro.py ``compact``, measured on chip round 2):
    XLA's scatter cost is per-LANE even for dropped/duplicate lanes, so
    the only way to make the update cheaper is fewer lanes; and a
    unique+sorted cap-lane scatter is ~3x cheaper than the B-lane
    scatter-add at the headline shapes. The per-lane segment reduction
    that dedup needs is restructured as one ``cumsum`` over the sorted
    deltas plus cap-lane boundary gathers — no B-lane scatter anywhere.

    Returns ``(useg, segstart, segend, order, inv)``, all int32:

    - ``useg``     [F, cap] — each field's unique ids, ascending, padded
                   with DISTINCT ascending out-of-range sentinels (so the
                   index vector is globally unique AND sorted — XLA's
                   ``unique_indices``/``indices_are_sorted`` promises
                   hold; dropped via scatter ``mode="drop"``);
    - ``segstart`` [F, cap] — first sorted-lane index of each segment
                   (padding: ``B - 1``, harmless — its result lanes are
                   dropped);
    - ``segend``   [F, cap] — last sorted-lane index of each segment;
    - ``order``    [F, B] — per-field stable argsort of the ids;
    - ``inv``      [F, B] — segment index of each ORIGINAL lane (the
                   forward expansion map: ``rows = urows[inv]``).

    Raises if any field's unique count exceeds ``cap`` (pick ``cap``
    from the data: max per-field per-batch unique ids; Zipf-skewed CTR
    fields run ~10-25% of B).
    """
    import numpy as np

    ids = np.asarray(ids)
    if ids.ndim != 2:
        raise ValueError("compact_aux expects [B, F] ids")
    b, f = ids.shape
    if cap < 1 or cap > max(b, 1):
        raise ValueError(f"cap must be in [1, B], got {cap} (B={b})")
    if b and ids.min() < 0:
        raise ValueError("compact_aux requires non-negative ids")
    imax = np.iinfo(np.int32).max
    if b and int(ids.max()) >= imax - cap:
        raise ValueError("id space collides with the sentinel range")

    from fm_spark_tpu import native

    nat = native.compact_aux_native(ids, cap)
    if nat is not None:
        return nat

    useg = np.zeros((f, cap), np.int32)
    segstart = np.full((f, cap), max(b - 1, 0), np.int32)
    segend = np.full((f, cap), max(b - 1, 0), np.int32)
    order = np.argsort(ids, axis=0, kind="stable").astype(np.int32).T
    inv = np.zeros((f, b), np.int32)
    sentinel = (imax - cap) + np.arange(cap, dtype=np.int32)
    for j in range(f):
        sid = ids[order[j], j]
        u, first = (np.unique(sid, return_index=True) if b
                    else (np.empty(0, np.int32), np.empty(0, np.int64)))
        s = u.size
        if s > cap:
            raise CompactCapOverflow(
                f"field {j}: {s} unique ids > compact cap {cap}; raise "
                "compact_cap (it must bound the per-field per-batch "
                "unique-id count)"
            )
        useg[j, :s] = u
        useg[j, s:] = sentinel[: cap - s]
        segstart[j, :s] = first
        segend[j, :s] = np.r_[first[1:] - 1, b - 1] if s else []
        seg_of_sorted = np.cumsum(
            np.r_[0, (sid[1:] != sid[:-1]).astype(np.int32)]
        ) if b else np.empty(0, np.int64)
        inv[j, order[j]] = seg_of_sorted
    return useg, segstart, segend, order, inv


def _check_sentinel_range(bucket: int, cap: int) -> None:
    """The compact aux's OOB padding sentinels live in
    ``[INT32_MAX - cap, INT32_MAX)`` (compact_aux). The aux builder
    guards the ID side (ids < INT32_MAX - cap); this trace-time check
    guards the TABLE side — a bucket dimension reaching into the
    sentinel range would make padding lanes in-bounds and ``mode="drop"``
    writes would corrupt real rows."""
    imax = 2**31 - 1
    if bucket > imax - cap:
        raise ValueError(
            f"table bucket dim {bucket} collides with the compact "
            f"sentinel range [{imax - cap}, {imax}); shard or split the "
            "table below INT32_MAX - cap rows"
        )


def device_compact_aux(ids_col, cap: int):
    """DEVICE-side :func:`compact_aux` for ONE field's full-batch id
    column — jit/shard_map-safe (static shapes, no host round-trip).

    Why it exists (PERF.md round-3): the host-built aux composes only
    with layouts where some host holds every field's full global column
    — which excludes multi-process feeds (each process holds a row
    slice) and 2-D ``(feat, row)`` meshes (a segment's lanes span hosts'
    slices but exactly one ROW SHARD owns the segment). Building the aux
    on device AFTER the batch re-shard sidesteps both: each chip
    compacts only the ``F/n`` columns it owns, so the per-chip sort cost
    that made device-side dedup lose on ONE chip (PERF.md round-2 A/B:
    39 sorts) shrinks by the mesh size.

    Returns ``((useg, segstart, segend, order, inv), nseg)`` matching
    the host builder's per-field contract bit-for-bit (both use a STABLE
    sort, so downstream cumsum segment totals are bitwise identical —
    pinned in tests/test_compact_device.py), plus the segment count for
    overflow accounting. Unlike the host builder this cannot raise on
    overflow: segments beyond ``cap`` (the LARGEST ids, since segments
    are ascending) simply get no ``useg`` slot — their updates are never
    written, and callers must zero their forward rows via
    ``inv >= cap`` masking (``sparse._compact_gather_all`` with
    ``mask_overflow=True``). That is the documented
    ``compact_overflow='drop'`` semantics: overflow ids behave as
    absent features for the overflowing batch.

    The drop selection is ID-BIASED, not uniform (ADVICE r3): segments
    sort id-ascending, so it is deterministically the LARGEST ids that
    drop — under hashed/Zipf id spaces the same high-id features are
    dropped on every overflowing batch rather than a random subset.
    Operators sizing ``cap`` near the unique-count envelope should
    expect systematic (not uniformly-spread) degradation on those
    features; see QUALITY.md.
    """
    b = ids_col.shape[0]
    imax = 2**31 - 1
    sid, order = _sorted_ids(ids_col)
    run_start = jnp.concatenate(
        [jnp.ones((1,), bool), sid[1:] != sid[:-1]]
    )
    run_end = jnp.concatenate([run_start[1:], jnp.ones((1,), bool)])
    seg = (jnp.cumsum(run_start) - 1).astype(jnp.int32)
    nseg = seg[-1] + 1
    lane = jnp.arange(b, dtype=jnp.int32)
    # Scatters against [cap]-sized outputs: small-operand fast rate;
    # segments past cap target index `cap` → dropped (overflow). NOTE:
    # no sorted/unique promises here — the OOB drop value `cap` is
    # interleaved between (and duplicates among) the ascending segment
    # targets, so neither promise holds and claiming them would be
    # undefined behavior XLA may exploit.
    start_tgt = jnp.where(run_start, seg, cap)
    end_tgt = jnp.where(run_end, seg, cap)
    useg = jnp.zeros((cap,), jnp.int32).at[start_tgt].set(
        sid, mode="drop"
    )
    segstart = jnp.full((cap,), b - 1, jnp.int32).at[start_tgt].set(
        lane, mode="drop"
    )
    segend = jnp.full((cap,), b - 1, jnp.int32).at[end_tgt].set(
        lane, mode="drop"
    )
    # Padding slots (pos >= nseg) carry the host builder's ascending OOB
    # sentinels so the sorted+unique scatter promises keep holding.
    pos = jnp.arange(cap, dtype=jnp.int32)
    useg = jnp.where(pos < nseg, useg, (imax - cap) + (pos - nseg))
    segstart = jnp.where(pos < nseg, segstart, b - 1)
    segend = jnp.where(pos < nseg, segend, b - 1)
    inv = jnp.zeros((b,), jnp.int32).at[order].set(seg, unique_indices=True)
    return (useg, segstart, segend, order, inv), nseg


def _to_table_width(rows, table):
    """``rows`` ([n, w] values about to be written into ``table``)
    zero-padded to the table's width. The one-chip training loop holds
    a narrow row table lane-padded (models/rows.py says why: zero
    columns that stay zero, because only zeros are ever written there);
    the arithmetic before a write runs at the model's width and only the
    write pays the lanes. ``rows`` itself for a table no wider."""
    extra = table.shape[1] - rows.shape[1]
    return jnp.pad(rows, ((0, 0), (0, extra))) if extra else rows


def compact_gather(table, useg):
    """Forward half of the compact path: gather each unique id's row
    once — ``cap`` ascending lanes against the big table (sentinels clip
    to the last row; those rows are never referenced by ``inv``).
    Per-lane rows are then ``urows[inv]`` against this [cap, w] buffer,
    which gathers at the small-operand fast rate (PERF.md fact 2)."""
    _check_sentinel_range(table.shape[0], useg.shape[-1])
    return table.at[useg].get(mode="clip", indices_are_sorted=True)


# Lanes a coalesced write takes at a time. Two users walk
# :func:`coalesce`'s unique rows in chunks of this many, as many chunks
# as hold them: sparse.py's AdaGrad body (a read-modify-write rule:
# gather, rule, set) and :func:`coalesced_add` (the SGD bodies'
# ``scatter_add`` write, where :func:`update_lanes` says so). Measured
# on the v5e (PERF.md §6, PR 34): a gather or a set of n rows of a
# [131072, 384] table costs some 90 ns a LANE, written or dropped, so a
# batch's ~570 unique rows a field cost an eighth of its 8,192 lanes in
# one chunk of 1,024; a smaller chunk would need two for the widest
# fields.
RULE_CHUNK = 1024


def rows_at(table, useg):
    """The rows of ``table`` at :func:`coalesce`'s ids (sentinel lanes
    clip to the last row; nobody reads them)."""
    _check_sentinel_range(table.shape[0], useg.shape[-1])
    return table.at[useg].get(mode="clip")


def set_rows_at(table, useg, rows):
    """``rows`` ([n, w], the model's width) SET at :func:`coalesce`'s
    ids: each row written once, padded to the table's width, sentinel
    lanes dropped. No ``unique_indices`` / ``indices_are_sorted``
    promise, true as both are: on the v5e the promised scatter costs
    0.6-0.7 ms a table whatever its lanes, the plain one 90 ns a lane
    (PERF.md §6, PR 34)."""
    _check_sentinel_range(table.shape[0], useg.shape[-1])
    return table.at[useg].set(
        _to_table_width(rows.astype(table.dtype), table), mode="drop")


# Most lanes a field's ``scatter_add`` write coalesces before it adds
# WHATEVER the plain add would cost (:func:`update_lanes`' first
# clause): the highest rung of PR 35's ladder at which the coalesced add
# measured faster than the plain one on every table, XLA's cheap
# lowering included (the module's docstring has every rung; PERF.md §6,
# PR 35 and PR 37). At 32,768 lanes it wins by 1.12x into [131072, 384]
# (a quarter of its rows: the plain add is on the cheap lowering
# already) and 3.0x into [262144, 128] (an eighth: dear); into [131072,
# 384] it ties at 49,152 (1.817 / 1.794 ms) and loses from 55,296, and
# at 65,536 it loses by 1.10x and 1.35x: the cheap lowering costs 14-41
# ns a lane from there up, and sorting and summing the whole batch
# first costs more than it saves. Uniform ids, every lane a row of its
# own, are the coalesced add's worst case: 8,192 of them cost it
# 1.19-1.20x the plain add (all eight chunks written, the coalesce pure
# cost), 55,296 of them 1.16x (4.781 against 4.125 ms into [524288,
# 128]). Since PR 39 the coalesce gathers no ids and the same rungs
# read: into [262144, 128] 1.106 coalesced against 1.165 plain at 65,536
# lanes (ahead by 5%) and 2.466 against 2.383 at 131,072 (behind by
# 3.5%, FM's rung); 55,296 uniform ids 1.07x (4.391 against 4.117). The
# constant stays where every rung under it wins and none over it is a
# cell's: moving it is a claim on the FM cells, with their numbers.
COALESCE_MAX_LANES = 32768

# Rows of the table per lane of the update from which the plain add is
# DEAR (:func:`update_lanes`' second clause). XLA lowers the plain add
# two ways and chooses by the lanes against the table's ROWS: compiled
# for a v5e, it sorts the update first from one lane over an eighth of
# the rows ([65536 ... 2097152, 128 | 384]: no ``sort`` at rows / 8
# lanes, one at rows / 8 + 1, whatever the columns;
# ``tests/test_table_layout.py`` holds a libtpu to it), and the ladder
# prices the two sides (PERF.md §6, PR 37): into [524288, 128] 75.7-77.0
# ns a lane at 32,768 to 65,536 lanes, the eighth itself included, and
# 17.7 at 131,072; into [262144, 128] 77.4 at 32,768 and 20.2-17.9 at
# 49,152 to 65,536; into [131072, 384] 100.6 at 16,384 and 54.0 at
# 20,480. With this many rows a lane or more the plain add pays the
# dear price however many lanes there are, and the coalesced add, 24-25
# ns a lane of 128 columns all told (17.3 since PR 39), wins 3.1-3.2x
# (4.4x) however many there are. The update's ELEMENTS decide nothing:
# 65,536 lanes x 128 columns are cheap into 262,144 rows and dear into
# 524,288.
PLAIN_DEAR_ROWS_PER_LANE = 8


def update_lanes(lanes: int, table_shape) -> int:
    """Lanes a field's ``scatter_add`` write puts into a table of
    ``table_shape`` at a time, given ``lanes`` ids (both static):
    ``RULE_CHUNK`` where :func:`apply_row_updates` coalesces first
    (:func:`coalesced_add`), ``lanes`` where it adds them as they come.
    The one statement of that choice: ``apply_row_updates`` asks it, and
    the training loop reports its answer
    (``train/update_lanes_per_field``). A batch of one chunk or less has
    nothing to save, and one that is not whole chunks is not walked in
    chunks (the AdaGrad body's conditions too). A larger one is left
    plain only where the plain add gets XLA's cheap lowering AND has
    more lanes than coalescing ever beat that lowering at."""
    whole_chunks = lanes > RULE_CHUNK and lanes % RULE_CHUNK == 0
    plain_is_dear = lanes * PLAIN_DEAR_ROWS_PER_LANE <= table_shape[0]
    coalesces = whole_chunks and (lanes <= COALESCE_MAX_LANES
                                  or plain_is_dear)
    return RULE_CHUNK if coalesces else lanes


def coalesced_add(table, ids, delta):
    """``table.at[ids].add(delta, mode="drop")`` with each row added
    ONCE: :func:`coalesce` sums the lanes of every unique id in float32
    (the same terms as the lane-by-lane add, reassociated; a table in
    fewer bits takes one rounded sum a row, not a rounded term an
    occurrence), and the sums are added ``RULE_CHUNK`` lanes at a time,
    as many chunks as hold the unique ids, the table updated in place.
    ``ids.shape[0]`` is a multiple of ``RULE_CHUNK``.

    An id is a value to this function, in range or not: lanes of one
    value are summed and added AT that value, so an out-of-range id (the
    2-D mesh's drop sentinel) is dropped by the same ``mode="drop"`` as
    in the plain add, and a negative one wraps as it does there. They
    need not avoid ``coalesce``'s own sentinels: those lie past the
    table's edge too (``_check_sentinel_range``), nothing is promised
    unique or sorted, and two dropped lanes at one index are two dropped
    lanes."""
    _check_sentinel_range(table.shape[0], ids.shape[0])
    if ids.shape[0] % RULE_CHUNK:
        raise ValueError(
            f"coalesced_add walks whole chunks of {RULE_CHUNK} lanes, "
            f"not {ids.shape[0]}")
    return _coalesced_add(table, ids, delta, RULE_CHUNK)


# A step writes F tables of one shape: under an inner jit the write is
# traced once a shape, not once a field (0.5-0.8 s of every trace of the
# FFM and DeepFM steps otherwise, and a run traces its step two or three
# times).
@functools.partial(jax.jit, static_argnames="chunk")
def _coalesced_add(table, ids, delta, chunk):
    with jax.named_scope("sgd/coalesce"):
        useg, totals, n = coalesce(ids, delta)

    def one_chunk(c, table):
        at = jax.lax.dynamic_slice(useg, (c * chunk,), (chunk,))
        rows = jax.lax.dynamic_slice(
            totals, (c * chunk, 0), (chunk, totals.shape[1]))
        return table.at[at].add(
            _to_table_width(rows.astype(table.dtype), table), mode="drop")

    with jax.named_scope("sgd/write"):
        return jax.lax.fori_loop(0, (n + chunk - 1) // chunk, one_chunk,
                                 table)


# Block size of the two-level prefix in compact_apply. Measured
# (bench_micro `cumsum`, round 3): a plain [131072, 65] fp32 jnp.cumsum
# cost 73ms/39-field on that round's attachment while the blocked two-level
# form cost 53ms — and compact_apply never needs the full prefix
# ARRAY, only its values at the 2·cap segment boundaries, so keeping
# the block-local prefix and block offsets SEPARATE (gathered at the
# boundary positions) also skips the final full-buffer add pass the
# probe still paid.
_CSUM_BLOCK = 512


def compact_apply(table, delta, caux, mode, key, urows,
                  segtotal_pallas: bool = False):
    """Update half of the compact path (see :func:`compact_aux`): per-
    segment sums via a two-level blocked fp32 prefix over the sorted
    deltas + cap-lane boundary gathers (``sum[s] = csum(end_s) −
    csum(start_s) + sdelta[start_s]`` — exact per segment, no
    cross-segment residue beyond the prefix's own reassociation), then
    ONE write per unique id: ``add`` for ``dedup``, stochastic-rounded
    ``set`` of ``urows + sum`` for ``dedup_sr`` (``urows`` doubles as
    the old-row operand — no second gather).

    ``segtotal_pallas`` (TrainConfig.segtotal_pallas, round 5): compute
    the segment sums with the Pallas sorted-run kernel
    (:mod:`fm_spark_tpu.ops.pallas_segsum`) instead of the blocked
    prefix — one streaming read, no prefix materialization; same values
    up to fp32 reassociation (tests/test_pallas_segsum.py)."""
    useg, segstart, segend, order, inv = caux
    cap = useg.shape[-1]
    _check_sentinel_range(table.shape[0], cap)
    sdelta = delta[order].astype(jnp.float32)
    b, w = sdelta.shape
    if segtotal_pallas:
        from fm_spark_tpu.ops import pallas_interpret, pallas_segsum

        segsum = pallas_segsum.segment_totals(
            sdelta, inv[order], cap, interpret=pallas_interpret())
    else:
        del inv
        blk = _CSUM_BLOCK
        pad = (-b) % blk
        padded = jnp.pad(sdelta, ((0, pad), (0, 0))) if pad else sdelta
        nb = padded.shape[0] // blk
        bl = jnp.cumsum(padded.reshape(nb, blk, w), axis=1)  # in-block
        off = jnp.cumsum(bl[:, -1, :], axis=0)               # inclusive
        off = jnp.concatenate([jnp.zeros_like(off[:1]), off[:-1]],
                              axis=0)

        def csum_at(pos):
            # Boundary positions are < b, so padding rows never enter.
            return bl[pos // blk, pos % blk] + off[pos // blk]

        segsum = csum_at(segend) - csum_at(segstart) + sdelta[segstart]
    return _compact_write(table, segsum, useg, mode, key, urows)


def _compact_write(table, segsum, useg, mode, key, urows):
    """The compact update's WRITE half: one unique+sorted cap-lane
    write of the fp32 per-segment totals — ``add`` for ``dedup``,
    stochastic-rounded ``set`` of ``urows + totals`` for ``dedup_sr``.
    Single definition shared by :func:`compact_apply` (XLA/segtotal
    totals) and :func:`compact_apply_totals` (the fused Pallas
    backward's totals) so the write semantics can never drift."""
    if mode == "dedup":
        upd = _to_table_width(segsum.astype(table.dtype), table)
        return table.at[useg].add(
            upd, mode="drop",
            unique_indices=True, indices_are_sorted=True,
        )
    if key is None or urows is None:
        raise ValueError("dedup_sr needs key= and urows=")
    new_rows = urows.astype(jnp.float32) + segsum
    vals = _to_table_width(
        stochastic_round(new_rows, table.dtype, key), table)
    return table.at[useg].set(
        vals, mode="drop",
        unique_indices=True, indices_are_sorted=True,
    )


def compact_apply_totals(table, totals, caux, mode, key, urows):
    """Apply PRECOMPUTED [cap, w] fp32 per-segment totals to ``table``
    — the write half of :func:`compact_apply` for callers that already
    hold the totals, i.e. the fused Pallas backward
    (ops/pallas_fused.fm_bwd_segment_totals), whose output is exactly
    the ``-lr·g_full`` segment sums the blocked prefix would produce.
    ``caux``/``mode``/``key``/``urows`` as in
    :func:`compact_apply`."""
    useg = caux[0]
    _check_sentinel_range(table.shape[0], useg.shape[-1])
    return _compact_write(table, totals, useg, mode, key, urows)


def _aux_apply(table, delta, aux, mode, key, old_rows):
    """Segment-sum + unique-target write from host-precomputed ``aux``
    (see :func:`dedup_aux`; per-field [B] slices here). No device sort,
    no per-lane re-expansion — the scatter touches each unique id once."""
    order, seg, useg, ord_first = aux
    summed = jax.ops.segment_sum(
        delta[order], seg, num_segments=delta.shape[0],
        indices_are_sorted=True,
    )
    if mode == "dedup":
        return table.at[useg].add(
            _to_table_width(summed.astype(table.dtype), table), mode="drop")
    new_rows = (
        old_rows[ord_first].astype(jnp.float32) + summed.astype(jnp.float32)
    )
    return table.at[useg].set(
        _to_table_width(stochastic_round(new_rows, table.dtype, key), table),
        mode="drop",
    )


def _pallas_pad(x: jax.Array, mult: int, fill=0):
    pad = (-x.shape[0]) % mult
    if pad == 0:
        return x
    widths = ((0, pad),) + ((0, 0),) * (x.ndim - 1)
    return jnp.pad(x, widths, constant_values=fill)


def pallas_gather(table: jax.Array, ids: jax.Array) -> jax.Array:
    """Pipelined-DMA row gather (ops/pallas_fm.py), padding ids to the
    kernel's tile multiple."""
    from fm_spark_tpu.ops import pallas_fm, pallas_interpret

    b = ids.shape[0]
    interpret = pallas_interpret()
    # Clamp pad/sentinel ids in-range: gather is side-effect free and the
    # 2-D sharded path masks non-owned lanes itself.
    safe = jnp.clip(_pallas_pad(ids, pallas_fm._TILE), 0,
                    table.shape[0] - 1)
    return pallas_fm.gather_rows(table, safe, interpret=interpret)[:b]


def _pallas_dedup_add(table, ids, delta):
    """dedup + pipelined read-modify-write: the Pallas replacement for
    both 'scatter_add' and 'dedup'. Any out-of-range id (the 2-D mesh's
    high drop sentinel, or a negative) becomes an invalid lane, matching
    XLA scatter's mode="drop". Numerics note: duplicates are summed in
    fp32 and rounded ONCE into the storage dtype — for fp32 tables this
    is 'scatter_add' up to reassociation, but for bf16 tables it is
    systematically MORE accurate than XLA's round-per-duplicate-write
    scatter (closer to 'dedup', which shares the segment-sum)."""
    from fm_spark_tpu.ops import pallas_fm, pallas_interpret

    n = table.shape[0]
    sid, summed, run_start, _ = _dedup(ids, delta)
    valid = run_start & (sid >= 0) & (sid < n)
    interpret = pallas_interpret()
    return pallas_fm.update_rows_add(
        table,
        _pallas_pad(jnp.where(valid, sid, 0), pallas_fm._TILE),
        _pallas_pad(valid, pallas_fm._TILE, fill=False),
        _pallas_pad(jnp.where(valid[:, None], summed, 0.0),
                    pallas_fm._TILE),
        interpret=interpret,
    )


def apply_row_updates(
    table: jax.Array,
    ids: jax.Array,
    delta: jax.Array,
    mode: str = "scatter_add",
    key: jax.Array | None = None,
    old_rows: jax.Array | None = None,
    use_pallas: bool = False,
    aux=None,
) -> jax.Array:
    """Apply per-row ``delta`` ([B, w] in compute dtype) to ``table``
    ([n, w] in storage dtype) at ``ids`` ([B]).

    ``scatter_add`` (the default) adds every lane's delta to its row;
    out-of-range ids are dropped. Whether the ``B`` lanes go in as they
    come or coalesced, ``RULE_CHUNK`` lanes at a time, is
    :func:`update_lanes`' answer for ``B`` and the table's shape, not
    the caller's: the result is the same to float32 reassociation (each
    unique row receives the float32 SUM of its occurrences in one add,
    where the plain add gives them one by one), and the module's
    docstring has what each costs on the chip. ``dedup`` masks its
    ``B`` lanes and ``_compact_write`` promises its scatter sorted
    indices: neither is ever the faster there.

    ``old_rows`` ([B, w], compute dtype) are the previously gathered rows
    — required for ``dedup_sr`` (the new value is formed in fp32 from
    them, so no second gather is paid). ``key`` seeds SR.
    ``use_pallas`` routes 'scatter_add'/'dedup' through the pipelined
    read-modify-write kernel (dedup_sr keeps its XLA set-semantics
    write-back, which stochastic rounding requires).
    ``aux`` (dedup modes) is :func:`dedup_aux`'s host-precomputed
    ``(order, seg, useg, ord_first)`` for THIS ids column — skips the
    device argsort and writes each unique id exactly once. SR note: the
    aux path draws its rounding noise at segment-compacted positions
    rather than sorted-lane positions, so dedup_sr aux-vs-device results
    are equal in distribution (and bitwise for fp32), not bitwise for
    bf16.
    """
    if mode not in SPARSE_UPDATE_MODES:
        raise ValueError(f"unknown sparse_update mode {mode!r}")
    n = table.shape[0]
    if aux is not None:
        if mode == "scatter_add":
            raise ValueError("aux requires a dedup mode")
        if mode == "dedup_sr" and (key is None or old_rows is None):
            raise ValueError("dedup_sr needs key= and old_rows=")
        return _aux_apply(table, delta, aux, mode, key, old_rows)
    if use_pallas and mode in ("scatter_add", "dedup"):
        return _pallas_dedup_add(table, ids, _to_table_width(delta, table))
    if mode == "scatter_add":
        if update_lanes(ids.shape[0], table.shape) != ids.shape[0]:
            return coalesced_add(table, ids, delta)
        # mode="drop" is XLA's default scatter OOB semantics, made
        # explicit: the 2-D field-sharded step routes non-owned lanes to
        # an out-of-bounds sentinel index that MUST be dropped.
        return table.at[ids].add(
            _to_table_width(delta.astype(table.dtype), table), mode="drop")

    sid, summed, run_start, order = _dedup(ids, delta)
    oob = jnp.where(run_start, sid, n)  # non-run-start lanes are dropped
    if mode == "dedup":
        upd = jnp.where(run_start[:, None], summed, 0.0)
        return table.at[oob].add(
            _to_table_width(upd.astype(table.dtype), table), mode="drop")

    if key is None or old_rows is None:
        raise ValueError("dedup_sr needs key= and old_rows=")
    # One representative old row per segment (duplicates share the row).
    new_rows = old_rows[order].astype(jnp.float32) + summed.astype(jnp.float32)
    vals = stochastic_round(new_rows, table.dtype, key)
    return table.at[oob].set(_to_table_width(vals, table), mode="drop")
