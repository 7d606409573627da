"""Fused Pallas embedding path: gather→FM-interaction forward and the
g_full→segment-totals backward that keeps the per-field gradient set
on-chip (ISSUE 8; ROADMAP item 4).

Three kernel families, priced per-kernel by ``bench_kernels.py`` and
wired as the ``TrainConfig.fused_embed`` step lever (sparse.py):

1. **Fused forward** (:func:`fm_fused_scores`): per-field pipelined-DMA
   row gather (the :mod:`pallas_fm` queue) fused with the FM interaction
   — each tile's gathered rows die in VMEM right after their ``xv``/
   ``Σxv²`` contributions land in the chained accumulator, so the
   F × [B, w] ``rows`` set never materializes in HBM. Traffic/field:
   read B·w (rows via DMA) + RW B·(w+1) (accumulator) versus XLA's
   gather-write + re-read of every field's rows — the bytes model
   prices this NEUTRAL-at-best at rank-64 training shapes (the
   accumulator RW dominates), so the step lever wires only the
   backward; the forward ships as a priced standalone (small-batch
   serving candidate).

2. **Fused backward** (:func:`fm_bwd_segment_totals`): the compact
   path's per-field ``g_full`` construction (the gfull_fused expression,
   sparse._gfull_grads), the ``-lr`` scaling, AND the sorted-run segment
   totals (the :mod:`pallas_segsum` windowed one-hot) in ONE kernel.
   The per-field gradient set — F × [B, w], the dominant HBM term the
   round-5 cd-bf16 probe priced at +23% — is never written: per 512-lane
   tile the expanded rows are re-derived from the VMEM-resident
   ``urows`` block by the same one-hot that accumulates the totals, the
   gradient lives for one tile, and only the [cap, w] totals reach HBM.
   Traffic/field: read B·w (the reordered ``s1`` rows — the one sorted
   vector operand) + ~3·B scalars + resident cap·w, versus the
   reference's g_full write+read + sdelta reorder write+read + blocked
   prefix write+read (≈ 5·B·w). Numerics are the REFERENCE'S, not
   merely close: every elementwise expression and the totals matmul
   mirror the gfull_fused + segtotal_pallas path operation-for-
   operation, so fp32 results are BIT-EXACT against it
   (tests/test_pallas_fused.py) and bf16 is tolerance-bounded.

3. **Sel-blocked FFM body** (:func:`ffm_sel_scores` /
   :func:`ffm_sel_bwd`): the round-5 staged FFM lever as Pallas kernels
   — the [B, F, F, k] ``sel``/``dsel`` tensors (config 4's dominant HBM
   traffic) are GUARANTEED tile-resident instead of relying on XLA
   fusing the blocked einsums; loops mirror the ``sel_blocked`` XLA
   body exactly (bit-exact fp32).

Availability contract (the structured-fallback rule, ISSUE 8 satellite):
this module never ``assert``s — every backend/shape constraint raises
:class:`fm_spark_tpu.ops.PallasUnavailable`, and the build-time
``*_supported`` probes let the ``fused_embed='auto'`` lever degrade to
the XLA path instead of dying on an attachment without a working Pallas
lowering. Interpret or compiled is :func:`fm_spark_tpu.ops.
pallas_interpret`'s one decision (cpu interprets, tpu compiles).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fm_spark_tpu.ops import PallasUnavailable, pallas_interpret, vmem

# Forward gather tile: rows per grid program = DMA queue depth
# (pallas_fm._TILE's measured sweet spot).
_TILE_FWD = 256
# Backward tile: MUST equal pallas_segsum._TILE — the bit-exactness
# claim against the segtotal_pallas reference rests on identical tile
# decomposition, window alignment, and one-hot matmul shapes.
_TILE_BWD = 512
# FFM interaction tile: the [T, F, F·k] block is the VMEM budget driver
# (avazu shape F=23, k=16 fp32 → 4.7MB per lane-padded buffer at T=128).
_TILE_FFM = 128

_LANE = 128                   # Mosaic row-DMA lane alignment (pallas_fm)
_SMEM_ID_LIMIT = 64 * 1024    # scalar-prefetched int32 ids that fit SMEM
# fp32 contract precision for the one-hot matmuls (see pallas_segsum).
_EXACT = jax.lax.Precision.HIGHEST


# --------------------------------------------------------------------------
# Build-time support checks (the fused_embed lever's fallback inputs).
# --------------------------------------------------------------------------


def fm_fwd_supported(batch: int, width: int) -> str | None:
    """Reason the fused forward cannot run COMPILED at this shape, or
    None. Interpret mode is unrestricted."""
    if pallas_interpret():
        return None
    if width % _LANE:
        return (f"table width {width} is not a multiple of {_LANE} "
                "(Mosaic row-DMA lane alignment); pad the table width")
    padded = batch + (-batch) % _TILE_FWD
    if padded > _SMEM_ID_LIMIT:
        return (f"batch {batch} exceeds the scalar-prefetch SMEM id "
                f"budget ({_SMEM_ID_LIMIT}); split the batch")
    return None


def _bwd_vmem_limit(cap: int, width: int, store_bytes: int,
                    cd_bytes: int = 4) -> int:
    """``vmem_limit_bytes`` of the fused backward: the two resident
    [cap+T+8, w] blocks (fp32 totals, storage-dtype urows — single-
    buffered trivial windows), the streamed tiles, and the per-tile
    temporaries (one-hot and its transpose, expanded rows, gradient,
    totals). Raises PallasUnavailable over budget."""
    t = _TILE_BWD
    return vmem.limit_for(
        vmem.buffer_bytes((cap + t + 8, width))
        + vmem.buffer_bytes((cap + t + 8, width), store_bytes)
        + vmem.buffer_bytes((t, width), cd_bytes, buffers=2)    # s1s
        + vmem.buffer_bytes((4, t), buffers=2)                  # coef
        + vmem.buffer_bytes((1, t), buffers=2)                  # seg
        + 4 * vmem.buffer_bytes((t + 8, t))
        + 6 * vmem.buffer_bytes((t + 8, width)),
        f"resident totals+urows [(cap+{t + 8}), {width}] (lower "
        "compact_cap or use the XLA path)")


def fm_bwd_supported(cap: int, width: int,
                     store_bytes: int = 4) -> str | None:
    """Reason the fused backward cannot serve (cap, width) with a
    ``store_bytes``-wide storage dtype, or None. The VMEM-residency
    budget applies on EVERY backend (interpret included) — it is the
    design's hard envelope, same contract as pallas_segsum. No
    lane-alignment rule on ``width``: the kernel uses only blocked specs
    whose trailing block dims equal the array's and does no row DMA."""
    try:
        _bwd_vmem_limit(cap, width, store_bytes)
    except PallasUnavailable as e:
        return str(e)
    return None


def _ffm_vmem_limit(num_fields: int, rank: int, cd_bytes: int) -> int:
    """``vmem_limit_bytes`` of the sel-blocked FFM kernels: the
    [T, F, F·k] rows tile in and the dvs tile out (both streamed, so
    double-buffered), and one [T, F, k] sel/selT/dsel triple per owner
    field. Raises PallasUnavailable over budget."""
    t = _TILE_FFM
    tile = (t, num_fields, num_fields * rank)
    return vmem.limit_for(
        vmem.buffer_bytes(tile, cd_bytes, buffers=4)
        + vmem.buffer_bytes(tile, cd_bytes)             # the loaded R
        + 3 * vmem.buffer_bytes((t, num_fields, rank), cd_bytes),
        f"sel tile pair [{t}, {num_fields}, {num_fields}·{rank}]")


_MOSAIC_VERDICTS: dict = {}


def _mosaic_refusal(key, fn, *avals) -> str | None:
    """What the compiler says to ``fn(*avals)`` on the TPU — its words
    if it refuses, None if it compiles — asked once per ``key``. In
    interpret mode there is nothing to compile, so nothing to refuse."""
    if pallas_interpret():
        return None
    if key not in _MOSAIC_VERDICTS:
        try:
            jax.jit(fn).lower(*avals).compile()
            _MOSAIC_VERDICTS[key] = None
        except Exception as e:  # noqa: BLE001 — the verdict IS the result
            words = " ".join(f"{type(e).__name__}: {e}".split())
            _MOSAIC_VERDICTS[key] = words[:400]
    return _MOSAIC_VERDICTS[key]


def ffm_sel_supported(num_fields: int, rank: int,
                      cd_bytes: int = 4) -> str | None:
    """Reason the Pallas sel-blocked FFM kernels cannot serve this
    (F, k, compute-dtype) shape, or None. On the TPU the last word is
    the compiler's: both kernels are compiled at one tile, because
    Mosaic (libtpu 0.0.34) refuses their gather-style indexing and the
    diagonal's ``.at[].set`` scatter whatever the shape, and only asking
    keeps this probe right when that changes."""
    try:
        _ffm_vmem_limit(num_fields, rank, cd_bytes)
    except PallasUnavailable as e:
        return str(e)
    cd = {4: jnp.float32, 2: jnp.bfloat16}[cd_bytes]
    sds = jax.ShapeDtypeStruct
    rows = sds((_TILE_FFM, num_fields, num_fields * rank), cd)
    vals = sds((_TILE_FFM, num_fields), cd)

    def both(r, v, ds):
        return ffm_sel_scores(r, v), ffm_sel_bwd(r, v, ds)

    reason = _mosaic_refusal(("ffm_sel", num_fields, rank, cd_bytes), both,
                             rows, vals, sds((_TILE_FFM,), cd))
    return f"Mosaic refuses the kernels: {reason}" if reason else None


# --------------------------------------------------------------------------
# 1. Fused gather → FM interaction forward.
# --------------------------------------------------------------------------


def _fwd_kernel(ids_ref, x_ref, acc_ref, ssq_ref, table_ref,
                acc_out, ssq_out, rows, sems):
    t = rows.shape[0]
    base = pl.program_id(0) * t

    def start(j, carry):
        pltpu.make_async_copy(
            table_ref.at[ids_ref[base + j]], rows.at[j], sems.at[j]
        ).start()
        return carry

    jax.lax.fori_loop(0, t, start, 0)

    def wait(j, carry):
        pltpu.make_async_copy(
            table_ref.at[ids_ref[base + j]], rows.at[j], sems.at[j]
        ).wait()
        return carry

    jax.lax.fori_loop(0, t, wait, 0)
    # The gathered tile's entire contribution lands here and the rows
    # buffer is reused by the next tile — no HBM materialization.
    xv = rows[...].astype(acc_out.dtype) * x_ref[...]
    k = xv.shape[1] - 1
    acc_out[...] = acc_ref[...] + xv
    ssq_out[...] = ssq_ref[...] + jnp.sum(
        xv[:, :k] * xv[:, :k], axis=1, keepdims=True
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fwd_field(table, ids, x, acc, ssq, interpret=False):
    b = ids.shape[0]
    w = table.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b // _TILE_FWD,),
        in_specs=[
            pl.BlockSpec((_TILE_FWD, 1), lambda i, ids: (i, 0)),   # x
            pl.BlockSpec((_TILE_FWD, w), lambda i, ids: (i, 0)),   # acc
            pl.BlockSpec((_TILE_FWD, 1), lambda i, ids: (i, 0)),   # ssq
            pl.BlockSpec(memory_space=pl.ANY),                     # table
        ],
        out_specs=(
            pl.BlockSpec((_TILE_FWD, w), lambda i, ids: (i, 0)),
            pl.BlockSpec((_TILE_FWD, 1), lambda i, ids: (i, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((_TILE_FWD, w), table.dtype),
            pltpu.SemaphoreType.DMA((_TILE_FWD,)),
        ],
    )
    return pl.pallas_call(
        _fwd_kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((b, w), acc.dtype),
            jax.ShapeDtypeStruct((b, 1), acc.dtype),
        ),
        input_output_aliases={2: 0, 3: 1},  # acc, ssq (after prefetch + x)
        interpret=interpret,
    )(ids, x, acc, ssq, table)


def fm_fused_scores(tables, ids, vals, *, use_linear: bool = True,
                    w0=None, compute_dtype=jnp.float32,
                    interpret: bool | None = None):
    """Fused gather→FM-interaction forward over per-field tables.

    ``tables``: F × [bucket, k+1] (fused-linear layout); ``ids``/``vals``
    [B, F]. Returns ``(scores [B], acc [B, k+1])`` — ``acc`` cols [:k]
    are ``s`` (the xv sum) and col k the linear-term sum, i.e. the
    forward residuals a backward needs. The per-field accumulation, the
    ``Σxv²`` chain, and the score assembly mirror sparse.py's
    association order; XLA may still re-tile the row reductions, so
    fp32 scores agree to ULP-level tolerance, not bitwise
    (tests/test_pallas_fused.py pins atol=1e-5 at unit-scale operands).
    """
    interpret = pallas_interpret() if interpret is None else interpret
    b, num_fields = ids.shape
    w = tables[0].shape[1]
    if not interpret:
        reason = fm_fwd_supported(b, w)
        if reason:
            raise PallasUnavailable(f"fm_fused_scores: {reason}")
    cd = jnp.dtype(compute_dtype)
    pad = (-b) % _TILE_FWD
    acc = jnp.zeros((b + pad, w), cd)
    ssq = jnp.zeros((b + pad, 1), cd)
    for f in range(num_fields):
        # Clip keeps padding/sentinel ids in-range; gathers are
        # side-effect free and padded lanes carry x = 0.
        idcol = jnp.pad(
            jnp.clip(ids[:, f], 0, tables[f].shape[0] - 1), (0, pad)
        ).astype(jnp.int32)
        x = jnp.pad(vals[:, f].astype(cd), (0, pad))[:, None]
        acc, ssq = _fwd_field(tables[f], idcol, x, acc, ssq,
                              interpret=interpret)
    acc, ssq = acc[:b], ssq[:b, 0]
    k = w - 1
    s = acc[:, :k]
    scores = 0.5 * (jnp.sum(s * s, axis=1) - ssq)
    if use_linear:
        scores = scores + acc[:, k]
    if w0 is not None:
        scores = scores + w0.astype(cd)
    return scores, acc


# --------------------------------------------------------------------------
# 2. Fused g_full + segment-totals backward (the compact update's core).
# --------------------------------------------------------------------------


def _bwd_kernel(first_ref, seg_ref, coef_ref, s1s_ref, neglr_ref, rv_ref,
                urows_ref, out_ref, *, k, use_rv):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    t = s1s_ref.shape[0]
    # Window math mirrors pallas_segsum._kernel exactly (sublane-aligned
    # start, T+8 rows absorbing the offset) — the bit-exactness anchor.
    first = first_ref[i]
    first_a = pl.multiple_of((first // 8) * 8, 8)
    seg = seg_ref[0, 0, :]                                  # [T] int32
    local = seg - first_a
    onehot = (
        local[None, :]
        == jax.lax.broadcasted_iota(jnp.int32, (t + 8, t), 0)
    ).astype(jnp.float32)                                   # [T+8, T]
    win = pl.ds(first_a, t + 8)
    cd = s1s_ref.dtype
    # Expanded rows re-derived from the RESIDENT urows block by the same
    # one-hot (0/1 matmul == exact gather for finite rows — at fp32
    # contract precision; the MXU's default pass would round fp32 rows
    # to bf16): the [B, w] per-field row expansion never exists off-chip
    # either.
    rows = jnp.dot(
        jnp.swapaxes(onehot, 0, 1),
        urows_ref[win, :].astype(jnp.float32),
        preferred_element_type=jnp.float32,
        precision=_EXACT,
    ).astype(cd)                                            # [T, w]
    # The per-lane coefficients ride as fp32 (exact for values already
    # rounded to the compute dtype): Mosaic cannot turn a bf16 lane
    # vector into a sublane column ("unsupported shape cast", v5e).
    ds = coef_ref[0, 0, :][:, None].astype(cd)
    x = coef_ref[0, 1, :][:, None].astype(cd)
    tch = coef_ref[0, 2, :][:, None].astype(cd)
    colmask = jax.lax.broadcasted_iota(jnp.int32, (1, k + 1), 1) < k
    # The gfull_fused expression, verbatim (sparse._gfull_grads):
    #   g = ds·(s1 − mask·xv_full)·x  (+ rv·rows·touched)
    xv = rows * x
    base = ds * (s1s_ref[...] - jnp.where(colmask, xv, jnp.zeros((), cd)))
    g = base * x
    if use_rv:
        g = g + rv_ref[...] * rows * tch
    d = neglr_ref[0, 0] * g                                 # f32 deltas
    totals = jnp.dot(onehot, d.astype(jnp.float32),
                     preferred_element_type=jnp.float32,
                     precision=_EXACT)                      # [T+8, w]
    out_ref[win, :] = out_ref[win, :] + totals


@functools.partial(jax.jit, static_argnames=("k", "cap", "interpret"))
def fm_bwd_segment_totals(urows, s1s, ds_s, x_s, tch_s, seg_s, neg_lr,
                          rv=None, *, k: int, cap: int,
                          interpret: bool = False):
    """Per-segment totals of the fused ``-lr·g_full`` deltas, with the
    gradient built ON-CHIP from sorted scalar streams + the resident
    unique-row block — the [B, w] gradient set never touches HBM.

    Sorted-by-segment per-lane streams (``[order_f]`` of the original
    lanes): ``s1s`` [B, k+1] (the shared ``[s, lin_on]`` rows — the one
    vector operand), ``ds_s``/``x_s``/``tch_s`` [B] (dscores, the
    field's x, touched as 0/1 floats, all compute dtype), ``seg_s`` [B]
    non-decreasing DENSE ranks (``inv[order]``; the pallas_segsum
    precondition — values ≥ cap drop to the trash row). ``urows``
    [cap, w] storage dtype; ``neg_lr`` f32 scalar; ``rv`` optional
    [k+1] per-column reg vector (compute dtype) — None skips the reg
    term entirely (matching the reference's conditional add).

    Returns [cap, w] fp32 totals — exactly what
    ``ops.scatter.compact_apply_totals`` writes. fp32 results are
    bit-exact against ``_gfull_grads`` + ``pallas_segsum
    .segment_totals`` composed (same tile size, window math, and matmul
    shapes; pinned in tests/test_pallas_fused.py).
    """
    b, w = s1s.shape
    if w != k + 1:
        raise PallasUnavailable(
            f"fm_bwd_segment_totals: s1s width {w} != k+1 ({k + 1})")
    t = _TILE_BWD
    cd = s1s.dtype
    vmem_limit = _bwd_vmem_limit(cap, w, jnp.dtype(urows.dtype).itemsize,
                                 jnp.dtype(cd).itemsize)
    pad = (-b) % t
    if pad:
        s1s = jnp.pad(s1s, ((0, pad), (0, 0)))
        ds_s = jnp.pad(ds_s, (0, pad))
        x_s = jnp.pad(x_s, (0, pad))
        tch_s = jnp.pad(tch_s, (0, pad))
        # Padding lanes park on the trash row with zero coefficients.
        seg_s = jnp.pad(seg_s, (0, pad), constant_values=cap)
    seg_s = jnp.minimum(seg_s, cap)                # clamp overflow
    nb = s1s.shape[0] // t
    first = seg_s[::t].astype(jnp.int32)           # [nb] prefetch
    seg3d = seg_s.reshape(nb, 1, t).astype(jnp.int32)
    coef = jnp.stack(
        [ds_s.astype(cd), x_s.astype(cd), tch_s.astype(cd),
         jnp.zeros_like(x_s, cd)], axis=0,
    ).astype(jnp.float32).reshape(4, nb, t).transpose(1, 0, 2)  # [nb, 4, t]
    neglr = jnp.asarray(neg_lr, jnp.float32).reshape(1, 1)
    use_rv = rv is not None
    rv_arr = (rv.astype(cd) if use_rv else jnp.zeros((w,), cd))[None, :]
    # Rows ≥ cap (the trash window) read zeros, so clamped/overflow
    # lanes expand to zero rows — the mask_overflow drop semantics.
    urows_pad = jnp.pad(urows, ((0, cap + t + 8 - urows.shape[0]),
                                (0, 0)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, 1, t), lambda i, first: (i, 0, 0)),
            pl.BlockSpec((1, 4, t), lambda i, first: (i, 0, 0)),
            pl.BlockSpec((t, w), lambda i, first: (i, 0)),
            pl.BlockSpec((1, 1), lambda i, first: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, w), lambda i, first: (0, 0)),
            # Constant index maps: urows + the totals accumulator stay
            # VMEM-resident across the sequential grid.
            pl.BlockSpec((cap + t + 8, w), lambda i, first: (0, 0)),
        ],
        out_specs=pl.BlockSpec((cap + t + 8, w), lambda i, first: (0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_bwd_kernel, k=k, use_rv=use_rv),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((cap + t + 8, w), jnp.float32),
        # "arbitrary" = sequential: every tile read-modify-writes the
        # one resident accumulator.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit),
        interpret=interpret,
    )(first, seg3d, coef, s1s, neglr, rv_arr, urows_pad)
    return out[:cap]


# --------------------------------------------------------------------------
# 3. Sel-blocked FFM interaction (forward + dvs backward).
# --------------------------------------------------------------------------


def _ffm_fwd_kernel(r_ref, x_ref, out_ref, *, num_fields, rank):
    F, kk = num_fields, rank
    R = r_ref[...]                                  # [T, F, F·k]
    x = x_ref[...]                                  # [T, F]
    t = R.shape[0]
    Rv = R.reshape(t, F, F, kk)
    # Verbatim mirror of the sel_blocked XLA body's owner-field loop —
    # each [T, F, k] pair lives only inside this tile.
    acc = jnp.zeros((t,), x.dtype)
    for i in range(F):
        sel_i = Rv[:, i] * x[:, i, None, None]
        selT_i = Rv[:, :, i, :] * x[:, :, None]
        prod = jnp.sum(sel_i * selT_i, axis=-1)     # [T, F]
        acc = acc + jnp.sum(prod, axis=1) - prod[:, i]
    out_ref[...] = acc[:, None]


def _ffm_bwd_kernel(r_ref, x_ref, ds_ref, out_ref, *, num_fields, rank):
    F, kk = num_fields, rank
    R = r_ref[...]
    x = x_ref[...]
    ds = ds_ref[...][:, 0]
    t = R.shape[0]
    Rv = R.reshape(t, F, F, kk)
    for i in range(F):
        selT_i = Rv[:, :, i, :] * x[:, :, None]
        dsel_i = ds[:, None, None] * selT_i
        dsel_i = dsel_i.at[:, i, :].set(0)          # zero diagonal
        out_ref[:, i, :] = (
            dsel_i * x[:, i, None, None]
        ).reshape(t, F * kk)


def _ffm_check(rows_stacked):
    """``(b, F, k, compiler_params)`` of one sel-blocked FFM call."""
    b, num_fields, fk = rows_stacked.shape
    rank = fk // num_fields
    if rank * num_fields != fk:
        raise PallasUnavailable(
            f"ffm_sel: packed width {fk} is not divisible by the field "
            f"count {num_fields}")
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel",),
        vmem_limit_bytes=_ffm_vmem_limit(
            num_fields, rank, jnp.dtype(rows_stacked.dtype).itemsize))
    return b, num_fields, rank, params


@functools.partial(jax.jit, static_argnames=("interpret",))
def ffm_sel_scores(rows_stacked, vals, *, interpret: bool = False):
    """Pairwise FFM interaction accumulator from stacked per-field rows
    ``[B, F, F·k]`` and ``vals`` [B, F] — returns ``acc`` [B] with
    ``scores = 0.5·acc`` (the caller applies the ½, mirroring the
    sel_blocked body). The [B, F, F, k] sel tensor exists only as one
    [T, F, k] pair per owner field per tile."""
    b, num_fields, rank, params = _ffm_check(rows_stacked)
    t = _TILE_FFM
    pad = (-b) % t
    if pad:
        rows_stacked = jnp.pad(rows_stacked, ((0, pad), (0, 0), (0, 0)))
        vals = jnp.pad(vals, ((0, pad), (0, 0)))
    nb = rows_stacked.shape[0] // t
    fk = num_fields * rank
    out = pl.pallas_call(
        functools.partial(_ffm_fwd_kernel, num_fields=num_fields,
                          rank=rank),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((t, num_fields, fk), lambda i: (i, 0, 0)),
            pl.BlockSpec((t, num_fields), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((t, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_stacked.shape[0], 1),
                                       rows_stacked.dtype),
        compiler_params=params,
        interpret=interpret,
    )(rows_stacked, vals.astype(rows_stacked.dtype))
    return out[:b, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def ffm_sel_bwd(rows_stacked, vals, dscores, *, interpret: bool = False):
    """Per-owner-field factor gradients ``dvs`` [B, F, F·k] from the
    sel-blocked backward — ``dsel`` is tile-resident; only the gradient
    set the scatter consumes is written (the same contract as the XLA
    sel_blocked body, now guaranteed rather than fusion-dependent)."""
    b, num_fields, rank, params = _ffm_check(rows_stacked)
    t = _TILE_FFM
    pad = (-b) % t
    if pad:
        rows_stacked = jnp.pad(rows_stacked, ((0, pad), (0, 0), (0, 0)))
        vals = jnp.pad(vals, ((0, pad), (0, 0)))
        dscores = jnp.pad(dscores, (0, pad))
    nb = rows_stacked.shape[0] // t
    fk = num_fields * rank
    out = pl.pallas_call(
        functools.partial(_ffm_bwd_kernel, num_fields=num_fields,
                          rank=rank),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((t, num_fields, fk), lambda i: (i, 0, 0)),
            pl.BlockSpec((t, num_fields), lambda i: (i, 0)),
            pl.BlockSpec((t, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((t, num_fields, fk), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (rows_stacked.shape[0], num_fields, fk), rows_stacked.dtype),
        compiler_params=params,
        interpret=interpret,
    )(rows_stacked, vals.astype(rows_stacked.dtype),
      dscores.astype(rows_stacked.dtype)[:, None])
    return out[:b]


# --------------------------------------------------------------------------
# Kernel registry: one tiny interpret-mode invocation per shipped Pallas
# kernel (tier-1 smoke, tests/test_pallas_smoke.py — ISSUE 8 satellite).
# --------------------------------------------------------------------------


def interpret_smokes():
    """``name → thunk`` running every Pallas kernel in the repo at a tiny
    interpret-mode shape; each thunk returns the kernel's output so the
    smoke can assert finiteness. New kernels REGISTER HERE — the smoke
    test pins this registry against the ``ops/pallas_*`` module surface.
    """
    import numpy as np

    from fm_spark_tpu.ops import pallas_fm, pallas_segsum

    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(size=(64, 8)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, 64, size=256), jnp.int32)
    uids = jnp.asarray(rng.permutation(64)[:64].astype(np.int32))
    uids = jnp.pad(uids, (0, 256 - 64))
    valid = jnp.pad(jnp.ones((64,), jnp.int32), (0, 256 - 64))
    delta = jnp.asarray(rng.normal(size=(256, 8)), jnp.float32)
    seg = jnp.asarray(np.sort(rng.integers(0, 16, 128)), jnp.int32)
    sdelta = jnp.asarray(rng.normal(size=(128, 8)), jnp.float32)
    tables = [jnp.asarray(rng.normal(size=(32, 5)), jnp.float32)
              for _ in range(3)]
    fids = jnp.asarray(rng.integers(0, 32, size=(48, 3)), jnp.int32)
    fvals = jnp.asarray(rng.uniform(0.5, 1.5, (48, 3)), jnp.float32)
    urows = jnp.asarray(rng.normal(size=(16, 5)), jnp.float32)
    s1s = jnp.asarray(rng.normal(size=(48, 5)), jnp.float32)
    lane = jnp.asarray(rng.normal(size=48), jnp.float32)
    seg48 = jnp.asarray(np.sort(rng.integers(0, 16, 48)), jnp.int32)
    rstk = jnp.asarray(rng.normal(size=(48, 3, 12)), jnp.float32)
    return {
        "pallas_fm.gather_rows": lambda: pallas_fm.gather_rows(
            table, ids, interpret=True),
        "pallas_fm.update_rows_add": lambda: pallas_fm.update_rows_add(
            jnp.copy(table), uids, valid, delta, interpret=True),
        "pallas_segsum.segment_totals":
            lambda: pallas_segsum.segment_totals(
                sdelta, seg, 16, interpret=True),
        "pallas_fused.fm_fused_scores": lambda: fm_fused_scores(
            tables, fids, fvals, interpret=True)[0],
        "pallas_fused.fm_bwd_segment_totals":
            lambda: fm_bwd_segment_totals(
                urows, s1s, lane, lane, jnp.ones_like(lane), seg48,
                jnp.float32(-0.1), None, k=4, cap=16, interpret=True),
        "pallas_fused.ffm_sel_scores": lambda: ffm_sel_scores(
            rstk, fvals, interpret=True),
        "pallas_fused.ffm_sel_bwd": lambda: ffm_sel_bwd(
            rstk, fvals, lane, interpret=True),
    }
