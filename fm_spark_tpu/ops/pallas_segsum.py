"""Pallas TPU kernel: sorted-run SEGMENT TOTALS for the compact update.

The compact path's update half needs, per field, the per-segment sums of
the sorted deltas (``compact_apply``). The shipped XLA formulation is a
blocked two-level fp32 prefix + cap-lane boundary gathers (round 3,
+11%); its remaining cost is one full write+read pass of the [B, w]
block-prefix buffer. This kernel computes the totals DIRECTLY — one
streaming read of the sorted deltas, one [cap, w] output — with no
prefix materialization at all (the round-4 "next levers" candidate,
VERDICT r4 #2a).

Why the round-4 sketch rejection ("per-tile variable segment counts
force overlapping output windows or a disjoint [B, w] partials buffer")
does not hold: a TPU Pallas grid is SEQUENTIAL and the whole [cap+T, w]
output block stays VMEM-resident under a constant index map (cap=16384,
w=65 fp32 = 8.65MB once lane-padded to 128), so each tile can
read-modify-write the dynamic
window ``out[first_seg(tile) : +T]`` — boundary segments spanning tiles
accumulate correctly through the resident block, no clobbering, no
partials buffer. Within a tile the totals are ONE one-hot matmul on the
MXU (``onehot[s, t] = (seg[t] − first == s)``, [T, T]·[T, w]), so the
VPU never loops lanes.

Traffic: read B·w (sorted deltas) + write cap·w — versus the XLA
prefix's read B·w + write B·w + read-at-boundaries. Upside ≈ the
remaining half of the blocked-prefix cost. Behind
``TrainConfig.segtotal_pallas``; interpret-mode semantics pinned in
tests/test_pallas_segsum.py; ``chip_smoke.py`` compiles it at config 3's
shape against its ``jax.numpy`` reference.

Overflow semantics (device-built aux): lanes whose segment index
reached past ``cap`` are clamped to the trash row ``cap`` outside the
kernel; trash accumulates into ``out[cap:]`` and is trimmed, so
overflow contributions can never corrupt a real segment — exactly the
masked-drop contract of ``_compact_gather_all(mask_overflow=True)``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fm_spark_tpu.ops import vmem

# Lanes per grid step. 512 makes the one-hot matmul a [512, 512]·[512, w]
# MXU op and bounds the per-tile distinct-segment count by construction
# (<= T), so the dynamic output window never needs more than T rows.
_TILE = 512
# The MXU's default pass rounds fp32 operands to bf16 — a 2e-3 relative
# error on every delta (measured on the v5e, PR 21), where the kernel
# declares fp32 sums. fp32 contract precision keeps the one-hot matmul
# the exact gather/sum it stands for.
_EXACT = jax.lax.Precision.HIGHEST


def _kernel(first_ref, seg_ref, x_ref, out_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    # The store window starts at first ROUNDED DOWN to a multiple of 8:
    # Mosaic requires sublane-aligned dynamic slices (and must be TOLD
    # the start is aligned), and the one-hot just grows 8 rows to absorb
    # the offset — local indices land in [0, T+8) instead of [0, T).
    first = first_ref[i]
    first_a = pl.multiple_of((first // 8) * 8, 8)
    seg = seg_ref[0, 0, :]                                 # [T] int32
    local = seg - first_a                                  # [0, T+8) valid
    onehot = (
        local[None, :]
        == jax.lax.broadcasted_iota(jnp.int32, (_TILE + 8, _TILE), 0)
    ).astype(jnp.float32)                                  # [T+8(seg), T(lane)]
    totals = jnp.dot(onehot, x_ref[...],
                     preferred_element_type=jnp.float32,
                     precision=_EXACT)                     # [T+8, w]
    win = pl.ds(first_a, _TILE + 8)
    out_ref[win, :] = out_ref[win, :] + totals


@functools.partial(jax.jit, static_argnames=("cap", "interpret"))
def segment_totals(sdelta: jax.Array, seg_sorted: jax.Array, cap: int,
                   interpret: bool = False) -> jax.Array:
    """Per-segment sums of sorted deltas: ``out[s] = Σ_{seg[t]=s} x[t]``.

    ``sdelta`` [B, w] float32, sorted by segment; ``seg_sorted`` [B]
    int32 non-decreasing (values ≥ cap = overflow, dropped to the trash
    row). Returns [cap, w] float32.

    PRECONDITION — dense ranks, not arbitrary ids: within any ``_TILE``
    consecutive lanes the segment values must span < ``_TILE`` (the
    one-hot window is [align8(first_seg(tile)), +_TILE+8) — first
    rounded down to a sublane multiple, 8 extra rows absorb the offset;
    a lane whose segment falls outside it contributes NOTHING,
    silently).
    Non-decreasing DENSE ranks (0, 0, 1, 2, 2, ...; every rank in
    [0, cap) occupied up to the unique count) satisfy this by
    construction — a tile of T lanes covers ≤ T distinct ranks — and
    that is exactly what both compact-aux builders emit (``inv`` is the
    cumsum-derived rank of each lane's id). Do NOT feed raw gapped ids;
    rank them first (one ``cumsum(seg[1:] != seg[:-1])``).
    """
    b, w = sdelta.shape
    t = _TILE
    # The whole [cap+T+8, w] fp32 accumulator stays VMEM-resident (that
    # residency IS the design — it's what makes the dynamic-window
    # read-modify-write race-free and partials-buffer-free), so its
    # lane-padded size is a hard budget: 8.65MB at the FM headline shape
    # (cap 16384, w 65), single-buffered as a trivial window. Refuse at
    # build time what the chip's VMEM cannot hold.
    vmem_limit = vmem.limit_for(
        vmem.buffer_bytes((cap + t + 8, w))             # resident totals
        + vmem.buffer_bytes((t, w), buffers=2)          # streamed deltas
        + vmem.buffer_bytes((1, t), buffers=2)          # segment ids
        + 3 * vmem.buffer_bytes((t + 8, t))             # iota/compare/one-hot
        + 3 * vmem.buffer_bytes((t + 8, w)),            # totals + window RMW
        f"segtotal_pallas accumulator [(cap+{t + 8}), {w}] fp32 (the "
        "kernel keeps the whole output resident; lower compact_cap or "
        "drop --segtotal-pallas for wide rows)")
    pad = (-b) % t
    if pad:
        sdelta = jnp.pad(sdelta, ((0, pad), (0, 0)))
        # Padding lanes carry zero values; park them on the trash row.
        seg_sorted = jnp.pad(seg_sorted, (0, pad),
                             constant_values=cap)
    seg_sorted = jnp.minimum(seg_sorted, cap)              # clamp overflow
    nb = sdelta.shape[0] // t
    first = seg_sorted[::t].astype(jnp.int32)              # [nb] prefetch
    # [nb, 1, t]: the singleton sublane dim makes the block's trailing
    # (1, t) EQUAL to the array's trailing dims — a (1, t) block on a
    # flat [nb, t] array violates Mosaic's (8, 128)-divisibility rule
    # (measured: lowering ValueError on chip, round 5).
    seg3d = seg_sorted.reshape(nb, 1, t).astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, 1, t), lambda i, first: (i, 0, 0)),
            pl.BlockSpec((t, w), lambda i, first: (i, 0)),
        ],
        # Constant index map: the [cap+T+8, w] accumulator stays
        # VMEM-resident across the sequential grid.
        out_specs=pl.BlockSpec((cap + t + 8, w), lambda i, first: (0, 0)),
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((cap + t + 8, w), jnp.float32),
        # "arbitrary" = sequential: every tile read-modify-writes the
        # one resident accumulator.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit),
        interpret=interpret,
    )(first, seg3d, sdelta)
    return out[:cap]
