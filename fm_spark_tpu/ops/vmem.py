"""VMEM accounting for the Pallas kernels: count what Mosaic allocates.

A VMEM buffer is tiled ``(sublanes, 128 lanes)``: the last dim pads to
128 lanes and the second-to-last to the dtype's sublane tile (8 rows of
32-bit values; 16 of 16-bit; 32 of 8-bit). A ``[16904, 65]`` fp32 block
is therefore 8.65 MB, not the 4.4 MB its element count suggests. Blocks
the pipeline streams are double-buffered; a block that covers its whole
array under a constant-zero index map is single-buffered (jax's Mosaic
lowering forces ``synchronous`` for such trivial windows).

Every kernel that keeps a block resident sums its buffers with
:func:`buffer_bytes`, adds its in-kernel temporaries, and asks
:func:`limit_for` for the ``vmem_limit_bytes`` to compile under — or
the :class:`~fm_spark_tpu.ops.PallasUnavailable` that makes the lever
refuse at build time.
"""

from __future__ import annotations

import math

from fm_spark_tpu.ops import PallasUnavailable, pallas_interpret

LANES = 128
#: Mosaic's scoped-VMEM limit when ``vmem_limit_bytes`` is not given.
DEFAULT_LIMIT = 16 * 1024 * 1024
#: Physical VMEM of the TPU v5e's TensorCore — what interpret mode (no
#: chip to ask) budgets for, so CPU tests refuse what the chip would.
_V5E_CAPACITY = 128 * 1024 * 1024


def buffer_bytes(shape, itemsize: int = 4, buffers: int = 1) -> int:
    """Bytes ``buffers`` VMEM copies of a ``shape`` block of
    ``itemsize``-byte elements occupy after (sublane, lane) tiling."""
    sublanes = 8 * max(1, 4 // itemsize)
    *lead, rows, cols = (1, *shape) if len(shape) == 1 else shape
    padded = (math.ceil(rows / sublanes) * sublanes
              * math.ceil(cols / LANES) * LANES)
    return buffers * math.prod(lead) * padded * itemsize


def usable_bytes() -> int:
    """VMEM a kernel may claim: three quarters of the TensorCore's
    physical capacity (the rest is Mosaic's own scratch and spills)."""
    if pallas_interpret():
        return _V5E_CAPACITY * 3 // 4
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.get_tpu_info().vmem_capacity_bytes * 3 // 4


def limit_for(need: int, what: str) -> int:
    """``vmem_limit_bytes`` for a kernel whose buffers and temporaries
    total ``need`` bytes (a quarter is added as headroom). Raises
    :class:`PallasUnavailable` when that exceeds :func:`usable_bytes`."""
    want = need + need // 4
    usable = usable_bytes()
    if want > usable:
        raise PallasUnavailable(
            f"{what} needs {want / 2**20:.1f} MiB of VMEM (lane-padded "
            f"buffers + temporaries), over the {usable / 2**20:.0f} MiB "
            "a kernel may claim on this chip")
    return max(DEFAULT_LIMIT, want)
