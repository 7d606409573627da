"""Bytes the table update of one training step must move through HBM
under a per-coordinate rule with one slot (AdaGrad), from counts alone:
the numerator of ``opt_update_hbm_roofline``.

"Must" is a floor that no implementation can go under, not the program's
own tally: a rule that reads a row's TOTAL gradient touches each unique
row of the batch once, whatever the batch's duplicates, so per unique
row the row is read and written (in the parameters' dtype) and its
accumulator row is read and written (float32). The per-lane gradient
rows, the sort and the segment sums that coalescing costs are
activations and are left out, as ``bytes.py`` leaves activations out.
``unique_rows`` is what the program counted (the window's mean of the
``unique_rows`` its log lines carry: the batch's unique rows summed over
the fields); the check run holds that counter to the benchmark's own
count of its batch (``reference/sgd.py``'s ``touched``), exactly.
"""

from __future__ import annotations

DTYPE_BYTES = {"float32": 4, "bfloat16": 2}
SLOT_BYTES = 4        # accumulators are float32 whatever the parameters are


def update_bytes(*, unique_rows: float, row_width: int,
                 param_bytes: int) -> float:
    """Bytes of one step's update over ``unique_rows`` rows of
    ``row_width`` columns: row and slot row, each read once and written
    once."""
    return unique_rows * row_width * 2 * (param_bytes + SLOT_BYTES)


def least_update_seconds(*, unique_rows: float, row_width: int,
                         param_bytes: int, hbm_bytes_per_s: float) -> float:
    """The least time one chip needs for those bytes."""
    return update_bytes(unique_rows=unique_rows, row_width=row_width,
                        param_bytes=param_bytes) / hbm_bytes_per_s
