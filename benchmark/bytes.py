"""Bytes one training step must move through HBM, from shapes alone —
the denominator of ``step_hbm_roofline``.

"Must" means the traffic no implementation of the step can avoid while it
touches tables lane by lane: every example reads one row per field,
reads and writes it again for the update, and reads its ids, values,
label and weight. Activations ([B, F, k] sums, FFM's [B, F, F, k]
products) can in principle stay on the chip and are left out, as is what
a dedup of hot ids would save: the share says how far the step as built
is from streaming its rows once, not how clever a step could be. The
terms follow ``fm_spark_tpu.obs.introspect.step_cost_model`` (gather,
update), with the row width taken from the configuration's reference
(that model prices FFM rows at ``rank + 1``; they are ``F * rank + 1``).
"""

from __future__ import annotations

ID_BYTES = 4          # int32 ids
VAL_BYTES = 4         # float32 feature values, labels, weights


def train_step_bytes(*, batch: int, fields: int, row_width: int,
                     param_bytes: int) -> dict[str, int]:
    """Bytes per family for one step over ``batch`` examples (the global
    batch; divide by the chips that share it)."""
    lanes = batch * fields
    return {
        "gather_rows_read": lanes * row_width * param_bytes,
        "update_rows_read": lanes * row_width * param_bytes,
        "update_rows_written": lanes * row_width * param_bytes,
        "ids": lanes * ID_BYTES,
        "vals_labels_weights": lanes * VAL_BYTES + 2 * batch * VAL_BYTES,
    }


def least_step_seconds(*, batch: int, fields: int, row_width: int,
                       param_bytes: int, chips: int,
                       hbm_bytes_per_s: float) -> float:
    """The least time one chip needs for its share of a step's bytes."""
    total = sum(train_step_bytes(batch=batch, fields=fields,
                                 row_width=row_width,
                                 param_bytes=param_bytes).values())
    return total / chips / hbm_bytes_per_s
