"""The least time the chip needs to move the bytes the update rule must
move (``benchmark/opt_bytes.py``: each unique row of the batch and its
accumulator row, read once and written once, at the HBM peak of
``peaks.json``) over the device time the WHOLE update took a step,
``opt_update_ms`` (%). Bound by bandwidth; the count is a floor no
implementation can go under (it leaves out the per-lane gradients, the
sort and the segment sums), so the share cannot pass 100%."""

from benchmark.layer_metrics import opt_update_ms


def read(run):
    update_ms = opt_update_ms.read(run)
    moved = run.log.get("opt_update_bytes")
    if not update_ms or not moved or run.peak is None:
        return None
    return 100.0 * moved / run.peak["hbm_bytes_per_s"] / (update_ms * 1e-3)
