"""The least time one chip needs to move a step's bytes through HBM
(``benchmark/bytes.py``, at the peak of ``peaks.json``) over the device
time a step took (%). Bound by bandwidth: a step of these models does a
few hundred operations per row it moves."""

import importlib

from benchmark import bytes as step_bytes
from benchmark.layer_metrics import step_device_ms

PARAM_BYTES = {"float32": 4, "bfloat16": 2}


def read(run):
    step_ms = step_device_ms.read(run)
    if step_ms is None or run.peak is None:
        return None
    config = run.cell.config
    model = config["model"]
    ref = importlib.import_module(f"benchmark.reference.{config['reference']}")
    least = step_bytes.least_step_seconds(
        batch=run.log["batch"], fields=model["num_fields"],
        row_width=ref.row_width(model["num_fields"], model["rank"]),
        param_bytes=PARAM_BYTES[model["param_dtype"]],
        chips=run.log["chips"],
        hbm_bytes_per_s=run.peak["hbm_bytes_per_s"])
    return 100.0 * least / (step_ms * 1e-3)
