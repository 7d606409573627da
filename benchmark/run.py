#!/usr/bin/env python3
"""One cell of BENCHMARK.json, once, in one process.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Refuses anything but a TPU with exactly the cell's chips before doing
any work, lets the cell's driver warm up the cell's own shapes (set-up),
measures for ``--seconds`` and prints, as the last line of stdout, one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` and, traced, ``breakdown``. With ``--trace 0`` the metrics are
the cell's end-to-end metrics; with ``--trace 1`` a 3-5 s part of the
window is profiled and they are its per-layer metrics. Everything else
(the program's own output included) goes to stderr.

The harness is data: a cell names a configuration
(``configs/<name>.json``), a traffic mix (``traffic/<mix>.json``, which
names its driver kind, ``drivers/<kind>.py``) and may keep numbers of its
own in ``cells/<cell>.json`` (they override the mix's); a per-layer
metric is ``layer_metrics/<name>.py``. Adding any of them is adding
files and entries, never an edit here.

``--rehearse`` runs the same control flow at the tiny sizes the files
give under ``"rehearsal"``, on whatever backend JAX has (the CPU, with
virtual devices for a four-chip cell). A rehearsal proves paths and
arguments only: its line says ``"rehearsal": true``, ``"correct":
false`` and carries no metric.
"""

import time

T_START = time.perf_counter()     # as near to process start as Python gets

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _finite(value) -> bool:
    return isinstance(value, (int, float)) and math.isfinite(value)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend; prints no metric")
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    from benchmark.harness import (
        Context, LayerRun, Result, load_cell, log, memory_peak_bytes,
        peak_for, require_chips)

    cell = load_cell(args.workload, rehearse=args.rehearse)
    if args.rehearse and cell.chips > 1:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{cell.chips}").strip()

    # Before anything touches the chip: is the rest of the repo here?
    import fm_spark_tpu  # noqa: F401

    device = require_chips(cell, rehearse=args.rehearse)
    peak = None if args.rehearse else peak_for(device["kind"])
    log(f"cell {cell.name} on {device}, seed {args.seed}, "
        f"{args.seconds:g} s, trace {args.trace}"
        + (", REHEARSAL" if args.rehearse else ""))

    # The profiler writes outside the checkout (a tree too large to copy
    # breaks the driver's check) and the directory goes when read.
    trace_dir = (tempfile.mkdtemp(prefix="fm_bench_trace_")
                 if args.trace else None)
    try:
        driver = importlib.import_module(f"benchmark.drivers.{cell.driver}")
        result: Result = driver.run(Context(
            cell=cell, seed=args.seed, seconds=args.seconds,
            t_start=T_START, trace_dir=trace_dir))
        reduced = None
        if trace_dir is not None:
            from benchmark import trace_reduce

            xplane = trace_reduce.find_xplane(trace_dir)
            reduced = trace_reduce.reduce(xplane) if xplane else None
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    device["memory_peak_bytes"] = memory_peak_bytes()
    correct = bool(result.correct)
    breakdown = None
    if args.trace:
        run = LayerRun(cell=cell, device=device, counters=result.counters,
                       log=result.log, traced=result.traced, trace=reduced,
                       peak=peak)
        wanted = cell.per_layer
        values = {m["name"]: importlib.import_module(
            f"benchmark.layer_metrics.{m['name']}").read(run)
            for m in wanted}
        if reduced is not None and result.traced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = result.traced["seconds"]
            breakdown = {"device_ops": reduced["device_ops"],
                         "idle_gaps": reduced["idle_gaps"]}
            log("trace per chip:", json.dumps(reduced["per_chip"]))
        elif not args.rehearse:
            log("the traced span held no device operation")
            correct = False
    else:
        wanted = cell.end_to_end
        values = {**result.end_to_end, "setup_s": result.setup_s}
        missing = [m["name"] for m in wanted if not _finite(
            values.get(m["name"]))]
        if missing:
            log(f"end-to-end metrics without a finite value: {missing}")
            correct = False

    metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in wanted if _finite(values.get(m["name"]))}
    log("notes:", json.dumps(result.notes))
    line = {"correct": correct, "attempted": int(result.attempted),
            "failed": int(result.failed), "metrics": metrics,
            "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    if args.rehearse:
        # Never a result: a CPU number is not written under a device
        # metric's name, and a rehearsal is never "correct".
        line = {"rehearsal": True, "correct": False,
                "attempted": line["attempted"], "failed": line["failed"],
                "metrics": {}, "device": device,
                "rehearsed": {"checks_passed": correct,
                              "values": {k: v["value"]
                                         for k, v in metrics.items()}}}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
