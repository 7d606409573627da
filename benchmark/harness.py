"""What the entry point and the drivers share: the manifest's files
resolved into a :class:`Cell`, the records that pass between harness,
driver and per-layer readers, and the device as JAX reports it."""

from __future__ import annotations

import dataclasses
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def log(*words) -> None:
    print("benchmark:", *words, file=sys.stderr, flush=True)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _named(entries: list, name: str, what: str) -> dict:
    found = [e for e in entries if e["name"] == name]
    if len(found) != 1:
        raise SystemExit(f"benchmark: BENCHMARK.json has {len(found)} "
                         f"{what} named {name!r}")
    return found[0]


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with every file it names, resolved."""

    name: str
    chips: int
    config: dict            # configs/<config>.json
    mix: dict               # traffic/<mix>.json, overridden by cells/<cell>.json
    end_to_end: list        # BENCHMARK.json metric entries this cell reports
    per_layer: list

    @property
    def driver(self) -> str:
        return self.mix["driver"]


def load_cell(name: str, rehearse: bool = False) -> Cell:
    manifest = _load(os.path.join(ROOT, "BENCHMARK.json"))
    entry = _named(manifest["workloads"], name, "workloads")
    config_entry = _named(manifest["configs"], entry["config"], "configs")
    config = _load(os.path.join(ROOT, config_entry["file"]))
    mix = load_mix(entry["traffic"])
    own = os.path.join(HERE, "cells", name + ".json")
    if os.path.exists(own):
        mix.update(_load(own))
    if rehearse:
        config = {**_rehearsal(config), "as_written": config}
        mix = _rehearsal(mix)

    def mine(metrics):
        return [m for m in metrics
                if "workloads" not in m or name in m["workloads"]]

    return Cell(name=name, chips=int(entry["chips"]), config=config, mix=mix,
                end_to_end=mine(manifest["end_to_end"]),
                per_layer=mine(manifest["per_layer"]))


def load_mix(name: str) -> dict:
    """``traffic/<name>.json``; a mix that says ``"like": "<other>"`` is
    that other mix with its own keys laid over it."""
    mix = _load(os.path.join(HERE, "traffic", name + ".json"))
    if "like" in mix:
        mix = {**load_mix(mix["like"]), **mix}
    return mix


def _rehearsal(doc: dict) -> dict:
    """``doc`` with its ``"rehearsal"`` values laid over it (one level
    into nested groups)."""
    out = dict(doc)
    for key, value in doc.get("rehearsal", {}).items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = {**out[key], **value}
        else:
            out[key] = value
    return out


def trace_span(mix: dict, seconds: float) -> tuple[float, float]:
    """``(seconds into the window, length)`` of the profiled span: the
    mix's, cut to fit a window shorter than the benchmark's."""
    return (min(float(mix["trace_after_seconds"]), 0.3 * seconds),
            min(float(mix["trace_seconds"]), 0.4 * seconds))


def start_trace(trace_dir: str) -> None:
    """The profiler on, without the Python tracer (per-call events of the
    host's interpreter would dwarf the device's)."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def peak_for(kind: str) -> dict:
    peaks = _load(os.path.join(HERE, "peaks.json"))
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in benchmark/peaks.json; "
                       "add its published peaks with their source")
    return peaks[kind]


def describe_device() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_chips(cell: "Cell", rehearse: bool = False) -> dict:
    """The device as JAX reports it; SystemExit (before any work, nothing
    on stdout) unless it is a TPU with exactly the cell's chips. A
    rehearsal takes whatever backend there is."""
    try:
        device = describe_device()
    except RuntimeError as e:       # no backend JAX may use came up
        raise SystemExit(f"benchmark: no accelerator — {e}") from e
    if not rehearse and (device["platform"] != "tpu"
                         or device["count"] != cell.chips):
        raise SystemExit(
            f"benchmark: cell {cell.name!r} needs a TPU with {cell.chips} "
            f"chip(s); JAX reports {device}. Nothing was run.")
    return device


def memory_peak_bytes() -> int:
    """``peak_bytes_in_use`` of the fullest chip (0 where the backend
    keeps no statistics: the CPU)."""
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


@dataclasses.dataclass
class Context:
    """What the harness hands a driver."""

    cell: Cell
    seed: int
    seconds: float
    t_start: float              # perf_counter at process start
    trace_dir: str | None       # traced run: where to point the profiler


@dataclasses.dataclass
class Result:
    """What a driver hands back (``drivers/*.py`` fill it)."""

    correct: bool
    attempted: int
    failed: int
    setup_s: float
    end_to_end: dict            # name -> value, every metric the driver takes
    counters: dict              # deltas over the window, by counter name
    log: dict                   # the window's own record (per driver kind)
    traced: dict | None = None  # {"seconds", "steps"?} of the profiled span
    notes: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class LayerRun:
    """What a per-layer reader sees of one traced run."""

    cell: Cell
    device: dict                # platform, kind, count, memory_peak_bytes
    counters: dict
    log: dict
    traced: dict | None         # the profiled span, as the driver timed it
    trace: dict | None          # trace_reduce.reduce() of it
    peak: dict | None           # peaks.json entry of this device kind
