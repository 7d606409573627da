"""What happened before the window, out of the program's ring of hot
intervals (``program_spans.ring()``), for the per-layer metrics that move
``setup_s``.

The program leaves one ``setup/run`` per ``cli train`` call (from
``cmd_train``'s entry to the instant before the loop's first step) and
one per ``PredictEngine`` (from its construction to the end of its first
``warmup()``), with the phases that take the time as ``setup/*``
intervals inside it, and one ``compile/trace``, ``compile/lower``,
``compile/backend`` (``cache_hit``) and ``compile/cache_read`` per
compilation, from jax's own events (``fm_spark_tpu/utils/compile_cache``).
The check's runs leave a ``setup/run`` each too: the cell's own is the
LAST one that ended before the window's first instant, which is the
start of the first ``train/step`` (``serve/queue``) that
``program_spans.train_window`` (``score_window``) selects. Only records
that END before that instant count.

A program without these records (the parent of the PR that added them):
``None`` from every reader, and the harness leaves the metric out.
"""

from __future__ import annotations

import json

from benchmark import program_spans
from benchmark.harness import log

RUN = "setup/run"
TABLES = ("setup/init", "setup/place", "setup/install")
BACKEND = "compile/backend"
COMPILE = ("compile/trace", "compile/lower", BACKEND)


def window_bounds(run, records) -> tuple[float, float] | None:
    """The window's first instant and the end of its last record."""
    window = program_spans.train_window(run, records, clean=False)
    if window is not None:
        steps = window[program_spans.STEP]
        return steps[0].t0, steps[-1].t1
    window = program_spans.score_window(run, records)
    if window is not None:
        queued = window[program_spans.QUEUE]
        return min(r.t0 for r in queued), max(r.t1 for r in queued)
    return None


def select(run, records=None) -> dict | None:
    """``{"first", "last", "run", "phases", "runs", "before", "inside"}``:
    the window's bounds, the cell's own ``setup/run``, the ``setup/*``
    records inside it (oldest first), every ``setup/run`` before the
    window, and the ``compile/*`` records that ended before the window
    and that lie inside it. None without a window or a ``setup/run``."""
    records = program_spans.ring() if records is None else records
    if not records:
        return None
    bounds = window_bounds(run, records)
    if bounds is None:
        return None
    first, last = bounds
    setup = [r for r in records
             if r.name.startswith("setup/") and r.t1 <= first]
    runs = [r for r in setup if r.name == RUN]
    if not runs:
        return None
    compiles = [r for r in records if r.name.startswith("compile/")]
    return {"first": first, "last": last, "run": runs[-1], "runs": runs,
            "phases": phases_of(runs[-1], setup),
            "before": [r for r in compiles if r.t1 <= first],
            "inside": [r for r in compiles if first <= r.t0 <= last]}


def phases_of(own, setup: list) -> list:
    """The ``setup/*`` records inside ``own`` (a ``setup/run``) on its
    thread, by start."""
    thread = getattr(own, "thread", None)
    return sorted((r for r in setup if r is not own and r.name != RUN
                   and own.t0 <= r.t0 and r.t1 <= own.t1
                   and getattr(r, "thread", None) == thread),
                  key=lambda r: r.t0)


def covered_s(records) -> float:
    """Seconds the records cover, thread by thread, an instant under two
    of them counted once (a trace inside a lowering; a lowering that
    begins where jax's clock says its trace has not quite ended)."""
    by_thread: dict = {}
    for r in records:
        by_thread.setdefault(getattr(r, "thread", None), []).append(r)
    total = 0.0
    for mine in by_thread.values():
        end = float("-inf")
        for r in sorted(mine, key=lambda r: r.t0):
            if r.t1 > end:
                total += r.t1 - max(r.t0, end)
                end = r.t1
    return total


# ----------------------------------------------- what the readers compute


def program_s(run) -> float | None:
    found = select(run)
    return None if found is None else found["run"].t1 - found["run"].t0


def tables_s(run) -> float | None:
    found = select(run)
    if found is None:
        return None
    return sum(r.t1 - r.t0 for r in found["phases"] if r.name in TABLES)


def warmup_s(run) -> float | None:
    found = select(run)
    return None if found is None else found["first"] - found["run"].t1


def compile_s(run) -> float | None:
    found = select(run)
    if found is None:
        return None
    return covered_s([r for r in found["before"] if r.name in COMPILE])


def fresh_compiles(run) -> int | None:
    found = select(run)
    if found is None:
        return None
    return sum(1 for r in found["before"]
               if r.name == BACKEND and not r.attrs.get("cache_hit"))


# ------------------------------------------- the whole picture, to stderr


def _s(seconds: float) -> float:
    return round(seconds, 4)


def _compile(r, records=None) -> dict:
    out = {"name": r.name, "s": _s(r.t1 - r.t0),
           **{k: r.attrs[k] for k in ("fun_name", "cache_hit") if k in r.attrs}}
    if records is not None:
        parent = next((p for p in records if p.span_id == r.parent_id), None)
        if parent is not None:
            out["under"] = parent.name
            out["step"] = parent.attrs.get("step")
    return out


def log_summary(run) -> None:
    """One stderr line a traced run's reader leaves for PERF.md: every
    ``setup/run`` before the window with its phases (each with the
    compile time inside it) and its self time, the ten longest
    ``compile/*`` with ``fun_name``, and every ``compile/*`` INSIDE the
    window with what it hangs under."""
    records = program_spans.ring()
    found = select(run, records)
    if found is None:
        return
    setup = [r for r in records if r.name.startswith("setup/")]
    before = found["before"]

    def compiling(t0, t1):
        return _s(covered_s([r for r in before if r.name in COMPILE
                             and t0 <= r.t0 and r.t1 <= t1]))

    runs = []
    for own in found["runs"]:
        phases = phases_of(own, setup)
        runs.append({
            **own.attrs, "s": _s(own.t1 - own.t0),
            "compile_s": compiling(own.t0, own.t1),
            "phases": [{"name": p.name, "s": _s(p.t1 - p.t0),
                        "compile_s": compiling(p.t0, p.t1), **p.attrs}
                       for p in phases],
            "self_s": _s(own.t1 - own.t0 - covered_s(phases))})
    by_name = {name: [r for r in before if r.name == name]
               for name in (*COMPILE, "compile/cache_read")}
    log("setup spans:", json.dumps({
        "runs": runs,
        "own_run_to_window_s": _s(found["first"] - found["run"].t1),
        "own_run_to_window_compile_s": compiling(found["run"].t1,
                                                 found["first"]),
        "compile_before_window": {
            name: {"n": len(rs), "s": _s(sum(r.t1 - r.t0 for r in rs))}
            for name, rs in by_name.items()},
        "longest": [_compile(r) for r in sorted(
            (r for r in before if r.name in COMPILE),
            key=lambda r: r.t0 - r.t1)[:10]],
        "compile_in_window": [_compile(r, records)
                              for r in found["inside"]]}))
