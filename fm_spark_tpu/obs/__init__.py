"""Unified telemetry plane: span tracing, metrics, flight recorder.

One per-run directory (the ISSUE 7 convention — ``artifacts/obs/
<run_id>/``) holds every stream a run emits, so "where did this run
spend its time, what faulted, and what did ingest/step-rate look like"
is one directory instead of five formats:

======================  ====================================================
``trace.jsonl``         span records (:mod:`fm_spark_tpu.obs.trace`)
``metrics.jsonl``       registry snapshots (:mod:`fm_spark_tpu.obs.metrics`)
``flight.jsonl``        flight-recorder spool — last-N window, SIGKILL-safe
``flight_dump.json``    atomic last-N dump on fault/SIGTERM/run end
``health*.jsonl``       the resilience health journals (EventLog)
``deadletter.jsonl``    quarantined-record journal (RecordGuard)
======================  ====================================================

``tools/obs_report.py`` renders a human-readable run report from such a
directory; ``bench.py`` stamps :func:`telemetry_block` into its result
JSON.

This module is the instrumentation facade the rest of the codebase
calls. Everything is a cheap no-op until :func:`configure` runs —
library code instruments unconditionally and pays (almost) nothing in
un-observed processes (the ≤1% disabled-path contract,
tests/test_obs_overhead.py). Two things are the exception, always live
and memory only: the metrics registry, so counters/gauges accumulate
even without a run directory, and the ring of hot intervals
(:func:`interval`, :func:`record_interval`, :func:`intervals`) that the
training loop, the prefetch producer and the serve coalescer record
into.
"""

from __future__ import annotations

import functools
import os
import signal as _signal
import threading
import time

from fm_spark_tpu.obs import introspect
from fm_spark_tpu.obs.flight import FlightRecorder, read_spool
from fm_spark_tpu.obs.ledger import (
    PerfLedger,
    default_ledger_path,
    measurement_fingerprint,
)
from fm_spark_tpu.obs.metrics import MetricsRegistry, registry
from fm_spark_tpu.obs.sentinel import (
    Sentinel,
    SentinelPolicy,
    keepbest_allowed,
)
from fm_spark_tpu.obs.trace import (
    Interval,
    NOOP_SPAN,
    RING_CAPACITY,
    Span,
    TRACE_HEADER,
    TraceContext,
    Tracer,
)
from fm_spark_tpu.obs import trace as _trace_mod

__all__ = [
    "FAULT_KINDS",
    "FlightRecorder",
    "Interval",
    "MetricsRegistry",
    "PerfLedger",
    "RING_CAPACITY",
    "Sentinel",
    "SentinelPolicy",
    "Span",
    "TRACE_HEADER",
    "TraceContext",
    "Tracer",
    "configure",
    "counter",
    "default_ledger_path",
    "device_memory_snapshot",
    "emit_span",
    "enabled",
    "event",
    "export_snapshot",
    "fault_timeline",
    "flight_dump",
    "gauge",
    "histogram",
    "install_signal_dump",
    "interval",
    "intervals",
    "introspect",
    "keepbest_allowed",
    "measurement_fingerprint",
    "mint_trace",
    "new_run_id",
    "read_spool",
    "record_interval",
    "registry",
    "run_dir",
    "run_id",
    "shutdown",
    "span",
    "telemetry_block",
    "traced",
]

TRACE_FILE = "trace.jsonl"
METRICS_FILE = "metrics.jsonl"
FLIGHT_FILE = "flight.jsonl"
FLIGHT_DUMP_FILE = "flight_dump.json"

#: Event kinds that belong on a run's fault/retry timeline (the health
#: journals' state transitions plus the ingest/checkpoint failure
#: events) — what :func:`fault_timeline` and the bench ``telemetry``
#: block surface.
FAULT_KINDS = frozenset({
    "failure", "backoff", "attempt", "probe",
    "circuit_open", "circuit_half_open", "circuit_rejected",
    "permanent_fault", "recovered", "supervisor_reset",
    "fault_classified", "mesh_shrink", "elastic_exhausted",
    "divergence_detected", "divergence_rollback",
    "divergence_rollback_exhausted",
    "ingest_aborted", "bad_record",
    "checkpoint_corrupt", "checkpoint_unverified_skipped",
    "checkpoint_unreadable", "checkpoint_walked_back",
    "backend_init_timeout", "down",
    "hang_detected", "reload_failed", "serve_batch_failed",
    # ISSUE 14: the live-introspection anomaly events — near-misses and
    # SLO overruns belong on the same timeline as the faults they
    # almost were, and a fired capture is the pointer to its evidence.
    "watchdog_near_miss", "serve_slo_overrun", "capture_fired",
})

_lock = threading.Lock()
_state = {"dir": None, "run_id": None, "tracer": None, "flight": None,
          "sink": None}
_prev_handlers: dict[int, object] = {}


def new_run_id() -> str:
    """UTC-timestamped, pid-suffixed run id — sortable and unique
    enough for one host's runs."""
    return time.strftime("%Y%m%d-%H%M%S", time.gmtime()) + f"-p{os.getpid()}"


def configure(obs_dir: str, run_id: str | None = None,
              enabled: bool = True, flight_capacity: int = 256,
              install_signals: bool = False,
              reset_metrics: bool = True) -> str:
    """Point the telemetry plane at a run directory and arm it.

    Creates ``obs_dir``, opens the trace sink (``trace.jsonl``) and the
    flight spool (``flight.jsonl`` — appended, so a retried attempt
    re-entering the same run dir continues the window), and (by
    default) resets the process-wide metrics registry so the run starts
    from a clean slate. Replaces any previous configuration (which is
    shut down first). Returns the run id.
    """
    shutdown(reason=None)
    obs_dir = os.path.abspath(str(obs_dir))
    os.makedirs(obs_dir, exist_ok=True)
    from fm_spark_tpu.utils.logging import EventLog

    if reset_metrics:
        registry().reset()
    sink = EventLog(os.path.join(obs_dir, TRACE_FILE))
    flight = FlightRecorder(flight_capacity,
                            spool_path=os.path.join(obs_dir, FLIGHT_FILE))
    tracer = Tracer(sink=sink, flight=flight, enabled=enabled)
    with _lock:
        _state.update(dir=obs_dir, run_id=run_id or new_run_id(),
                      tracer=tracer, flight=flight, sink=sink)
    flight.record("run_start", run_id=_state["run_id"])
    if install_signals:
        install_signal_dump()
    return _state["run_id"]


def shutdown(reason: str | None = "run_end") -> None:
    """Flush and close the telemetry plane (no-op when unconfigured).
    With a ``reason``, writes a final metrics snapshot and flight dump
    first, so a clean run end leaves the same artifacts a fault would."""
    with _lock:
        flight, sink = _state["flight"], _state["sink"]
        d = _state["dir"]
        _state.update(dir=None, run_id=None, tracer=None, flight=None,
                      sink=None)
    # The capture engine is scoped to the run whose directory it writes
    # into: a new run (configure calls shutdown first) re-arms its own.
    introspect.clear()
    if reason is not None:
        # A REAL shutdown (not configure()'s reason=None replace) is a
        # thread-lifecycle boundary (ISSUE 15): the live-metrics
        # endpoint's serve_forever thread must not outlive the run it
        # narrates.
        try:
            from fm_spark_tpu.obs import export as _export

            _export.stop_metrics_server()
        except Exception:
            pass
    if flight is None:
        return
    try:
        if reason is not None:
            flight.record(reason)
            registry().export_jsonl(os.path.join(d, METRICS_FILE))
            flight.dump(reason)
        flight.close()
        if sink is not None:
            sink.close()
    except Exception:
        pass


def enabled() -> bool:
    tr = _state["tracer"]
    return tr is not None and tr.enabled


def run_dir() -> str | None:
    return _state["dir"]


def run_id() -> str | None:
    return _state["run_id"]


# ------------------------------------------------------------------ spans

def span(name: str, **attrs):
    """A span context manager, or the shared no-op when unconfigured."""
    tr = _state["tracer"]
    if tr is None:
        return NOOP_SPAN
    return tr.span(name, **attrs)


def emit_span(name: str, t_start: float, dur_s: float, **attrs) -> None:
    """Retroactive span record for a caller-timed interval (see
    :meth:`Tracer.emit_span`); no-op when unconfigured."""
    tr = _state["tracer"]
    if tr is not None:
        tr.emit_span(name, t_start, dur_s, **attrs)


def interval(name: str, **ids) -> Interval:
    """A hot interval (context manager): ALWAYS recorded into the
    in-memory ring, also a ``jax.profiler.TraceAnnotation`` while a
    profiler session is on, also a ``trace.jsonl`` record when a run
    directory is configured (:class:`fm_spark_tpu.obs.trace.Interval`).
    Only for the few sites inside the loops every run executes;
    everything else keeps :func:`span` and its free no-op."""
    return Interval(name, ids, _state["tracer"])


def record_interval(name: str, t0: float, t1: float,
                    parent_id: str | None = None, **ids) -> Interval:
    """A hot interval the caller timed itself on
    ``time.perf_counter()``: ring and ``trace.jsonl``, no profiler
    annotation."""
    return _trace_mod.record_interval(name, t0, t1, ids, _state["tracer"],
                                      parent_id)


def intervals() -> list[Interval]:
    """Snapshot of the ring of finished hot intervals, oldest first
    (the last :data:`RING_CAPACITY`; ``registry().reset()`` and
    :func:`configure` leave it alone)."""
    return _trace_mod.intervals()


def mint_trace(sample: float = 1.0) -> TraceContext | None:
    """Mint a per-request :class:`TraceContext` (the distributed-trace
    front door hook, ISSUE 18), or None when tracing is off or the
    request is sampled out. Disabled-path contract: one tracer check —
    an unconfigured process never pays the urandom/random cost (held to
    the ≤1% bound in tests/test_obs_overhead.py)."""
    tr = _state["tracer"]
    if tr is None or not tr.enabled:
        return None
    return _trace_mod.mint_trace(sample)


def traced(name: str | None = None):
    """Decorator form of :func:`span`; binds the tracer at CALL time so
    decoration at import (before :func:`configure`) still traces."""

    def deco(fn):
        label = name or fn.__qualname__

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            tr = _state["tracer"]
            if tr is None or not tr.enabled:
                return fn(*args, **kwargs)
            with tr.span(label):
                return fn(*args, **kwargs)

        return wrapper

    return deco


# ----------------------------------------------------------------- events

def event(kind: str, **fields) -> None:
    """Record one event into the flight ring (no-op when unconfigured;
    best-effort by the telemetry contract)."""
    flight = _state["flight"]
    if flight is None:
        return
    try:
        fields.pop("seq", None)
        fields.pop("kind", None)
        flight.record(kind, **fields)
    except Exception:
        pass


def flight_dump(reason: str, path: str | None = None,
                **extra) -> str | None:
    """Atomically dump the last-N window now (fault endings call this).
    ``path`` overrides the default ``flight_dump.json`` target — the
    introspection capture bundles (ISSUE 14) dump INTO the bundle so a
    later dump on the default path can never overwrite a capture's
    flight context."""
    flight = _state["flight"]
    if flight is None:
        return None
    return flight.dump(reason, path=path, extra=extra or None)


def fault_timeline(limit: int = 50) -> list[dict]:
    """The flight ring filtered to fault/retry/breaker events, oldest
    first, capped to the most recent ``limit``."""
    flight = _state["flight"]
    if flight is None:
        return []
    out = [e for e in flight.events() if e.get("kind") in FAULT_KINDS]
    return out[-max(int(limit), 0):]


# ---------------------------------------------------------------- metrics

def counter(name: str):
    return registry().counter(name)


def gauge(name: str):
    return registry().gauge(name)


def histogram(name: str, buckets=None):
    return registry().histogram(name, buckets=buckets)


def export_snapshot() -> dict | None:
    """Append one registry snapshot to the run dir's ``metrics.jsonl``
    (no-op without a run dir)."""
    d = _state["dir"]
    if d is None:
        return None
    return registry().export_jsonl(os.path.join(d, METRICS_FILE))


def device_memory_snapshot(devices=None) -> dict | None:
    """Device-memory watermarks into the registry (ISSUE 9): per-device
    ``memory_stats()`` totals (``bytes_in_use`` and the PJRT
    ``peak_bytes_in_use`` high-water mark — the HBM peak the ledger
    records next to every leg's rate) plus the host-visible live-buffer
    total from ``jax.live_arrays()``. Best-effort and lazy: jax is
    only *looked up*, never imported — an unconfigured process, or a
    CPU backend without memory stats, just reports what exists.
    Returns the snapshot dict (``None`` when jax is not even loaded).
    """
    import sys

    jax = sys.modules.get("jax")
    if jax is None:
        return None
    reg = registry()
    out = {"live_buffer_bytes": None, "bytes_in_use": None,
           "peak_bytes_in_use": None}
    try:
        live = sum(int(getattr(a, "nbytes", 0))
                   for a in jax.live_arrays())
        out["live_buffer_bytes"] = live
        reg.gauge("device.live_buffer_bytes").set(live)
    except Exception:
        pass
    try:
        in_use = peak = 0
        found = False
        for d in devices if devices is not None else jax.local_devices():
            stats = getattr(d, "memory_stats", None)
            stats = stats() if callable(stats) else None
            if not stats:
                continue
            found = True
            in_use += int(stats.get("bytes_in_use", 0))
            peak += int(stats.get("peak_bytes_in_use",
                                  stats.get("bytes_in_use", 0)))
        if found:
            out["bytes_in_use"] = in_use
            out["peak_bytes_in_use"] = peak
            reg.gauge("device.bytes_in_use").set(in_use)
            reg.gauge("device.peak_bytes_in_use").set(peak)
    except Exception:
        pass
    return out


def telemetry_block() -> dict:
    """The run's headline telemetry as one JSON-ready block — what
    ``bench.py`` stamps into its result JSON: step-time percentiles
    (the ``step_time_ms`` histogram), ingest rate/accounting, and the
    fault-event timeline."""
    reg = registry()
    step = reg.histogram("step_time_ms").summary()
    rate = reg.gauge("ingest.rows_per_sec").value
    block = {
        "run_id": _state["run_id"],
        "obs_dir": _state["dir"],
        "step_time_ms": {k: step[k] for k in
                         ("count", "mean", "p50", "p95", "p99")},
        "ingest_rows_per_sec": rate,
        "ingest_rows_total": reg.counter("ingest.rows_ok_total").value,
        "ingest_quarantined_total":
            reg.counter("ingest.rows_quarantined_total").value,
        "device_memory": {
            "live_buffer_bytes": reg.gauge(
                "device.live_buffer_bytes").value,
            "bytes_in_use": reg.gauge("device.bytes_in_use").value,
            "peak_bytes_in_use": reg.gauge(
                "device.peak_bytes_in_use").value,
        },
        "fault_events": [
            {k: v for k, v in e.items() if k != "seq"}
            for e in fault_timeline()
        ],
    }
    return block


# ---------------------------------------------------------------- signals

def _signal_handler(signum, frame):
    flight_dump(f"signal:{signum}")
    export_snapshot()
    prev = _prev_handlers.get(signum)
    if callable(prev):
        prev(signum, frame)
    elif prev != _signal.SIG_IGN:
        # SIG_DFL — or None, a handler installed from C that we
        # displaced and cannot re-invoke: restore the default action
        # and re-raise so the signal still terminates the process.
        # Swallowing it would turn SIGTERM into a no-op and leave the
        # orchestrator to escalate to SIGKILL — the uncatchable ending
        # this recorder exists to avoid.
        _signal.signal(signum, _signal.SIG_DFL)
        os.kill(os.getpid(), signum)


def install_signal_dump(signals=(_signal.SIGTERM,)) -> bool:
    """Chain a dump-then-delegate handler onto ``signals`` so a SIGTERM
    leaves the last-N window on disk before whatever handler (or the
    default death) runs. Main-thread only (signal API restriction);
    returns whether installation happened."""
    if threading.current_thread() is not threading.main_thread():
        return False
    for sig in signals:
        prev = _signal.getsignal(sig)
        if prev is _signal_handler:
            continue
        _prev_handlers[sig] = prev
        _signal.signal(sig, _signal_handler)
    return True
