"""Lightweight span tracing: context-manager + decorator API, JSONL out.

The telemetry plane's time axis (ISSUE 7). A span is a named interval
with a monotonic-clock duration, a process-unique id, and the id of the
span it nests inside (per-thread parent stack), emitted as one JSONL
record through the existing :class:`fm_spark_tpu.utils.logging.EventLog`
sink (``event: "span"``) and mirrored into the flight-recorder ring so
the last-N window survives a crash.

Hot-path contract: the DISABLED path must be nearly free — ``≤1%``
step-time regression on a 200-step synthetic train loop, asserted by
``tests/test_obs_overhead.py``. :meth:`Tracer.span` on a disabled
tracer returns a shared no-op singleton (no allocation, trivial
``__enter__``/``__exit__``), and the instrumented loops additionally
latch ``obs.enabled()`` once so per-step work is a single attribute
check.

Usage::

    with obs.span("train/eval", step=120) as sp:
        metrics = evaluate(...)
        sp.set(auc=metrics["auc"])

    @obs.traced("ingest/chunk_parse")
    def parse_chunk(...): ...

Hot intervals (ISSUE 24) are the always-live twin: a few named call
sites inside the loops every run executes (``train/*``, ``feed/*``,
``serve/*``), the phases before those loops (``setup/*``, ISSUE 38) and
every compilation jax reports (``compile/*``, recorded by
``utils/compile_cache``'s listeners) record through :class:`Interval`
into one bounded in-memory ring whether or not a run directory is
configured — to spans what the registry is to metrics. One call feeds three sinks: the ring
(:func:`intervals`), the profiler's trace (a
``jax.profiler.TraceAnnotation`` while a profiler session is on) and,
when configured, the :class:`Tracer` (``trace.jsonl``, flight ring).
"""

from __future__ import annotations

import collections
import functools
import itertools
import os
import random
import re
import sys
import threading
import time

__all__ = ["Interval", "NOOP_SPAN", "RING_CAPACITY", "Span",
           "TraceContext", "TRACE_HEADER", "Tracer", "intervals",
           "mint_trace", "record_interval"]

_SEQ = itertools.count(1)
_TLS = threading.local()

#: The cross-process propagation header (ISSUE 18): every HTTP hop
#: inside the serving fleet carries ``X-FM-Trace: <trace_id>;<parent
#: span_id>`` so spans minted in different processes stitch into one
#: request timeline. fmlint's ``trace-propagation`` rule holds
#: ``fm_spark_tpu/serve/`` to it.
TRACE_HEADER = "X-FM-Trace"

_TOKEN_RE = re.compile(r"[A-Za-z0-9][A-Za-z0-9_\-]{0,63}$")


class TraceContext:
    """Cross-process trace identity: the request's ``trace_id`` plus the
    span_id of the hop that handed it over (the remote parent).

    Stdlib-only and deliberately tiny — two string slots and a header
    codec. A context is minted ONCE per accepted request at the front
    door (:func:`mint_trace`) and re-derived at every hop via
    :meth:`child`, so each process's spans carry the same ``trace``
    attribute and a ``remote_parent`` link into the upstream process.
    """

    __slots__ = ("trace_id", "parent_span_id")

    def __init__(self, trace_id: str, parent_span_id: str | None = None):
        self.trace_id = str(trace_id)
        self.parent_span_id = parent_span_id

    def child(self, span_id: str | None) -> "TraceContext":
        """The context to hand DOWNSTREAM from a hop whose span is
        ``span_id`` (None — e.g. tracing disabled locally — keeps the
        current parent so the chain degrades, never breaks)."""
        if span_id is None:
            return self
        return TraceContext(self.trace_id, str(span_id))

    def to_header(self) -> str:
        return f"{self.trace_id};{self.parent_span_id or ''}"

    @classmethod
    def from_header(cls, value) -> "TraceContext | None":
        """Parse an ``X-FM-Trace`` header value; junk (None, empty,
        malformed, oversized tokens) returns None — an untrusted peer
        must never crash the replica's request path."""
        if not value or not isinstance(value, str):
            return None
        trace_id, _, parent = value.partition(";")
        trace_id = trace_id.strip()
        parent = parent.strip()
        if not _TOKEN_RE.match(trace_id):
            return None
        if parent and not _TOKEN_RE.match(parent):
            parent = ""
        return cls(trace_id, parent or None)

    def __repr__(self):
        return (f"TraceContext({self.trace_id!r}, "
                f"{self.parent_span_id!r})")


def mint_trace(sample: float = 1.0) -> TraceContext | None:
    """Mint a fresh request trace, or None when sampled out.

    ``sample`` is the kept fraction (the ``--trace-sample`` knob):
    1.0 traces every request (the test default), 0.0 none. The id is
    ``os.urandom`` hex — unique across the fleet's processes without
    any coordination.
    """
    if sample < 1.0 and random.random() >= sample:
        return None
    return TraceContext(os.urandom(8).hex())


def _stack() -> list:
    st = getattr(_TLS, "stack", None)
    if st is None:
        st = _TLS.stack = []
    return st


def _unstack(st: list, span) -> None:
    if st and st[-1] is span:
        st.pop()
    else:
        # Mis-nested manual open/close: drop this span wherever it
        # sits rather than corrupting the siblings' parentage.
        try:
            st.remove(span)
        except ValueError:
            pass


class _NoopSpan:
    """Shared do-nothing span: the disabled fast path (no allocation)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self


NOOP_SPAN = _NoopSpan()


class Span:
    """One named interval. Use as a context manager; ``set()`` attaches
    attributes any time before exit (they ride the emitted record)."""

    __slots__ = ("tracer", "name", "attrs", "span_id", "parent_id",
                 "ts", "_t0", "dur_s")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict):
        self.tracer = tracer
        self.name = name
        self.attrs = attrs
        self.span_id = None
        self.parent_id = None
        self.ts = 0.0
        self._t0 = 0.0
        self.dur_s = 0.0

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    def __enter__(self) -> "Span":
        st = _stack()
        self.parent_id = st[-1].span_id if st else None
        self.span_id = f"{os.getpid():x}-{next(_SEQ):x}"
        self.ts = time.time()
        st.append(self)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.dur_s = time.perf_counter() - self._t0
        _unstack(_stack(), self)
        if exc_type is not None:
            self.attrs.setdefault("error", exc_type.__name__)
        self.tracer._finish(self)
        return False


class Tracer:
    """Span factory bound to a JSONL sink + flight-recorder ring.

    ``sink`` is anything with ``emit(event, **fields)`` (an
    :class:`~fm_spark_tpu.utils.logging.EventLog`); ``flight`` anything
    with ``record(kind, **fields)``. Both optional and best-effort —
    tracing must never take down the operation it narrates.
    """

    def __init__(self, sink=None, flight=None, enabled: bool = True):
        self.sink = sink
        self.flight = flight
        self.enabled = bool(enabled)

    def span(self, name: str, **attrs):
        if not self.enabled:
            return NOOP_SPAN
        return Span(self, name, attrs)

    def traced(self, name: str | None = None):
        """Decorator form; the label defaults to the qualname."""

        def deco(fn):
            label = name or fn.__qualname__

            @functools.wraps(fn)
            def wrapper(*args, **kwargs):
                if not self.enabled:
                    return fn(*args, **kwargs)
                with Span(self, label, {}):
                    return fn(*args, **kwargs)

            return wrapper

        return deco

    def emit_span(self, name: str, t_start: float, dur_s: float,
                  **attrs) -> None:
        """Emit a RETROACTIVE span record for an interval timed by the
        caller (``t_start`` wall-clock, ``dur_s`` monotonic duration).
        For windows that outlive any single ``with`` block — e.g. the
        trainer's log windows, where holding an open span across loop
        iterations would leak it onto the parent stack on an exception
        mid-window. Parented to the current innermost open span."""
        if not self.enabled:
            return
        sp = Span(self, name, attrs)
        st = _stack()
        sp.parent_id = st[-1].span_id if st else None
        sp.span_id = f"{os.getpid():x}-{next(_SEQ):x}"
        sp.ts = float(t_start)
        sp.dur_s = float(dur_s)
        self._finish(sp)

    def _finish(self, span: "Span | Interval") -> None:
        fields = {
            "name": span.name,
            "span_id": span.span_id,
            "parent_id": span.parent_id,
            "t_start": round(span.ts, 6),
            "dur_ms": round(span.dur_s * 1e3, 3),
            "thread": threading.get_ident(),
        }
        for k, v in span.attrs.items():
            fields.setdefault(k, v)
        try:
            if self.sink is not None:
                self.sink.emit("span", **fields)
            if self.flight is not None:
                self.flight.record("span", **fields)
        except Exception:
            pass


# ----------------------------------------------------------- hot intervals

#: Finished intervals the ring keeps (the oldest fall off): ~6 MB at
#: worst; a 20 s scoring window makes ~8,000, 360 FFM steps ~3,000.
RING_CAPACITY = 65536

_RING: collections.deque = collections.deque(maxlen=RING_CAPACITY)
#: ``time.perf_counter()`` to wall clock, fixed at import: a hot interval
#: reads one clock, and its ``t_start`` in ``trace.jsonl`` is derived.
_WALL_OFFSET = time.time() - time.perf_counter()  # fmlint: disable=wallclock-duration -- the offset between the two clocks, not a duration: it turns a perf_counter stamp into the wall-clock timestamp trace.jsonl carries
_annotation = None      # jax.profiler.TraceAnnotation, once jax is loaded


def _find_annotation():
    """``jax.profiler.TraceAnnotation`` if jax is ALREADY imported (obs
    never imports it), else None."""
    global _annotation
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)
    _annotation = getattr(profiler, "TraceAnnotation", None)
    return _annotation


class Interval:
    """One finished (or open) hot interval, and the context manager that
    times it: ``name``, ``t0`` / ``t1`` (``time.perf_counter()``
    seconds), the recording ``thread``, its process-unique ``span_id``
    (a :class:`Span`'s format, made when first read: most are never
    read), the ``parent_id`` that caused it (the thread's innermost open span
    or interval; for a request, its batch) and ``attrs``, the few
    integers that identify the work (``step``, ``rows``, ``bucket``,
    ``requests``); ``profiled`` says a profiler session was on at entry.

    Unlike :class:`Span` it is live without ``obs.configure()``: on exit
    — by an exception too, ``BaseException`` included — the object
    itself is appended to the ring. Inside a profiler session it is also
    a ``TraceAnnotation`` under the same name (outside one that is a
    flag test in C++), and a configured :class:`Tracer` gets the same
    record.
    """

    __slots__ = ("name", "attrs", "tracer", "t0", "t1", "thread",
                 "parent_id", "profiled", "_id", "_ann", "_st")

    def __init__(self, name: str, attrs: dict, tracer: "Tracer | None"):
        self.name = name
        self.attrs = attrs
        self.tracer = tracer
        self.t0 = self.t1 = 0.0
        self.thread = threading.get_ident()
        self.parent_id = None
        #: A profiler session was on when this interval was entered (it
        #: is in the xplane too). A reader can tell from it in which
        #: step a session started or stopped: that step paid for it.
        self.profiled = False
        self._id = (os.getpid(), next(_SEQ))
        self._ann = None
        self._st = None

    @property
    def span_id(self) -> str:
        sid = self._id
        if type(sid) is tuple:
            sid = self._id = f"{sid[0]:x}-{sid[1]:x}"
        return sid

    @property
    def ts(self) -> float:
        """Wall-clock start (what ``trace.jsonl`` calls ``t_start``)."""
        return self.t0 + _WALL_OFFSET

    @property
    def dur_s(self) -> float:
        return self.t1 - self.t0

    def set(self, **attrs) -> "Interval":
        self.attrs.update(attrs)
        return self

    def __enter__(self) -> "Interval":
        st = self._st = _stack()
        self.parent_id = st[-1].span_id if st else None
        st.append(self)
        ann = _annotation or _find_annotation()
        if ann is not None and ann.is_enabled():
            self._ann = ann(self.name, **self.attrs)
            self._ann.__enter__()
            self.profiled = True
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None
        _unstack(self._st, self)
        self._st = None
        if exc_type is not None:
            self.attrs.setdefault("error", exc_type.__name__)
        self._finish()
        return False

    def _finish(self) -> None:
        _RING.append(self)
        # The ring outlives the run directory: a kept record must not
        # keep a closed tracer (its sink, its flight spool) alive.
        tracer, self.tracer = self.tracer, None
        if tracer is not None and tracer.enabled:
            tracer._finish(self)


def record_interval(name: str, t0: float, t1: float, attrs: dict,
                    tracer: "Tracer | None" = None,
                    parent_id: str | None = None) -> Interval:
    """Record an interval the CALLER timed (``t0`` / ``t1`` on
    ``time.perf_counter()``) into the ring and the tracer. No profiler
    annotation: the profiler takes none after the fact. ``parent_id``
    defaults to the thread's innermost open span."""
    iv = Interval(name, attrs, tracer)
    iv.t0, iv.t1 = float(t0), float(t1)
    if parent_id is None:
        st = _stack()
        parent_id = st[-1].span_id if st else None
    iv.parent_id = parent_id
    iv._finish()
    return iv


def intervals() -> list:
    """Snapshot of the ring, oldest first, without stopping writers
    (``deque.append`` is atomic; copying one that a writer touches
    mid-copy raises ``RuntimeError`` and is simply tried again)."""
    while True:
        try:
            return list(_RING)
        except RuntimeError:
            continue
