"""AOT micro-batched predict engine: the low-latency request path.

The training side runs at 0.76M samples/s/chip on the v5e (config 3,
one chip: PERF.md §5), but until ISSUE 12 the repo could only
``predict`` in offline batch mode. This engine is the
millions-of-users half: a warm process answers scoring requests with
**zero fresh XLA compiles on the request path**, because every
executable it will ever dispatch is AOT ``lower().compile()``-d at
:meth:`PredictEngine.warmup` — one per padded **batch bucket** —
through the PR-1 persistent compile cache (a warm process deserializes
each in milliseconds instead of compiling).

Shape discipline is the whole trick: a request of ``n`` rows is padded
to the smallest configured bucket ``>= n``, so the engine only ever
dispatches shapes it compiled at warmup — never a fresh shape, never a
fresh compile, bounded executable count. Padding is provably free for
correctness: per-row scores are row-independent (verified bitwise in
tests — padded and unpadded executions agree exactly), and padded rows
are sliced off before any caller sees them.

Request path (the **coalescer / micro-batcher**): callers
:meth:`~PredictEngine.submit` requests of 1..bucket-max rows; a worker
thread takes the first queued request and accumulates more until the
explicit **latency budget** expires or the largest bucket fills, then
executes ONE padded batch and splits results back per request — every
request answered exactly once, each from exactly ONE model generation
(the worker reads the generation reference once per batch; see
:mod:`fm_spark_tpu.serve.reload` for the swap side of that contract).
The batch execute runs under the ``serve_request`` watchdog phase
(deadline = the SLO): an overrun becomes a structured
:class:`~fm_spark_tpu.resilience.watchdog.HangDetected` + flight dump
instead of a silently blown tail latency.

Offline batch predict (``cli predict``) rides :meth:`PredictEngine.
score` — the same bucketed AOT executables without the coalescer
thread — and is bit-identical to the pre-engine eager path.
"""

from __future__ import annotations

import queue
import threading
import time

import numpy as np

from fm_spark_tpu import obs
from fm_spark_tpu.resilience import watchdog

__all__ = [
    "DEFAULT_BUCKETS",
    "Generation",
    "PredictEngine",
    "ServeFuture",
]

#: Default padded-batch buckets: batch-1 for pure-latency traffic up
#: through 512 rows per dispatch (one executable each; ~4x steps keep
#: the worst-case pad waste under 4x and the executable count small).
DEFAULT_BUCKETS = (1, 8, 64, 512)


class Generation:
    """One immutable served model generation. The engine holds exactly
    one reference; a swap replaces the reference, never the contents —
    the single-assignment atomicity the no-torn-swap invariant rides.

    ``params`` is the tree the bucket executables take: the model's
    parameters on the device in the form a holder that only reads takes
    (:mod:`fm_spark_tpu.models.rows`: narrow tables packed or
    lane-padded where the device would otherwise copy them on every
    dispatch). ``shapes`` is the canonical tree's
    ``jax.ShapeDtypeStruct``s — what a checkpoint of this model
    restores into. No canonical copy is kept; ``rows.canonical(params,
    shapes)`` is the way back. ``held`` counts what was installed
    (tables packed, padded, as they were; resident bytes)."""

    __slots__ = ("params", "shapes", "held", "step", "gen_id")

    def __init__(self, params, shapes, held, step: int, gen_id: int):
        self.params = params
        self.shapes = shapes
        self.held = held
        self.step = int(step)
        self.gen_id = int(gen_id)


class ServeFuture:
    """Exactly-once result slot for one submitted request."""

    __slots__ = ("_event", "_value", "_exc")

    def __init__(self):
        self._event = threading.Event()
        self._value = None
        self._exc = None

    def _set(self, value) -> None:
        self._value = value
        self._event.set()

    def _set_exception(self, exc: BaseException) -> None:
        self._exc = exc
        self._event.set()

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: float | None = None) -> np.ndarray:
        if not self._event.wait(timeout):
            raise TimeoutError("serve request not answered in time")
        if self._exc is not None:
            raise self._exc
        return self._value


class _Request:
    __slots__ = ("ids", "vals", "n", "future", "t_submit", "t_wall",
                 "deadline", "trace")

    def __init__(self, ids, vals, deadline=None, trace=None):
        self.ids = ids
        self.vals = vals
        self.n = int(ids.shape[0])
        self.future = ServeFuture()
        self.t_submit = time.perf_counter()
        #: Wall-clock twin of ``t_submit`` — the start stamp of the
        #: request's retroactive ``serve/coalesce`` link span (stored,
        #: never subtracted; durations stay monotonic).
        self.t_wall = time.time()
        #: Absolute ``time.monotonic()`` deadline (None = unbounded).
        #: Propagated by the front door (ISSUE 17) so the coalescer
        #: never HOLDS a request past its SLO waiting for batch-mates,
        #: and never SCORES one that already expired in the queue.
        self.deadline = deadline
        #: Distributed-trace context (ISSUE 18) or None: tags the
        #: request's link span + latency exemplar, and rides into the
        #: SLO-overrun capture context.
        self.trace = trace


_STOP = object()


class PredictEngine:
    """Bucketed AOT scoring over an atomically swappable generation.

    ``nnz`` pins the per-row feature width (the second input axis);
    every request must match it — a stray width would be a fresh shape,
    i.e. a compile on the request path, so it is rejected loudly
    instead. Call :meth:`warmup` once before serving (compiles — or,
    warm, deserializes — every bucket executable); then :meth:`submit`
    / :meth:`predict` for coalesced serving or :meth:`score` for
    direct offline batches.
    """

    def __init__(self, spec, params, *, nnz: int | None = None,
                 step: int = 0, buckets=DEFAULT_BUCKETS,
                 latency_budget_ms: float = 2.0, journal=None,
                 ids_dtype="int32", vals_dtype="float32"):
        import jax

        # Where this engine's set-up began: the first warmup() closes
        # the ``setup/run`` interval that starts here.
        self._t_built: float | None = time.perf_counter()
        self.spec = spec
        self.buckets = tuple(sorted({int(b) for b in buckets}))
        if not self.buckets or self.buckets[0] < 1:
            raise ValueError(f"need >= 1 positive bucket, got {buckets}")
        self.nnz = int(nnz if nnz is not None
                       else getattr(spec, "num_fields", 0))
        if self.nnz < 1:
            raise ValueError(
                "engine needs the per-row feature width: pass nnz= "
                "(specs without num_fields cannot imply it)")
        self.latency_budget_s = max(float(latency_budget_ms), 0.0) / 1e3
        self.journal = journal
        self._ids_dtype = np.dtype(ids_dtype)
        self._vals_dtype = np.dtype(vals_dtype)
        self._jax = jax
        self._predict = jax.jit(
            lambda p, i, v: self.spec.predict(p, i, v))
        self._compiled: dict[int, object] = {}
        self._install(params, step, gen_id=0)
        self._queue: queue.Queue = queue.Queue()
        self._carry: _Request | None = None
        self._worker: threading.Thread | None = None
        self._worker_lock = threading.Lock()
        self._closed = False
        self._last_slo_dump: float | None = None

    # -------------------------------------------------------- generations

    def generation(self) -> Generation:
        """The CURRENT generation reference (one atomic read — the
        same read the batch worker performs per micro-batch)."""
        return self._gen

    def _install(self, params, step: int, gen_id: int) -> Generation:
        """Put canonical ``params`` on the device in serving form
        (``rows.hold``; the caller's arrays are not consumed),
        off the request path, then make them THE generation by a single
        reference store — the one way a generation comes to be, at
        construction and at every swap. The gauges say what this engine
        serves now: the live /healthz endpoint (ISSUE 14) reads the
        step, and a fresh engine that never swaps must still report it."""
        from fm_spark_tpu.models import rows

        with obs.interval("setup/install", gen_id=gen_id) as phase:
            gen = Generation(
                *rows.hold(params, self.spec.row_tables, writes=False),
                step, gen_id)
            phase.set(tables=sum(gen.held[f"tables_{form}"]
                                 for form in rows.FORMS),
                      resident_table_bytes=gen.held["resident_table_bytes"])
        self._gen = gen  # fmlint: disable=thread-lock-discipline -- THE swap: one atomic reference store; worker reads the reference once per batch (no-torn-swap contract, chaos-audited)
        obs.gauge("serve/generation_step").set(gen.step)
        for name, value in gen.held.items():
            obs.gauge(f"serve/{name}").set(value)
        return gen

    def swap_generation(self, params, step: int) -> Generation:
        """Install a new generation via a single reference assignment.

        The caller (the reload follower) does all loading/verification
        OFF the request path first, and :meth:`_install` has the new
        params fully materialized in serving form before its store, so
        a concurrent batch sees either the old reference or the new one
        — never a mixture (the no-torn-swap contract, audited in chaos
        drills). Requests already batched against the old generation
        finish on it."""
        old = self._gen
        gen = self._install(params, step, gen_id=old.gen_id + 1)
        obs.counter("serve.swaps_total").add(1)
        obs.event("serve_swap", step=gen.step, gen_id=gen.gen_id,
                  from_step=old.step, **gen.held)
        if self.journal is not None:
            self.journal.emit("serve_swap", step=gen.step,
                              gen_id=gen.gen_id, from_step=old.step)
        return gen

    # ------------------------------------------------------------ compile

    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(
            f"request of {n} rows exceeds the largest bucket "
            f"{self.buckets[-1]} (predict() chunks; submit() callers "
            "must pre-chunk)")

    def warmup(self) -> dict:
        """AOT-compile every (bucket, nnz) executable NOW — the only
        place the engine ever compiles. With a populated persistent
        compile cache this is pure deserialization (asserted via
        :func:`fm_spark_tpu.utils.compile_cache.cache_stats` in tests
        and bench_serve). Returns ``{"seconds", "buckets",
        "cache_stats"}``. The bucket loop is the hot interval
        ``setup/warmup``; the first call also closes ``setup/run``
        (``entry="serve"``), which began when the engine was built."""
        from fm_spark_tpu.utils import compile_cache

        jax = self._jax
        t0 = time.perf_counter()
        stats0 = compile_cache.cache_stats()
        gen = self._gen
        with obs.interval("setup/warmup", buckets=len(self.buckets),
                          nnz=self.nnz):
            for b in self.buckets:
                if b in self._compiled:
                    continue
                lowered = self._predict.lower(
                    gen.params,
                    jax.ShapeDtypeStruct((b, self.nnz),
                                         self._ids_dtype),
                    jax.ShapeDtypeStruct((b, self.nnz),
                                         self._vals_dtype),
                )
                self._compiled[b] = lowered.compile()  # fmlint: disable=thread-lock-discipline -- warmup() runs before serving starts; bucket entries are add-only and never mutated after
        if self._t_built is not None:
            obs.record_interval("setup/run", self._t_built,
                                time.perf_counter(), entry="serve",
                                model=type(self.spec).__name__)
            self._t_built = None
        stats1 = compile_cache.cache_stats()
        out = {
            "seconds": round(time.perf_counter() - t0, 4),
            "buckets": list(self.buckets),
            "cache_stats": stats1,
            "fresh_compiles": stats1["misses"] - stats0["misses"],
        }
        obs.event("serve_warmup", **{k: out[k] for k in
                                     ("seconds", "fresh_compiles")},
                  **gen.held)
        return out

    # ------------------------------------------------------------ execute

    def _coerce(self, ids, vals) -> tuple[np.ndarray, np.ndarray]:
        ids = np.asarray(ids)
        vals = np.asarray(vals)
        if ids.ndim != 2 or ids.shape != vals.shape:
            raise ValueError(
                f"want matching (n, {self.nnz}) ids/vals, got "
                f"{ids.shape} / {vals.shape}")
        if ids.shape[1] != self.nnz:
            raise ValueError(
                f"request width {ids.shape[1]} != engine nnz "
                f"{self.nnz} — a fresh shape would mean a fresh "
                "compile on the request path; build the engine with "
                "the request width")
        if ids.shape[0] < 1:
            raise ValueError("empty request")
        return (ids.astype(self._ids_dtype, copy=False),
                vals.astype(self._vals_dtype, copy=False))

    def _execute(self, gen: Generation, ids: np.ndarray,
                 vals: np.ndarray, requests: int = 1):
        """One padded-bucket dispatch on ``gen``; returns ``(scores,
        batch)``: the first ``n`` scores as host floats and the
        finished ``serve/batch`` hot interval (pad, dispatch, device,
        the copy back), whose id and bounds let the coalescer
        decompose each request into queue / execute / split. The ONLY
        dispatch path — spans, SLO watchdog, and the zero-compile
        property all live here."""
        n = ids.shape[0]
        bucket = self._bucket_for(n)
        compiled = self._compiled.get(bucket)
        if compiled is None:
            raise RuntimeError(
                f"bucket {bucket} not compiled — call warmup() before "
                "serving (the request path never compiles)")
        pad = bucket - n
        with obs.interval("serve/batch", rows=n, bucket=bucket, pad=pad,
                          requests=requests, gen_step=gen.step) as batch:
            if pad:
                ids = np.concatenate(
                    [ids, np.zeros((pad, self.nnz), self._ids_dtype)])
                vals = np.concatenate(
                    [vals, np.zeros((pad, self.nnz), self._vals_dtype)])
            t0 = time.perf_counter()
            with watchdog.phase("serve_request"):
                out = np.asarray(compiled(gen.params, ids, vals))
            t1 = time.perf_counter()
        obs.histogram("serve/batch_ms").observe((t1 - t0) * 1e3)
        obs.counter("serve.batches_total").add(1)
        obs.counter("serve.rows_total").add(n)
        if pad:
            obs.counter("serve.padded_rows_total").add(pad)
        return out[:n], batch

    def score(self, ids, vals) -> np.ndarray:
        """Direct (non-coalesced) bucketed scoring — the offline batch
        path ``cli predict`` and warm ladders use. Chunks inputs wider
        than the largest bucket; output order matches input order."""
        ids, vals = self._coerce(ids, vals)
        gen = self._gen
        cap = self.buckets[-1]
        if ids.shape[0] <= cap:
            return self._execute(gen, ids, vals)[0]
        return np.concatenate([
            self._execute(gen, ids[lo:lo + cap], vals[lo:lo + cap])[0]
            for lo in range(0, ids.shape[0], cap)
        ])

    # ---------------------------------------------------------- coalescer

    def _ensure_worker(self) -> None:
        with self._worker_lock:
            if self._closed:
                raise RuntimeError("engine is closed")
            if self._worker is None or not self._worker.is_alive():
                self._worker = threading.Thread(
                    target=self._run, name="fm-spark-serve-batcher",
                    daemon=True)
                self._worker.start()

    def submit(self, ids, vals, deadline: float | None = None,
               trace=None) -> ServeFuture:
        """Enqueue one request (<= bucket-max rows) for coalescing;
        returns its :class:`ServeFuture`. ``deadline`` is an absolute
        ``time.monotonic()`` timestamp: the coalescer stops gathering
        at the batch's earliest deadline, and a request that expires
        while still queued is answered with :class:`TimeoutError`
        (exactly once, never scored, never silently dropped).
        ``trace`` (a :class:`~fm_spark_tpu.obs.trace.TraceContext`)
        yields one ``serve/coalesce`` link span joining this request
        to the shared micro-batch execute span."""
        ids, vals = self._coerce(ids, vals)
        if ids.shape[0] > self.buckets[-1]:
            raise ValueError(
                f"submit() takes at most bucket-max ({self.buckets[-1]}) "
                "rows per request; use predict() to auto-chunk")
        self._ensure_worker()
        req = _Request(ids, vals, deadline=deadline, trace=trace)
        obs.counter("serve.requests_total").add(1)
        self._queue.put(req)
        return req.future

    def predict(self, ids, vals, timeout: float | None = 60.0
                ) -> np.ndarray:
        """Submit-and-wait; wide inputs are chunked to bucket-max and
        reassembled in order."""
        ids, vals = self._coerce(ids, vals)
        cap = self.buckets[-1]
        futures = [self.submit(ids[lo:lo + cap], vals[lo:lo + cap])
                   for lo in range(0, ids.shape[0], cap)]
        return np.concatenate([f.result(timeout) for f in futures])

    def _gather(self) -> list[_Request] | None:
        """Block for the first request, then accumulate under the
        latency budget / until bucket-max; ``None`` = stop. The
        ``serve/gather`` hot interval spans the whole call; ``idle_s``
        is the part spent blocked before the first request arrived."""
        with obs.interval("serve/gather") as gathered:
            first = self._carry
            self._carry = None  # fmlint: disable=thread-lock-discipline -- coalescer-thread-local carry: only the single worker thread (_run/_gather) ever touches it
            if first is None:
                first = self._queue.get()
            gathered.set(idle_s=time.perf_counter() - gathered.t0)
            if first is _STOP:
                return None
            batch = [first]
            rows = first.n
            cap = self.buckets[-1]
            deadline = time.monotonic() + self.latency_budget_s
            if first.deadline is not None:
                deadline = min(deadline, first.deadline)
            while rows < cap:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                try:
                    nxt = self._queue.get(timeout=remaining)
                except queue.Empty:
                    break
                if nxt is _STOP:
                    # Finish this batch, then stop: queued requests are
                    # answered, never dropped.
                    self._queue.put(_STOP)
                    break
                if rows + nxt.n > cap:
                    self._carry = nxt  # fmlint: disable=thread-lock-discipline -- heads the next batch; coalescer-thread-local (single worker thread)
                    break
                batch.append(nxt)
                rows += nxt.n
                if nxt.deadline is not None:
                    deadline = min(deadline, nxt.deadline)
            gathered.set(rows=rows, requests=len(batch))
            return batch

    def _run(self) -> None:
        while True:
            batch = self._gather()
            if batch is None:
                return
            # A request whose deadline passed while it sat in the
            # queue is answered with TimeoutError NOW — scoring it
            # would spend batch capacity on an answer the client has
            # already abandoned (the front door's admission estimate
            # stays honest because expired work never reaches the
            # device).
            now = time.monotonic()
            expired = [r for r in batch
                       if r.deadline is not None and r.deadline < now]
            if expired:
                obs.counter("serve.deadline_expired_total").add(
                    len(expired))
                for r in expired:
                    r.future._set_exception(TimeoutError(
                        "request deadline expired before dispatch"))
                batch = [r for r in batch if r not in expired]
                if not batch:
                    continue
            # ONE generation read per micro-batch: every row in this
            # dispatch — and every response split from it — scores on
            # the same params (the no-torn-swap contract).
            gen = self._gen  # fmlint: disable=thread-lock-discipline -- single atomic reference read per micro-batch IS the protocol (no-torn-swap contract)
            with obs.interval("serve/assemble", requests=len(batch)):
                ids = (batch[0].ids if len(batch) == 1 else
                       np.concatenate([r.ids for r in batch]))
                vals = (batch[0].vals if len(batch) == 1 else
                        np.concatenate([r.vals for r in batch]))
            try:
                out, executed = self._execute(gen, ids, vals,
                                              requests=len(batch))
            except BaseException as e:  # noqa: BLE001 — every queued
                # caller must be answered (exactly once), even by the
                # failure; HangDetected and injected faults land here.
                obs.counter("serve.batch_failures_total").add(1)
                if isinstance(e, watchdog.HangDetected):
                    # SLO overrun (ISSUE 14): the serve_request phase
                    # blew its deadline. Arm a rate-limited deep
                    # capture while the slow program is resident, and
                    # dump the flight window (the capture-context
                    # satellite) — heavy evidence rate-limited like
                    # the watchdog near-miss: a sustained SLO breach
                    # at load overruns every micro-batch, and the
                    # worker must answer callers, not fsync per batch.
                    overrun = dict(phase=e.phase,
                                   deadline_s=round(e.deadline_s, 3),
                                   elapsed_s=round(e.elapsed_s, 3),
                                   rows=int(ids.shape[0]),  # fmlint: disable=jax-host-sync -- ids is a host np.ndarray (coalesced request rows), not a traced value
                                   gen_step=gen.step)
                    # The offending requests' trace ids ride the
                    # capture context verbatim into capture.json —
                    # the bundle names the traces it explains.
                    traces = [r.trace.trace_id for r in batch
                              if r.trace is not None][:8]
                    if traces:
                        overrun["traces"] = traces
                    obs.counter("serve.slo_overruns_total").add(1)
                    armed = False
                    bundle = None
                    try:
                        from fm_spark_tpu.obs import introspect

                        armed = introspect.active()
                        if armed:
                            bundle = introspect.fire(
                                "serve_slo_overrun", **overrun)
                    except Exception:
                        pass
                    now = time.monotonic()
                    throttled = (self._last_slo_dump is not None
                                 and now - self._last_slo_dump
                                 < watchdog.NEAR_MISS_DUMP_INTERVAL_S)
                    if ((armed and bundle is not None)
                            or (not armed and not throttled)):
                        self._last_slo_dump = now
                        obs.event("serve_slo_overrun", **overrun)
                        obs.flight_dump("serve_slo_overrun", **overrun)
                obs.event("serve_batch_failed",
                          error=f"{type(e).__name__}: "
                                f"{(str(e).splitlines() or [''])[0][:200]}",
                          rows=int(ids.shape[0]), gen_step=gen.step)  # fmlint: disable=jax-host-sync -- ids is a host np.ndarray; failure path, not the dispatch loop
                if self.journal is not None:
                    self.journal.emit(
                        "serve_batch_failed",
                        error=f"{type(e).__name__}", gen_step=gen.step)
                for r in batch:
                    r.future._set_exception(e)
                continue
            # Every request's queue / execute / split, from the stamps
            # of its batch's ``serve/batch`` interval: ``serve/queue``
            # (submit to execute start, parented to that batch) for all
            # of them in the always-live ring, and for a traced request
            # the ``serve/coalesce`` link span built from the same
            # three numbers.
            exec_sid = executed.span_id
            t_exec0, t_exec1 = executed.t0, executed.t1
            hist = obs.histogram("serve/request_ms")
            with obs.interval("serve/split", requests=len(batch)):
                off = 0
                t_done = time.perf_counter()
                for r in batch:
                    r.future._set(out[off:off + r.n])
                    off += r.n
                    lat_ms = (t_done - r.t_submit) * 1e3
                    hist.observe(lat_ms,
                                 exemplar=(r.trace.trace_id
                                           if r.trace is not None
                                           else None))
                    obs.record_interval("serve/queue", r.t_submit,
                                        t_exec0, parent_id=exec_sid,
                                        rows=r.n)
                    if r.trace is not None:
                        # One link span per coalesced request: the
                        # request's queue-to-split window, joined to
                        # the SHARED ``serve/batch`` span via
                        # ``exec_span`` (N requests, one execute — the
                        # coalescing topology stays visible in the
                        # merged trace).
                        obs.emit_span(
                            "serve/coalesce", r.t_wall,
                            t_done - r.t_submit,
                            trace=r.trace.trace_id,
                            remote_parent=r.trace.parent_span_id,
                            exec_span=exec_sid,
                            queue_ms=round(
                                (t_exec0 - r.t_submit) * 1e3, 3),
                            exec_ms=round((t_exec1 - t_exec0) * 1e3, 3),
                            split_ms=round((t_done - t_exec1) * 1e3, 3),
                            rows=r.n)

    def close(self) -> None:
        """Stop the coalescer after answering everything queued."""
        with self._worker_lock:
            self._closed = True
            worker = self._worker
        if worker is not None and worker.is_alive():
            self._queue.put(_STOP)
            worker.join(timeout=30.0)
