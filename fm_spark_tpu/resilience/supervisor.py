"""The retry/timeout/backoff state machine for a flaky device attachment.

What used to be ad-hoc (bench.py's hand-rolled parent retry loop, a
bash poll loop's inlined backoff) is here one tested object:

- **Bounded exponential backoff + deterministic jitter**
  (:class:`BackoffPolicy`): delay doubles per consecutive failure, is
  capped, and jitters by a seeded RNG — reproducible in tests, never
  synchronized across restarts in production.
- **Cheap health probe** (:func:`device_probe`): device enumeration in a
  watchdog thread — on this attachment a dead backend HANGS
  ``jax.devices()`` rather than raising, so the probe times out instead
  of trusting an exception to arrive.
- **Circuit breaker**: after N consecutive failed operations the
  supervisor stops burning the deadline on a known-dead attachment and
  raises :class:`CircuitOpen`; a later healthy probe half-opens it for
  one trial.
- **Health-event journal**: every transition is emitted to a JSONL
  :class:`~fm_spark_tpu.utils.logging.EventLog`, so a degraded round
  leaves a machine-readable account of WHAT flapped and what the
  supervisor did about it.

Two entry points: :meth:`Supervisor.run` wraps a whole retryable
operation (a bench sweep leg); :meth:`Supervisor.recover` is the
incremental form for callers that own their loop (``FMTrainer.fit``
catches the device loss itself, then asks the supervisor to account /
probe / back off before it rebuilds state from the checkpoint).
"""

from __future__ import annotations

import dataclasses
import random
import threading
import time

from fm_spark_tpu import obs
from fm_spark_tpu.resilience import faults
from fm_spark_tpu.resilience.faults import is_device_loss

__all__ = [
    "BackoffPolicy",
    "CircuitOpen",
    "RetriesExhausted",
    "Supervisor",
    "device_probe",
]


@dataclasses.dataclass(frozen=True)
class BackoffPolicy:
    """Bounded exponential backoff: ``initial * multiplier**(k-1)``
    seconds after the k-th consecutive failure, capped at ``max_delay``,
    jittered by ±``jitter`` fraction (seeded RNG — deterministic in
    tests). ``max_attempts`` bounds one :meth:`Supervisor.run` call."""

    initial: float = 1.0
    multiplier: float = 2.0
    max_delay: float = 60.0
    jitter: float = 0.1
    max_attempts: int = 4

    def delay(self, failure_index: int, rng: random.Random | None = None
              ) -> float:
        from fm_spark_tpu.utils.sleeps import sleep_scale

        d = min(
            self.initial * self.multiplier ** max(failure_index - 1, 0),
            self.max_delay,
        ) * sleep_scale()  # designed sleep: FM_SPARK_TEST_SLEEP_SCALE
        if self.jitter and rng is not None:
            d *= 1.0 + self.jitter * (2.0 * rng.random() - 1.0)
        return max(d, 0.0)


class RetriesExhausted(RuntimeError):
    """One operation failed ``max_attempts`` times; the last underlying
    exception rides as ``__cause__``."""


class CircuitOpen(RuntimeError):
    """The breaker tripped: N consecutive operations failed and the
    probe still reports the attachment unhealthy — stop retrying and
    degrade (salvage what completed) instead of burning the deadline."""


def device_probe(timeout: float = 30.0) -> bool:
    """Cheap attachment health probe: device enumeration under a
    thread-join timeout. A healthy backend answers in well under a
    second; a dead attachment HANGS the call (the observed mode), which
    the join timeout converts into ``False`` instead of a stuck
    process. The ``probe`` fault point makes the outcome injectable."""
    out: dict = {}

    def _enumerate():
        try:
            faults.inject("probe")
            import jax

            out["n"] = len(jax.devices())
        except Exception:
            out["n"] = 0

    t = threading.Thread(target=_enumerate, daemon=True)
    t.start()
    t.join(timeout)
    return bool(out.get("n"))


class Supervisor:
    """Retry/backoff/circuit-breaker runtime around device-touching work.

    State machine: ``closed`` (normal) → ``open`` after
    ``breaker_threshold`` consecutive failed operations → ``half_open``
    when a probe reports the attachment healthy again → ``closed`` on
    the next success. Every transition and retry is journaled.

    ``probe``/``sleep`` are injectable so the whole machine unit-tests
    without a device or wall-clock (tests/test_resilience.py — the
    fault-matrix suite).
    """

    def __init__(self, policy: BackoffPolicy | None = None, journal=None,
                 probe=None, probe_timeout: float = 30.0,
                 breaker_threshold: int = 3, seed: int = 0,
                 sleep=time.sleep):
        self.policy = policy or BackoffPolicy()
        self.journal = journal
        self.probe_timeout = probe_timeout
        self.breaker_threshold = breaker_threshold
        self._set_state("closed")
        self.consecutive_failures = 0
        # Cumulative failure count across the supervisor's whole life —
        # unlike consecutive_failures it survives note_success/reset, so
        # a caller can DELTA it around one operation to learn whether
        # that operation saw weather (the per-leg attachment-health
        # verdict the perf ledger's fingerprints record, ISSUE 9).
        self.total_failures = 0
        # Identity tracking for the transient-vs-permanent verdict
        # (resilience/elastic.py): a run of IDENTICAL failures (numerals
        # normalized) is the signature of a dead attachment, not a flap.
        self.last_failure: str | None = None
        self.identical_failures = 0
        self._probe = probe
        self._sleep = sleep
        self._rng = random.Random(seed)

    # ------------------------------------------------------------ events

    _BREAKER_STATES = ("closed", "half_open", "open")

    def _emit(self, event: str, **fields) -> None:
        if self.journal is not None:
            self.journal.emit(event, **fields)
        # Telemetry side-channel (ISSUE 7): failure/backoff totals as
        # registry instruments, and a flight-recorder dump at the
        # TERMINAL verdicts — the evidence a dead attachment used to
        # destroy. Best-effort by the journal contract; the journal
        # itself (mirror_to_flight) carries the event into the last-N
        # ring. (The breaker-state gauge is set by _set_state, at the
        # transition — several events fire BEFORE their transition
        # lands, so sampling self.state here would latch stale values.)
        try:
            if event == "failure":
                self.total_failures += 1
                obs.counter("resilience.failures_total").add(1)
            elif event == "backoff":
                obs.counter("resilience.backoffs_total").add(1)
            elif event == "probe":
                obs.counter("resilience.probes_total").add(1)
                if not fields.get("healthy"):
                    obs.counter("resilience.probe_failures_total").add(1)
            if event in ("circuit_open", "permanent_fault"):
                obs.flight_dump(event, **{
                    k: v for k, v in fields.items() if k != "reason"})
        except Exception:
            pass

    def _set_state(self, state: str) -> None:
        """The ONLY writer of breaker state: keeps the registry gauge
        exactly in lockstep with every transition."""
        self.state = state
        try:
            obs.gauge("resilience.breaker_state").set(
                self._BREAKER_STATES.index(state))
        except Exception:
            pass

    @staticmethod
    def _describe(exc: BaseException) -> str:
        first = (str(exc).splitlines() or [""])[0]
        return f"{type(exc).__name__}: {first[:200]}"

    def _note_failure_identity(self, exc: BaseException) -> None:
        """Track runs of identical failures (the permanent-fault
        signature — elastic.classify_failures semantics)."""
        from fm_spark_tpu.resilience.elastic import normalize_failure

        desc = self._describe(exc)
        if (self.last_failure is not None
                and normalize_failure(desc)
                == normalize_failure(self.last_failure)):
            self.identical_failures += 1
        else:
            self.identical_failures = 1
        self.last_failure = desc

    def permanent(self, threshold: int | None = None) -> bool:
        """Is the current failure run classified PERMANENT — the same
        failure, ``threshold`` (default: ``breaker_threshold``) times in
        a row? The elastic controller's shrink trigger; a mixed failure
        run keeps the transient verdict (keep retrying/backing off)."""
        t = self.breaker_threshold if threshold is None else threshold
        return self.identical_failures >= max(t, 1)

    def health_verdict(self) -> str:
        """The attachment-health verdict this supervisor's journal
        currently supports — what the perf ledger stamps into a
        measurement's fingerprint (ISSUE 9): ``down`` when the breaker
        is open or the failure run classifies permanent, ``flaky``
        while a failure streak is live, else ``healthy``. Per-operation
        weather is the caller's delta over :attr:`total_failures`."""
        if self.state == "open" or self.permanent():
            return "down"
        if self.consecutive_failures:
            return "flaky"
        return "healthy"

    def reset(self, op: str = "op") -> None:
        """Re-arm the breaker after the caller changed the world (an
        elastic mesh shrink): the new, smaller gang deserves a fresh
        failure budget. Journaled — a silent reset would make the
        health journal's consecutive counts unexplainable."""
        self._emit("supervisor_reset", op=op,
                   after_failures=self.consecutive_failures)
        self.consecutive_failures = 0
        self.identical_failures = 0
        self.last_failure = None
        self._set_state("closed")

    # ------------------------------------------------------------- probe

    def probe(self) -> bool:
        """Run the health probe (injected or the default device
        enumeration); an exception counts as unhealthy."""
        fn = self._probe or (lambda: device_probe(self.probe_timeout))
        with obs.span("resilience/probe") as sp:
            try:
                healthy = bool(fn())
            except Exception:
                healthy = False
            sp.set(healthy=healthy)
        self._emit("probe", healthy=healthy)
        return healthy

    # ----------------------------------------------------------- breaker

    def _check_circuit(self, op: str) -> None:
        if self.state != "open":
            return
        if self.probe():
            self._set_state("half_open")
            self._emit("circuit_half_open", op=op)
            return
        self._emit("circuit_rejected", op=op)
        raise CircuitOpen(
            f"{op}: circuit open after {self.consecutive_failures} "
            "consecutive failed operations and an unhealthy probe"
        )

    def _note_op_failure(self, op: str) -> None:
        self.consecutive_failures += 1
        if (self.state != "open"
                and self.consecutive_failures >= self.breaker_threshold):
            self._set_state("open")
            self._emit("circuit_open", op=op,
                       consecutive_failures=self.consecutive_failures,
                       permanent=self.permanent())

    def note_success(self, op: str = "op") -> None:
        """Close the circuit and zero the consecutive-failure count
        (called automatically by :meth:`run`; loop owners call it after
        real post-recovery progress)."""
        if self.consecutive_failures or self.state != "closed":
            self._emit("recovered", op=op,
                       after_failures=self.consecutive_failures)
        self.consecutive_failures = 0
        self.identical_failures = 0
        self.last_failure = None
        self._set_state("closed")

    # --------------------------------------------------------- run/recover

    def run(self, fn, op: str = "op", retryable=is_device_loss):
        """Run ``fn()`` with up to ``policy.max_attempts`` tries.

        Only exceptions passing ``retryable`` (default:
        :func:`is_device_loss` — the subsystem's reason to exist) are
        retried; everything else propagates immediately, because
        retrying a program bug just re-crashes until the deadline.
        Exhaustion raises :class:`RetriesExhausted` and counts one
        operation failure toward the breaker.
        """
        self._check_circuit(op)
        last: BaseException | None = None
        for attempt in range(1, self.policy.max_attempts + 1):
            self._emit("attempt", op=op, attempt=attempt)
            try:
                result = fn()
            except Exception as e:  # noqa: BLE001 — classified below
                if not retryable(e):
                    self._emit("failure", op=op, attempt=attempt,
                               error=self._describe(e), retryable=False)
                    raise
                last = e
                # Drop the traceback NOW: its frames pin the failed
                # attempt's locals (multi-GB tables in a bench leg)
                # through the probe, the backoff sleep, and the next
                # attempt's fresh init — exactly the two-resident-sets
                # condition retries must avoid.
                last.__traceback__ = None
                self._note_failure_identity(e)
                self._emit("failure", op=op, attempt=attempt,
                           error=self._describe(e), retryable=True)
                if attempt == self.policy.max_attempts:
                    break
                if self.permanent():
                    # N identical consecutive failures: the attachment
                    # is DEAD, not flapping — re-probing and re-sleeping
                    # the remaining attempts only burns the deadline
                    # (the BENCH_r05 failure mode). Exhaust now; the
                    # elastic controller decides whether to shrink.
                    self._emit("permanent_fault", op=op,
                               identical_failures=self.identical_failures,
                               skipped_attempts=(self.policy.max_attempts
                                                 - attempt))
                    break
                healthy = self.probe()
                delay = self.policy.delay(attempt, self._rng)
                self._emit("backoff", op=op, attempt=attempt,
                           delay_s=round(delay, 3), healthy=healthy)
                with obs.span("resilience/backoff", op=op,
                              delay_s=round(delay, 3)):
                    self._sleep(delay)
            else:
                self.note_success(op)
                return result
        self._note_op_failure(op)
        raise RetriesExhausted(
            f"{op}: {self.policy.max_attempts} attempts failed "
            f"(last: {self._describe(last)})"
        ) from last

    def recover(self, op: str, exc: BaseException) -> None:
        """Account one caught device-loss failure for a caller that owns
        its retry loop (``FMTrainer.fit``): journal it, trip the breaker
        at the threshold (raises :class:`CircuitOpen` — training cannot
        make progress on an attachment that keeps dying), else probe and
        back off before the caller rebuilds from its checkpoint."""
        self.consecutive_failures += 1
        self._note_failure_identity(exc)
        self._emit("failure", op=op, error=self._describe(exc),
                   retryable=True,
                   consecutive_failures=self.consecutive_failures)
        if self.consecutive_failures >= self.breaker_threshold:
            self._set_state("open")
            self._emit("circuit_open", op=op,
                       consecutive_failures=self.consecutive_failures,
                       permanent=self.permanent())
            raise CircuitOpen(
                f"{op}: {self.consecutive_failures} consecutive device "
                "losses — escalating instead of thrashing the checkpoint"
            ) from exc
        # The probe and backoff below each carry their own span: this
        # is the wall-clock the trainer excludes from its throughput
        # window (logger.add_pause), so the spans make it attributable.
        healthy = self.probe()
        delay = self.policy.delay(self.consecutive_failures, self._rng)
        self._emit("backoff", op=op, delay_s=round(delay, 3),
                   healthy=healthy)
        with obs.span("resilience/backoff", op=op,
                      delay_s=round(delay, 3)):
            self._sleep(delay)
