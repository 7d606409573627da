"""Hot model reload from the checkpoint chain: the serving follower.

The trainer's crash-consistent chain (PR 3) already has an atomic
publish point — ``last_good.json`` advances only to manifest-verified
steps — so "deploy the newest model" is a POLL, not an RPC:
:class:`ReloadFollower` watches ``last_good`` through the read-only
:class:`~fm_spark_tpu.checkpoint.ChainFollower` (never a write on the
trainer's directory — the ISSUE 12 satellite), loads + verifies the
new generation entirely OFF the request path, and installs it via
:meth:`~fm_spark_tpu.serve.engine.PredictEngine.swap_generation` — a
single atomic reference store, so a request sees exactly one
consistent generation, never a torn mixture.

Failure is a MODE, not an exception: when a reload attempt fails
(corrupt bytes, a torn chain, an injected ``serve_reload`` fault), the
follower journals ``reload_failed``, raises the ``serve/degraded``
gauge, and KEEPS SERVING the old generation; the next poll retries
from scratch. Staleness is always measurable: the
``serve/staleness_steps`` gauge tracks ``last_good - served_step`` on
every poll, and bounded staleness after recovery is one of the chaos
auditor's serving invariants
(:func:`fm_spark_tpu.resilience.chaos.audit_serve_events`).
"""

from __future__ import annotations

import threading
import time

from fm_spark_tpu import obs
from fm_spark_tpu.checkpoint import ChainFollower
from fm_spark_tpu.resilience import faults

__all__ = ["ReloadFollower"]


class ReloadFollower:
    """Poll a checkpoint chain and hot-swap the engine's generation.

    ``opt_state_example`` pins the checkpoint's optimizer-state
    structure (``{}`` for the pure-SGD field_sparse families; the
    caller builds the optax example for families that carry one).
    ``params_example`` defaults to the canonical shapes of what the
    engine serves (it holds its tables in another form, never the
    canonical tree) — chain generations must share the serving model's
    structure.
    """

    def __init__(self, engine, directory: str, *,
                 poll_s: float = 2.0, journal=None,
                 params_example=None, opt_state_example=None):
        self.engine = engine
        self.poll_s = float(poll_s)
        self.journal = journal
        self.chain = ChainFollower(directory, journal=journal)
        self._params_example = (params_example if params_example
                                is not None
                                else engine.generation().shapes)
        self._opt_example = ({} if opt_state_example is None
                             else opt_state_example)
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        # Outcome counters are written by the poll thread and read by
        # callers (cli serve's summary, tests): guarded — an unlocked
        # += from a direct poll_once() call racing the loop drops
        # counts (fmlint thread-lock-discipline, ISSUE 15).
        self._counter_lock = threading.Lock()
        self.reloads = 0
        self.failures = 0

    # ------------------------------------------------------------ polling

    def _emit(self, event: str, **fields) -> None:
        obs.event(event, **fields)
        if self.journal is not None:
            self.journal.emit(event, **fields)

    def _set_staleness(self, last_good: int | None,
                       served: int) -> int:
        staleness = max(int(last_good) - int(served), 0) \
            if last_good is not None else 0
        obs.gauge("serve/staleness_steps").set(staleness)
        return staleness

    def _fail(self, error: str, target_step: int,
              served: int) -> None:
        """The degraded-mode transition, in one place: count, raise
        the gauge, journal — the old generation keeps serving."""
        with self._counter_lock:
            self.failures += 1
        obs.counter("serve.reload_failures_total").add(1)
        obs.gauge("serve/degraded").set(1)
        self._emit("reload_failed", target_step=int(target_step),
                   served_step=int(served), error=error)

    @property
    def degraded(self) -> bool:
        return bool(obs.gauge("serve/degraded").value or 0)

    def poll_once(self) -> str:
        """One poll of the chain. Returns the outcome:

        ``no_checkpoint``  nothing published yet
        ``fresh``          serving the newest verified generation
        ``swapped``        a newer generation was loaded + installed
        ``stale_chain``    the chain walked back BELOW the served step
                           (newest steps all torn/corrupt/demoted) —
                           keep serving what we have
        ``demoted``        the restored generation was tombstoned
                           between restore and swap (a demotion racing
                           this reload) — refused, old generation
                           keeps serving
        ``failed``         the reload attempt itself failed — degraded
                           mode, old generation keeps serving
        """
        last_good = self.chain.last_good_step()
        served = self.engine.generation().step
        self._set_staleness(last_good, served)
        if last_good is None:
            return "no_checkpoint"
        if last_good <= served:
            return "fresh"
        with obs.span("serve/reload", target_step=int(last_good),
                      served_step=int(served)):
            try:
                # The drill hook (ISSUE 12): serve_reload faults land
                # HERE — inside the attempt, before the swap — so an
                # injected error exercises exactly the degraded path a
                # real torn chain would, and an injected exit is the
                # SIGKILL-mid-reload drill.
                faults.inject("serve_reload")
                restored = self.chain.restore(self._params_example,
                                              self._opt_example)
            except Exception as e:  # noqa: BLE001 — degraded mode IS
                # the handler: serving must outlive a failed reload
                self._fail(f"{type(e).__name__}: "
                           f"{(str(e).splitlines() or [''])[0][:200]}",
                           last_good, served)
                return "failed"
        if restored is None or restored["step"] <= served:
            # Verified chain tip is not ahead of us (torn newest steps
            # walked back past the pointer, or the tip was DEMOTED —
            # ISSUE 13's quarantined-tip case): not a failure, not a
            # swap; the staleness gauge keeps measuring the gap.
            self._fail("no verified step newer than served generation "
                       "(torn/corrupt/demoted chain tip)", last_good,
                       served)
            return "stale_chain"
        if self.chain.is_tombstoned(restored["step"]):
            # Demotion raced the reload: the tombstone landed AFTER
            # restore() walked the chain but before the swap. The
            # verdict wins — a demoted generation must never be
            # installed, even loaded-and-verified.
            obs.counter("serve.demoted_refused_total").add(1)
            self._fail(f"generation {restored['step']} was demoted "
                       "mid-reload (tombstone veto)", last_good, served)
            return "demoted"
        layout = ((restored.get("extra") or {}).get("layout")
                  or "canonical")
        if layout != "canonical":
            self._fail(f"chain holds {layout}-layout checkpoints; "
                       "serving follows canonical layouts only",
                       last_good, served)
            return "failed"
        self.engine.swap_generation(restored["params"],
                                    restored["step"])
        with self._counter_lock:
            self.reloads += 1
        obs.counter("serve.reloads_total").add(1)
        obs.gauge("serve/degraded").set(0)
        self._set_staleness(self.chain.last_good_step(),
                            restored["step"])
        return "swapped"

    # ----------------------------------------------------------- threading

    def start(self) -> "ReloadFollower":
        if self._thread is None or not self._thread.is_alive():
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._loop, name="fm-spark-serve-reload",
                daemon=True)
            self._thread.start()
        return self

    def _loop(self) -> None:
        while not self._stop.wait(self.poll_s):
            t0 = time.perf_counter()
            try:
                self.poll_once()
            except Exception as e:  # noqa: BLE001 — the poll loop
                # must never die silently; journal and keep polling
                self._emit("reload_failed",
                           error=f"poll loop: {type(e).__name__}: "
                                 f"{(str(e).splitlines() or [''])[0][:160]}")
            obs.histogram("serve/reload_poll_ms").observe(
                (time.perf_counter() - t0) * 1e3)

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=30.0)
            self._thread = None
        self.chain.close()
