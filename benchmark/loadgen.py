"""Open-loop request schedule and the two threads that play it.

A schedule is one FIXED trace of arrivals and sizes, drawn once from the
mix's own ``schedule_seed``, and played with the rows the run's ``--seed``
picks:

- arrivals: ``n = round(rate * seconds)`` instants, sorted uniforms on
  ``[0, seconds)`` — a Poisson process conditioned on its count;
- rows per request: the ``n`` mid-quantiles of a log-normal (median,
  sigma) clipped to ``[min, max]``, shuffled — the same multiset of
  sizes, so the same total of rows, at every rate and length;
- each request is the slice ``[offset, offset + rows)`` of one pool of
  Zipf rows (``synthetic.zipf_pool``); pool and offsets come from the
  run's seed.

Why the trace is frozen: at four fifths of the knee queueing is made by
the few worst bursts of a window, and which bursts a window holds is the
schedule's draw, not the system's doing. With one trace every run and
every PR meets the same bursts, and a seed still changes every id that
is looked up. (It steadies the median; the tails stay hostage to stalls
of the chip's shared host and are reported per layer, PERF.md.)

One sender thread submits each request when it is due (never earlier,
and without waiting for answers: open loop) and one collector thread
takes the answers in order. The sender sleeps to within ``SPIN_SECONDS``
of the due instant and yields in a loop from there: a sleep on the
chip's host overshoots by about 1.1 ms (my chip run, PR 22), which
would sit in every latency. Latency runs from the instant a request was
DUE, so a late generator or a stalled server lengthens it; how late the
generator itself ran is reported beside it.
"""

from __future__ import annotations

import dataclasses
import statistics
import threading
import time

import numpy as np

SPIN_SECONDS = 0.002


@dataclasses.dataclass(frozen=True)
class Schedule:
    due: np.ndarray        # float64 [n] seconds from the window's start
    rows: np.ndarray       # int64 [n]
    offset: np.ndarray     # int64 [n] first pool row of each request
    seconds: float

    def __len__(self) -> int:
        return len(self.due)

    @property
    def total_rows(self) -> int:
        return int(self.rows.sum())


def lognormal_sizes(n: int, median: float, sigma: float,
                    lo: int, hi: int) -> np.ndarray:
    """The ``n`` mid-quantiles of LogNormal(ln median, sigma), rounded
    and clipped to ``[lo, hi]``, in increasing order."""
    inv = statistics.NormalDist().inv_cdf
    z = np.asarray([inv((i + 0.5) / n) for i in range(n)])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(
        np.int64)


def make_schedule(*, requests_per_s: float, seconds: float, seed: int,
                  schedule_seed: int, stream: int, rows_per_request: dict,
                  pool_rows: int) -> Schedule:
    """The schedule of one phase (``stream`` keeps the warm-up's draw
    apart from the window's): arrivals and sizes from ``schedule_seed``,
    the rows asked for from ``seed``."""
    n = max(1, int(round(requests_per_s * seconds)))
    trace = np.random.default_rng((schedule_seed, stream))
    due = np.sort(trace.random(n)) * seconds
    sizes = lognormal_sizes(n, rows_per_request["median"],
                            rows_per_request["sigma"],
                            rows_per_request["min"], rows_per_request["max"])
    rows = trace.permutation(sizes)
    hi = pool_rows - int(rows_per_request["max"])
    if hi < 1:
        raise ValueError("the id pool is smaller than the largest request")
    offset = np.random.default_rng((seed, stream)).integers(0, hi, n)
    return Schedule(due=due, rows=rows, offset=offset,
                    seconds=float(seconds))


class Played:
    """What one played schedule recorded (all times ``perf_counter``)."""

    def __init__(self, n: int, t0: float):
        self.t0 = t0
        self.sent = np.full(n, np.nan)
        self.done = np.full(n, np.nan)
        self.answers: list = [None] * n
        self.errors: list = []          # (index, repr(exception))


def play(submit, pool_ids, pool_vals, sched: Schedule, *,
         grace_seconds: float, during=None) -> Played:
    """Submit every request of ``sched`` when due and collect every
    answer; returns once all are answered or ``grace_seconds`` after the
    schedule's end. ``submit(ids, vals)`` returns a future with
    ``result(timeout)``. A request with no answer by then, or whose
    future raised, is listed in ``errors``. ``during(t0)`` runs on the
    calling thread while the schedule plays (the traced run starts and
    stops the profiler there)."""
    n = len(sched)
    due, rows, offset = (sched.due.tolist(), sched.rows.tolist(),
                         sched.offset.tolist())
    futures: list = []
    t0 = time.perf_counter() + 0.05
    rec = Played(n, t0)
    give_up = t0 + sched.seconds + grace_seconds
    sender_done = threading.Event()

    def send():
        clock, sleep, sent = time.perf_counter, time.sleep, rec.sent
        try:
            for i in range(n):
                at = t0 + due[i]
                wait = at - clock()
                if wait > SPIN_SECONDS:
                    sleep(wait - SPIN_SECONDS)
                while clock() < at:
                    sleep(0)            # yields the interpreter lock
                lo = offset[i]
                hi = lo + rows[i]
                sent[i] = clock()
                futures.append(submit(pool_ids[lo:hi], pool_vals[lo:hi]))
        finally:
            sender_done.set()

    def collect():
        clock = time.perf_counter
        for i in range(n):
            while i >= len(futures):
                if sender_done.is_set() and i >= len(futures):
                    rec.errors.append((i, "never submitted"))
                    break
                time.sleep(0.0002)
            else:
                try:
                    rec.answers[i] = futures[i].result(
                        timeout=max(give_up - clock(), 0.0))
                    rec.done[i] = clock()
                except Exception as e:  # noqa: BLE001 — counted as failed
                    rec.errors.append((i, repr(e)))

    threads = [threading.Thread(target=send, name="bench-send"),
               threading.Thread(target=collect, name="bench-collect")]
    for t in threads:
        t.start()
    try:
        if during is not None:
            during(t0)
    finally:
        for t in threads:
            t.join()
    return rec


def percentile(x: np.ndarray, q: float) -> float:
    return float(np.percentile(x, q)) if len(x) else float("nan")
