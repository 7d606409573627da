"""The measured window's records out of the program's ring of hot
intervals (``fm_spark_tpu.obs.intervals()``: finished intervals on
``time.perf_counter()``, the clock the harness and both drivers use),
for the per-layer metrics whose ``source`` is ``program_span``.

A reader runs after ``driver.run()`` has returned, in the same process:
the loop is over, the producer closed, the engine closed, and the ring
holds every interval of the run (at most 65,536; the longest cell makes
about 8,000). ``LayerRun`` carries no instant of the window, so the
window is selected by COUNT, from the ring's tail:

- training: the loop ended inside the step whose log line closed the
  window, ``log_every`` steps after the window's last line; before
  those lie the window's ``run.log["steps"]`` ``train/step`` records,
  and before them the warm-up and the check run. Children belong to a
  step by ``parent_id``; a ``feed/produce`` (another thread) belongs to
  the window if it ended inside those steps' time range. The metrics
  leave out the two log periods in which the profiler's session started
  and stopped (readers run in traced runs only, and the session costs
  4-9 s of a 20 s window, all of it in two steps' self time).
- scoring: nothing is submitted after the window (the re-check asks the
  reference), so the last ``run.log["stats"]["requests"]``
  ``serve/queue`` records are the window's requests, and every
  ``serve/batch`` that began after the first of them was submitted is
  one of the window's batches.

Fewer than half the expected records, or a program without the ring (the
parent of the PR that added it): ``None``, and the harness leaves the
metric out of the line.
"""

from __future__ import annotations

import json
import statistics

from benchmark.harness import log

STEP = "train/step"
STEP_PARTS = ("train/next_batch", "train/prep", "train/dispatch",
              "train/loss_fetch")
PRODUCE, PUT_WAIT = "feed/produce", "feed/put_wait"
QUEUE, BATCH = "serve/queue", "serve/batch"
COALESCER = ("serve/gather", "serve/assemble", BATCH, "serve/split")


def ring() -> list | None:
    """A snapshot of the program's ring, oldest first; None where the
    program has none."""
    from fm_spark_tpu import obs

    take = getattr(obs, "intervals", None)
    return take() if take is not None else None


def seconds(records) -> list[float]:
    return [r.t1 - r.t0 for r in records]


def _inside(records, name: str, t0: float, t1: float) -> list:
    return [r for r in records if r.name == name and t0 <= r.t1 <= t1]


def _without_profiler_steps(window: list, period: int) -> list:
    """``window`` less the two log periods that paid for the profiler:
    the benchmark starts and stops its session from inside a log line,
    so the cost (seconds, on the chip's host) lands in the self time of
    the step before the first ``profiled`` one and of the last
    ``profiled`` one. A share of the LOOP's time leaves them out, with
    the ``period - 1`` steps before each: a period's one loss fetch
    waits for all its steps."""
    inside = [k for k, r in enumerate(window)
              if getattr(r, "profiled", False)]
    if not inside:
        return window
    paid = {k - back for k in (inside[0] - 1, inside[-1])
            for back in range(max(period, 1))}
    return [r for k, r in enumerate(window) if k not in paid]


def train_window(run, records=None, clean: bool = True) -> dict | None:
    """``name -> records`` of the window's steps: ``train/step``, its
    four parts, and the feed's two (by time range). ``clean`` leaves
    out the two log periods in which the profiler started and stopped."""
    records = ring() if records is None else records
    steps = int(run.log.get("steps") or 0)
    if records is None or steps <= 0:
        return None
    whole = [r for r in records if r.name == STEP]
    after = int(run.cell.mix.get("log_every", 0))
    window = whole[:len(whole) - after][-steps:]
    if 2 * len(window) < steps:
        return None
    t0, t1 = window[0].t0, window[-1].t1
    if clean:
        window = _without_profiler_steps(window, after)
    mine = {r.span_id for r in window}
    out = {STEP: window}
    for name in STEP_PARTS:
        out[name] = [r for r in records
                     if r.name == name and r.parent_id in mine]
    for name in (PRODUCE, PUT_WAIT):
        out[name] = _inside(records, name, t0, t1)
    return out


def score_window(run, records=None) -> dict | None:
    """``name -> records`` of the window's requests (``serve/queue``)
    and of the coalescer's four intervals per micro-batch."""
    records = ring() if records is None else records
    requests = int(run.log.get("stats", {}).get("requests") or 0)
    if records is None or requests <= 0:
        return None
    queued = [r for r in records if r.name == QUEUE][-requests:]
    if 2 * len(queued) < requests:
        return None
    t0 = min(r.t0 for r in queued)
    out = {QUEUE: queued}
    for name in COALESCER:
        out[name] = [r for r in records if r.name == name and r.t0 >= t0]
    return out


# ----------------------------------------------- what the readers compute


def train_share(run, name: str) -> float | None:
    """Time in the part ``name`` over time in ``train/step`` (%)."""
    window = train_window(run)
    if window is None:
        return None
    return 100.0 * sum(seconds(window[name])) / sum(seconds(window[STEP]))


def train_ms(run, name: str, mean: bool = False) -> float | None:
    """Median (or mean) length of the window's ``name`` records (ms)."""
    window = train_window(run)
    if window is None or not window[name]:
        return None
    take = statistics.fmean if mean else statistics.median
    return 1e3 * take(seconds(window[name]))


def score_median_ms(run, name: str) -> float | None:
    window = score_window(run)
    if window is None or not window[name]:
        return None
    return 1e3 * statistics.median(seconds(window[name]))


# ------------------------------------------- the whole picture, to stderr


def _ms(values) -> dict:
    values = sorted(values)
    if not values:
        return {"n": 0}
    return {"n": len(values), "mean": round(1e3 * statistics.fmean(values), 4),
            "p50": round(1e3 * statistics.median(values), 4),
            "min": round(1e3 * values[0], 4),
            "max": round(1e3 * values[-1], 4)}


def log_train_summary(run) -> None:
    """One stderr line a traced run's reader leaves for PERF.md: the
    mean step and its five parts (``self`` by subtraction), per step,
    over the whole window (what ``window_span_s / window_steps`` of the
    driver's notes should equal) and without the profiler's two log
    periods (what the metrics read)."""
    whole = train_window(run, clean=False)
    window = train_window(run)
    if whole is None:
        return

    def per_step(w):
        n = len(w[STEP])
        mean = {name: sum(seconds(w[name])) / n
                for name in (STEP, *STEP_PARTS)}
        mean["self"] = mean[STEP] - sum(mean[p] for p in STEP_PARTS)
        return {"steps": n, **{k: round(1e3 * v, 4) for k, v in mean.items()}}

    log("program spans:", json.dumps({
        "mean_ms_per_step_whole_window": per_step(whole),
        "mean_ms_per_step": per_step(window),
        **{name: _ms(seconds(window[name]))
           for name in (*STEP_PARTS, PRODUCE, PUT_WAIT)}}))


def log_score_summary(run) -> None:
    window = score_window(run)
    if window is None:
        return
    gathers = window["serve/gather"]
    log("program spans:", json.dumps({
        **{name: _ms(seconds(records)) for name, records in window.items()},
        "gather_idle": _ms([r.attrs.get("idle_s", 0.0) for r in gathers]),
        "requests_per_batch": round(
            len(window[QUEUE]) / max(len(window[BATCH]), 1), 3)}))
