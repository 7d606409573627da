"""The per-layer metrics read from the program's hot intervals: the
selection of the window's records by count on a hand-made ring, each
reader's arithmetic, and every cell's traced rehearsal reporting every
new name (the CPU has a ring, if no device plane)."""

import importlib
import json
import types

import pytest

from benchmark import program_spans
from benchmark.tests.test_rehearsal import MANIFEST, run

SPAN_METRICS = [m for m in MANIFEST["per_layer"]
                if m["source"] == "program_span"]


class Rec:
    """What a reader uses of ``fm_spark_tpu.obs.trace.Interval``."""

    _ids = iter(range(1, 1 << 30))

    def __init__(self, name, t0, dur, parent=None, **attrs):
        self.name, self.t0, self.t1 = name, t0, t0 + dur
        self.span_id = f"1-{next(self._ids):x}"
        self.parent_id = parent.span_id if parent is not None else None
        self.profiled = False
        self.attrs = attrs


def a_step(ring, t, step, parts, self_s=0.001):
    """One ``train/step`` at ``t`` with its parts back to back and
    ``self_s`` after them; returns the instant it ends. Children finish
    (and enter the ring) before their parent, as in the program."""
    parent = Rec("train/step", t, sum(parts.values()) + self_s, step=step)
    for name, dur in parts.items():
        ring.append(Rec(name, t, dur, parent, step=step))
        t += dur
    ring.append(parent)
    return parent.t1


def train_ring(check=8, warm=4, steps=10, log_every=2):
    """A run as the training driver makes one: the check run's steps,
    warm-up steps, the window's, and ``log_every`` more up to the line
    that closed it. The window's steps take 100 ms (40 ms of it blocked
    on the queue at every other step); all others take ten times that,
    so a selection that slips shows. The producer makes a batch of 30
    or 90 ms, alternating, beside each step."""
    ring, t = [], 0.0

    def some(n, first_step, scale):
        nonlocal t
        for k in range(n):
            step = first_step + k
            ring.append(Rec("feed/produce", t, scale * (0.03, 0.09)[k % 2],
                            rows=8))
            parts = {"train/next_batch": scale * (0.0, 0.04)[k % 2],
                     "train/prep": scale * 0.02,
                     "train/dispatch": scale * 0.005}
            if (step + 1) % log_every == 0:
                parts["train/loss_fetch"] = scale * 0.05
            t = a_step(ring, t, step, parts, self_s=scale * 0.001)

    some(check, 0, 10.0)
    some(warm, 0, 10.0)
    some(steps, warm, 1.0)
    some(log_every, warm + steps, 10.0)
    return ring


def train_run(steps=10, log_every=2):
    return types.SimpleNamespace(
        log={"steps": steps},
        cell=types.SimpleNamespace(mix={"log_every": log_every}))


def score_ring(warm=20, requests=40, per_batch=4):
    """Warm traffic, then the window's requests: each batch of 27 ms
    takes ``per_batch`` requests that waited 5, 10, 15 and 20 ms."""
    ring, t = [], 0.0

    def some(n, queue_scale):
        nonlocal t
        for _ in range(n // per_batch):
            t += 0.030
            ring.append(Rec("serve/gather", t - 0.030, 0.002, idle_s=0.001))
            ring.append(Rec("serve/assemble", t - 0.028, 0.0001))
            batch = Rec("serve/batch", t, 0.027, rows=128, requests=per_batch)
            ring.append(batch)
            for k in range(per_batch):
                wait = queue_scale * 0.005 * (k + 1)
                ring.append(Rec("serve/queue", t - wait, wait, batch, rows=32))
            ring.append(Rec("serve/split", t + 0.027, 0.0001))

    some(warm, 10.0)
    # score() of the check sample: batches with no request queued.
    ring.append(Rec("serve/batch", t + 0.1, 0.5, rows=512, requests=1))
    t += 1.0
    some(requests, 1.0)
    return ring


def score_run(requests=40):
    return types.SimpleNamespace(log={"stats": {"requests": requests}},
                                 cell=types.SimpleNamespace(mix={}))


# ------------------------------------------------------------ the selection


def test_training_selection_by_count():
    ring = train_ring()
    window = program_spans.train_window(train_run(), ring)
    assert [r.attrs["step"] for r in window["train/step"]] == list(range(4, 14))
    # Every part belongs to one of those steps; the 100 ms steps only.
    for name in program_spans.STEP_PARTS:
        assert {r.attrs["step"] for r in window[name]} <= set(range(4, 14))
    assert len(window["train/next_batch"]) == 10
    assert len(window["train/loss_fetch"]) == 5
    assert max(program_spans.seconds(window["train/step"])) < 0.2
    # The producer's batches by time range: those of the window's steps.
    assert len(window["feed/produce"]) == 10
    assert max(program_spans.seconds(window["feed/produce"])) < 0.1


def test_training_metrics_leave_out_the_periods_that_paid_for_the_profiler():
    """The session starts inside the log line of step 7 and stops inside
    that of step 11: steps 8-11 were entered with it on. Both log steps'
    self time holds seconds of the profiler; their periods go."""
    ring = train_ring()
    steps = {r.attrs["step"]: r for r in ring if r.name == "train/step"}
    for k in (8, 9, 10, 11):
        steps[k].profiled = True
    for k in (7, 11):
        steps[k].t1 += 2.0
    kept = program_spans.train_window(train_run(), ring)
    assert [r.attrs["step"] for r in kept["train/step"]] == [4, 5, 8, 9, 12, 13]
    assert len(kept["train/loss_fetch"]) == 3
    assert max(program_spans.seconds(kept["train/step"])) < 0.2
    whole = program_spans.train_window(train_run(), ring, clean=False)
    assert len(whole["train/step"]) == 10
    assert sum(program_spans.seconds(whole["train/step"])) > 4.0
    # The feed is selected by the whole window's time range either way.
    assert kept["feed/produce"] == whole["feed/produce"]


def test_training_selection_needs_half_the_steps():
    ring = train_ring(check=0, warm=0, steps=4)
    assert program_spans.train_window(train_run(steps=9), ring) is None
    window = program_spans.train_window(train_run(steps=8), ring)
    assert len(window["train/step"]) == 4
    assert program_spans.train_window(train_run(steps=0), ring) is None
    assert program_spans.train_window(train_run(), None) is None
    assert program_spans.train_window(train_run(), []) is None


def test_scoring_selection_by_count():
    ring = score_ring()
    window = program_spans.score_window(score_run(), ring)
    assert len(window["serve/queue"]) == 40
    assert max(program_spans.seconds(window["serve/queue"])) < 0.021
    # The window's ten batches; neither the warm traffic's nor score()'s.
    assert len(window["serve/batch"]) == 10
    assert {r.attrs["requests"] for r in window["serve/batch"]} == {4}
    assert len(window["serve/split"]) == 10
    assert program_spans.score_window(score_run(121), ring) is None
    assert program_spans.score_window(score_run(0), ring) is None
    assert program_spans.score_window(score_run(), None) is None


# ------------------------------------------------------------- the readers


def reader(name):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read


def test_training_readers(monkeypatch):
    monkeypatch.setattr(program_spans, "ring", train_ring)
    run_ = train_run()
    # Per pair of steps: parts 0.025 + 0.115, self 0.001 each.
    step_sum = 5 * (0.025 + 0.115 + 0.002)
    assert reader("input_wait_share")(run_) == pytest.approx(
        100 * 5 * 0.04 / step_sum)
    assert reader("device_wait_share")(run_) == pytest.approx(
        100 * 5 * 0.05 / step_sum)
    assert reader("input_place_ms")(run_) == pytest.approx(20.0)
    assert reader("step_dispatch_ms")(run_) == pytest.approx(5.0)
    # The MEAN of the alternating 30 and 90 ms batches, not their median.
    assert reader("feed_batch_ms")(run_) == pytest.approx(60.0)


def test_scoring_readers(monkeypatch):
    monkeypatch.setattr(program_spans, "ring", score_ring)
    assert reader("score_queue_ms_p50")(score_run()) == pytest.approx(12.5)
    assert reader("score_batch_ms_p50")(score_run()) == pytest.approx(27.0)


@pytest.mark.parametrize("name", [m["name"] for m in SPAN_METRICS])
def test_reader_returns_none_without_a_ring(name, monkeypatch):
    """The parent of the PR that added the ring has no
    ``obs.intervals``: nothing to read, nothing raised."""
    from fm_spark_tpu import obs

    monkeypatch.delattr(obs, "intervals")
    assert program_spans.ring() is None
    run_ = train_run() if "score" not in name else score_run()
    assert reader(name)(run_) is None


def test_seven_metrics_on_their_layers():
    assert {m["name"]: m["layer"] for m in SPAN_METRICS} == {
        "input_wait_share": "input", "input_place_ms": "input",
        "feed_batch_ms": "input", "step_dispatch_ms": "entry_points",
        "device_wait_share": "device", "score_queue_ms_p50": "serving",
        "score_batch_ms_p50": "serving"}


# ----------------------------------------------------------- the rehearsals


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_traced_rehearsal_reports_every_span_metric(cell, tmp_path):
    done = run("--workload", cell, "--seed", "3", "--seconds", "3",
               "--trace", "1", "--rehearse", cache_dir=tmp_path)
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    values = line["rehearsed"]["values"]
    want = {m["name"] for m in SPAN_METRICS if cell in m["workloads"]}
    assert want and want <= set(values), (sorted(want), sorted(values))
    assert all(values[name] >= 0.0 for name in want)
    assert line["metrics"] == {}        # a rehearsal is never a result
    assert "benchmark: program spans:" in done.stderr
