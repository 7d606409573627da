"""The five per-layer metrics under ``setup_s``: the selection of the
cell's own ``setup/run`` and of what ended before the window on a
hand-made ring, each reader's arithmetic, the manifest's entries, and
every cell's traced rehearsal reporting all five (the CPU has a ring and
jax reports its compilations there too)."""

import importlib
import json

import pytest

from benchmark import program_spans, setup_spans
from benchmark.tests.test_program_spans import (
    Rec, a_step, score_ring, score_run, train_run)
from benchmark.tests.test_rehearsal import MANIFEST, run

NAMES = ["setup_program_s", "setup_tables_s", "setup_warmup_s",
         "setup_compile_s", "setup_fresh_compiles"]
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def a_call(ring, t, steps, *, data=1.0, init=0.5, place=0.25, build=0.125,
           self_s=0.125, first_step=2.0, slow=1.0, warm=False):
    """One ``cli train`` call at ``t``: ``setup/run`` with its phases, a
    first step that traces, lowers and reads (or compiles) the program
    under its dispatch, then ``steps - 1`` plain steps. Returns the
    instant it ends."""
    t0 = t
    t += self_s
    for name, dur in (("setup/data", data), ("setup/init", init),
                      ("setup/place", place), ("setup/step_build", build)):
        ring.append(Rec(name, t, dur))
        t += dur
    ring.append(Rec("setup/run", t0, t - t0, entry="train"))
    # The first dispatch: 0.5 s of trace, 0.25 of lowering, 1 s in the
    # backend of which a warm process spends 0.5 reading the cache.
    dispatch = Rec("train/dispatch", t, first_step, step=0)
    ring.append(Rec("compile/trace", t, 0.5, dispatch, fun_name="step"))
    ring.append(Rec("compile/lower", t + 0.5, 0.25, dispatch,
                    fun_name="jit(step)"))
    if warm:
        ring.append(Rec("compile/cache_read", t + 0.875, 0.5, dispatch))
    ring.append(Rec("compile/backend", t + 0.75, 1.0, dispatch,
                    fun_name="jit(step)", cache_hit=warm))
    ring.append(dispatch)
    ring.append(Rec("train/step", t, first_step, step=0))
    t += first_step
    for k in range(1, steps):
        t = a_step(ring, t, k, {"train/next_batch": 0.0,
                                "train/prep": 0.001 * slow,
                                "train/dispatch": 0.002 * slow,
                                "train/loss_fetch": 0.01 * slow})
    return t


def train_ring(recompile_at=None):
    """Two check runs of 8 steps, the reference's own jit between them
    and the cell's call, then the cell's own call: a first step, 3 warm
    steps, the window's 10 and ``log_every`` = 2 more. The check runs'
    set-up is ten times the cell's, so a selection that slips shows."""
    ring, t = [], 100.0
    t = a_call(ring, t, 8, data=10.0, init=5.0, place=2.5, slow=10.0)
    t = a_call(ring, t, 8, data=10.0, init=5.0, place=2.5, slow=10.0,
               warm=True)
    ring.append(Rec("compile/trace", t, 2.0, fun_name="reference"))
    # A lowering that jax's clock starts before its trace has ended.
    ring.append(Rec("compile/lower", t + 1.5, 1.0, fun_name="jit(reference)"))
    ring.append(Rec("compile/backend", t + 2.5, 1.5,
                    fun_name="jit(reference)", cache_hit=True))
    t += 4.0
    t = a_call(ring, t, 4, warm=True)
    for k in range(4, 16):
        step = a_step(ring, t, k, {"train/next_batch": 0.0,
                                   "train/prep": 0.001,
                                   "train/dispatch": 0.002,
                                   "train/loss_fetch": 0.01})
        if k == recompile_at:
            dispatch = [r for r in ring if r.name == "train/dispatch"][-1]
            ring.append(Rec("compile/backend", t + 0.001, 0.001, dispatch,
                            fun_name="jit(step)", cache_hit=False))
        t = step
    return ring


def reader(name):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read


# ------------------------------------------------------------ the selection


def test_the_last_setup_run_before_the_window_is_the_cells_own():
    ring = train_ring()
    found = setup_spans.select(train_run(), ring)
    assert len(found["runs"]) == 3
    own = found["run"]
    assert own is found["runs"][-1] and own.t1 - own.t0 == pytest.approx(2.0)
    assert [r.name for r in found["phases"]] == [
        "setup/data", "setup/init", "setup/place", "setup/step_build"]
    assert all(own.t0 <= r.t0 and r.t1 <= own.t1 for r in found["phases"])
    # The window's first instant is its first step's start: step 4.
    steps = [r for r in ring if r.name == "train/step"]
    first = [r for r in steps if r.attrs["step"] == 4][-1]
    assert found["first"] == first.t0
    assert all(r.t1 <= found["first"] for r in found["before"])
    assert len([r for r in found["before"]
                if r.name == "compile/backend"]) == 4
    assert found["inside"] == []


def test_records_that_end_inside_the_window_count_for_nothing():
    quiet = setup_spans.select(train_run(), train_ring())
    ring = train_ring(recompile_at=9)
    found = setup_spans.select(train_run(), ring)
    (inside,) = found["inside"]
    assert inside.attrs["cache_hit"] is False
    assert len(found["before"]) == len(quiet["before"])
    # A set-up run that ends inside the window (a reload's engine, a
    # second trainer) is not the cell's either.
    ring.append(Rec("setup/run", found["first"] - 1.0, 1.5, entry="serve"))
    assert setup_spans.select(train_run(), ring)["run"] is found["run"]


def test_without_a_setup_run_every_reader_says_none(monkeypatch):
    """The parent of the PR that added the spans: a ring, a window, no
    ``setup/run``, no ``compile/*``."""
    parents = [r for r in train_ring()
               if not r.name.startswith(("setup/", "compile/"))]
    assert program_spans.train_window(train_run(), parents) is not None
    assert setup_spans.select(train_run(), parents) is None
    for ring in (parents, [], None):
        monkeypatch.setattr(program_spans, "ring", lambda ring=ring: ring)
        assert [reader(name)(train_run()) for name in NAMES] == [None] * 5
        setup_spans.log_summary(train_run())       # and leaves no line
    # A window that cannot be selected: None too, nothing raised.
    monkeypatch.setattr(program_spans, "ring", train_ring)
    assert [reader(name)(train_run(steps=400)) for name in NAMES] == [None] * 5


def test_covered_seconds_count_an_instant_once_per_thread():
    a = Rec("compile/trace", 0.0, 2.0)
    inner = Rec("compile/trace", 0.5, 0.25)
    late = Rec("compile/lower", 1.5, 1.0)
    apart = Rec("compile/backend", 4.0, 1.0)
    assert setup_spans.covered_s([apart, late, inner, a]) == 3.5
    other = Rec("compile/backend", 0.0, 1.0)
    other.thread = 2
    assert setup_spans.covered_s([a, other]) == 3.0
    assert setup_spans.covered_s([]) == 0.0


# ------------------------------------------------------------- the readers


def test_training_readers(monkeypatch, capfd):
    monkeypatch.setattr(program_spans, "ring", train_ring)
    run_ = train_run()
    assert reader("setup_program_s")(run_) == pytest.approx(2.0)
    # init + place of the cell's own call, not the check runs' 7.5.
    assert reader("setup_tables_s")(run_) == pytest.approx(0.75)
    # The first step (2 s) and three warm ones of 14 ms.
    assert reader("setup_warmup_s")(run_) == pytest.approx(2.0 + 3 * 0.014)
    # Three steps' trace + lower + backend = 1.75 each (the cache read
    # lies inside the backend) and the reference's 4.0 less the half
    # second its lowering overlaps its trace.
    assert reader("setup_compile_s")(run_) == pytest.approx(3 * 1.75 + 4.0)
    # The first check run compiled; everything after it hit the cache.
    assert reader("setup_fresh_compiles")(run_) == 1
    (line,) = [ln for ln in capfd.readouterr().err.splitlines()
               if ln.startswith("benchmark: setup spans: ")]
    doc = json.loads(line.split("setup spans: ", 1)[1])
    assert [r["s"] for r in doc["runs"]] == [17.75, 17.75, 2.0]
    own = doc["runs"][-1]
    assert own["self_s"] == 0.125 and own["entry"] == "train"
    assert [p["name"] for p in own["phases"]] == [
        "setup/data", "setup/init", "setup/place", "setup/step_build"]
    assert doc["own_run_to_window_compile_s"] == 1.75
    assert doc["longest"][0] == {"name": "compile/trace", "s": 2.0,
                                 "fun_name": "reference"}
    assert len(doc["longest"]) == 10 and doc["compile_in_window"] == []
    assert doc["compile_before_window"]["compile/cache_read"] == {
        "n": 2, "s": 1.0}


def test_a_compilation_inside_the_window_is_named_with_its_step(
        monkeypatch, capfd):
    monkeypatch.setattr(program_spans, "ring",
                        lambda: train_ring(recompile_at=9))
    assert reader("setup_program_s")(train_run()) == pytest.approx(2.0)
    assert reader("setup_fresh_compiles")(train_run()) == 1
    (line,) = [ln for ln in capfd.readouterr().err.splitlines()
               if ln.startswith("benchmark: setup spans: ")]
    doc = json.loads(line.split("setup spans: ", 1)[1])
    assert doc["compile_in_window"] == [{
        "name": "compile/backend", "s": 0.001, "fun_name": "jit(step)",
        "cache_hit": False, "under": "train/dispatch", "step": 9}]


def serve_ring():
    """An engine built and warmed (install inside its ``setup/run``, four
    buckets read from the cache under ``setup/warmup``), then the pool,
    the check and the warm traffic of ``score_ring``, whose clock starts
    at 0."""
    ring = [Rec("setup/install", -10.0, 0.25, tables=39)]
    warmup = Rec("setup/warmup", -9.5, 1.5, buckets=4)
    for k in range(4):
        ring.append(Rec("compile/backend", -9.5 + 0.375 * k, 0.25, warmup,
                        fun_name="jit(_lambda_)", cache_hit=True))
    ring += [warmup, Rec("setup/run", -10.25, 2.25, entry="serve")]
    return ring + score_ring()


def test_scoring_readers(monkeypatch):
    monkeypatch.setattr(program_spans, "ring", serve_ring)
    run_ = score_run()
    window = program_spans.score_window(run_, serve_ring())
    first = min(r.t0 for r in window["serve/queue"])
    assert reader("setup_program_s")(run_) == pytest.approx(2.25)
    assert reader("setup_tables_s")(run_) == pytest.approx(0.25)
    assert reader("setup_warmup_s")(run_) == pytest.approx(first + 8.0)
    assert reader("setup_compile_s")(run_) == pytest.approx(1.0)
    assert reader("setup_fresh_compiles")(run_) == 0


# ------------------------------------------------------------ the manifest


def test_five_entries_on_entry_points_move_setup_s():
    mine = [m for m in MANIFEST["per_layer"] if m["moves"] == "setup_s"]
    assert [m["name"] for m in mine] == NAMES
    assert MANIFEST["per_layer"][-5:] == mine       # appended, in order
    for m in mine:
        assert m["layer"] == "entry_points" and m["better"] == "lower"
        assert m["source"] == "program_span" and m["workloads"] == CELLS
        assert m["unit"] == ("compilations" if m["name"].endswith("compiles")
                             else "s")
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


# ----------------------------------------------------------- the rehearsals


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_reports_all_five(cell, tmp_path):
    done = run("--workload", cell, "--seed", "5", "--seconds", "3",
               "--trace", "1", "--rehearse", cache_dir=tmp_path)
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    values = line["rehearsed"]["values"]
    assert set(NAMES) <= set(values), sorted(values)
    assert all(values[name] >= 0.0 for name in NAMES)
    # An empty cache of its own: this process compiled every program.
    assert values["setup_fresh_compiles"] > 0
    assert values["setup_tables_s"] <= values["setup_program_s"]
    assert line["metrics"] == {}        # a rehearsal is never a result
    (summary,) = [ln for ln in done.stderr.splitlines()
                  if ln.startswith("benchmark: setup spans: ")]
    doc = json.loads(summary.split("setup spans: ", 1)[1])
    assert doc["compile_in_window"] == []
    entry = "serve" if "score" in cell else "train"
    assert doc["runs"][-1]["entry"] == entry
    assert doc["runs"][-1]["s"] == pytest.approx(values["setup_program_s"],
                                                 abs=1e-3)
