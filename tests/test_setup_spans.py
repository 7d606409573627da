"""Set-up and compilation as hot intervals (ISSUE 38): what ``cli train``
and ``PredictEngine`` leave in the ring BEFORE their loops
(``setup/run`` and its phases), and what jax's own compile events
become (``compile/trace|lower|backend|cache_read``, from the listeners
``utils/compile_cache.enable`` registers).

The contracts:

- one ``setup/run`` per ``cli train`` call, ending before the loop's
  first ``train/step``; its phases lie inside it, disjoint, in order,
  and sum to no more than it; a second call's records are told from the
  first's by time;
- ``PredictEngine(...).warmup()`` leaves ``setup/run`` (``entry="serve"``)
  with ``setup/install`` and ``setup/warmup`` inside it, and no
  ``serve/warmup``; a swap leaves a ``setup/install`` alone;
- a compilation hangs under the interval open on its thread (which step
  compiled, and what), carries jax's ``fun_name``, and says whether the
  persistent cache answered; the count of those it did not agrees with
  ``cache_stats()["misses"]``;
- the listeners never raise into a compile, are registered once, and
  cost a bounded number of calls and bytes per event;
- inside the loop nothing is recorded that was not before.
"""

import dataclasses
import glob
import json
import os
import sys
import threading
import time
import tracemalloc

import jax
import jax.numpy as jnp
import pytest
from jax._src import monitoring

from fm_spark_tpu import cli, models, obs
from fm_spark_tpu import configs as configs_lib
from fm_spark_tpu.serve import PredictEngine
from fm_spark_tpu.utils import compile_cache

PHASES = ["setup/data", "setup/init", "setup/place", "setup/step_build"]
CHILDREN = ["train/next_batch", "train/prep", "train/dispatch",
            "train/loss_fetch"]
TRACE, LOWER, BACKEND, READ = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec")

TINY = {
    "fm": ("criteo1tb_fm_r64", dict(bucket=64, num_fields=5, rank=4)),
    "ffm_adagrad": ("avazu_ffm_r16_adagrad", dict(
        bucket=64, num_fields=5, rank=4, learning_rate=0.05,
        adagrad_init_accumulator=1.0 / 128 ** 2)),
    "dlrm": ("criteo1tb_dlrm_mlperf", dict(
        rank=8, num_fields=8, dense_fields=3, bottom_mlp_dims=(16, 8),
        mlp_dims=(16, 16), bucket=48)),
}


def since(t_mark: float, prefix: str = "") -> list:
    """The ring's records that began after ``t_mark`` (the ring is the
    process's: other tests' records lie before the mark)."""
    return [iv for iv in obs.intervals()
            if iv.t0 >= t_mark and iv.name.startswith(prefix)]


def ended_since(t_mark: float, prefix: str = "") -> list:
    """The same by END: what a listener records began ``duration`` before
    it was told."""
    return [iv for iv in obs.intervals()
            if iv.t1 >= t_mark and iv.name.startswith(prefix)]


@pytest.fixture
def one_chip(monkeypatch):
    monkeypatch.setattr(jax, "device_count", lambda *a: 1)


@pytest.fixture(params=sorted(TINY))
def tiny(request, monkeypatch, one_chip):
    base, small = TINY[request.param]
    cfg = dataclasses.replace(configs_lib.CONFIGS[base],
                              name=f"setup_spans_{request.param}", **small)
    monkeypatch.setitem(configs_lib.CONFIGS, cfg.name, cfg)
    return cfg


def train(config: str, *extra, steps: int = 4) -> float:
    t = time.perf_counter()
    assert cli.main([
        "train", "--config", config, "--synthetic", "512",
        "--batch-size", "128", "--steps", str(steps), "--log-every", "2",
        "--test-fraction", "0", *extra]) == 0
    return t


def check_run(records: list, own, phases=PHASES) -> list:
    """``own`` (a ``setup/run``) holds exactly ``phases``: inside it, on
    its thread, disjoint, in order, summing to no more than it."""
    inside = [iv for iv in records if iv.name.startswith("setup/")
              and iv is not own and own.t0 <= iv.t0 and iv.t1 <= own.t1]
    assert [iv.name for iv in inside] == phases
    for a, b in zip(inside, inside[1:]):
        assert a.t1 <= b.t0
    assert all(iv.thread == own.thread for iv in inside)
    assert sum(iv.dur_s for iv in inside) <= own.dur_s
    return inside


# ------------------------------------------------------------ cli train


def test_cli_train_leaves_one_setup_run_with_its_phases(tiny):
    t = train(tiny.name)
    records = since(t)
    (own,) = [iv for iv in records if iv.name == "setup/run"]
    assert own.attrs == {"entry": "train", "config": tiny.name, "chips": 1}
    assert own.thread == threading.get_ident() and own.t0 >= t
    data, init, place, build = check_run(records, own)
    assert data.attrs == {"rows": 512}
    tables = tiny.num_fields - tiny.dense_fields
    assert init.attrs["tables"] == place.attrs["tables"] == tables
    # On the CPU every table's default layout is row-major already.
    assert place.attrs["form"] == "as_is"
    slots = tiny.optimizer == "adagrad"
    assert (place.attrs["bytes"] > init.attrs["bytes"]) == slots
    assert init.attrs["bytes"] >= tables * tiny.bucket * 4
    # Set-up ends where the loop begins: nothing of the loop inside it.
    steps = [iv for iv in records if iv.name == "train/step"]
    assert len(steps) == 4 and own.t1 <= steps[0].t0
    assert steps[0].t0 - own.t1 < 0.05
    assert not [iv for iv in records if iv.name.startswith(("train/", "feed/"))
                and iv.t1 <= own.t1 and iv.thread == own.thread]


def test_a_second_call_is_told_from_the_first(tiny):
    t1 = train(tiny.name)
    t2 = train(tiny.name)
    records = since(t1)
    first, second = [iv for iv in records if iv.name == "setup/run"]
    assert t1 <= first.t0 <= first.t1 <= t2 <= second.t0 <= second.t1
    check_run(records, first)
    check_run(records, second)
    # The last one before an instant is the later call's.
    before = [iv for iv in records if iv.name == "setup/run"
              and iv.t1 <= time.perf_counter()]
    assert before[-1] is second


def test_a_checkpoint_read_is_a_phase(monkeypatch, one_chip, tmp_path):
    base, small = TINY["fm"]
    cfg = dataclasses.replace(configs_lib.CONFIGS[base],
                              name="setup_spans_resume", **small)
    monkeypatch.setitem(configs_lib.CONFIGS, cfg.name, cfg)
    train(cfg.name, "--checkpoint-dir", str(tmp_path / "chain"), steps=2)
    t = train(cfg.name, "--checkpoint-dir", str(tmp_path / "chain"))
    records = since(t)
    (own,) = [iv for iv in records if iv.name == "setup/run"]
    phases = check_run(records, own, [*PHASES[:2], "setup/resume", *PHASES[2:]])
    assert phases[2].attrs == {"layout": "canonical"}
    # Resumed at step 2 of 4.
    assert [iv.attrs["step"] for iv in records
            if iv.name == "train/step"] == [2, 3]


def test_a_loop_without_phases_still_leaves_its_setup_run(one_chip):
    """The single-strategy trainer is not instrumented: ``cmd_train``
    closes ``setup/run`` before handing over to it."""
    t = train("movielens_fm_r8")
    records = since(t, "setup/")
    assert [iv.name for iv in records] == ["setup/data", "setup/run"]
    assert records[1].attrs["config"] == "movielens_fm_r8"


def test_the_window_holds_no_record_the_parent_would_not(tiny):
    t = train(tiny.name, steps=12)
    records = since(t)
    steps = [iv for iv in records if iv.name == "train/step"]
    assert [iv.attrs["step"] for iv in steps] == list(range(12))
    for parent in steps:
        kids = [iv.name for iv in records if iv.parent_id == parent.span_id]
        logged = parent.attrs["step"] % 2 == 1
        assert kids == CHILDREN[:3] + CHILDREN[3:] * logged
    # After the first step (whose dispatch compiles) the loop's thread
    # records the loop's own names and nothing else.
    main = [iv.name for iv in records
            if iv.thread == steps[0].thread and iv.t0 >= steps[1].t0]
    assert set(main) == {"train/step", *CHILDREN}
    # The first dispatch's compilation hangs under it, with its step.
    dispatch = next(iv for iv in records if iv.name == "train/dispatch")
    under = [iv for iv in records if iv.parent_id == dispatch.span_id]
    assert {iv.name for iv in under} >= {"compile/trace", "compile/lower",
                                        "compile/backend"}
    assert dispatch.attrs["step"] == 0
    assert any(iv.name == "compile/backend"
               and iv.attrs["fun_name"] in ("jit(step)", "jit(_step)")
               for iv in under)


# ------------------------------------------------------------ the scorer


def small_engine():
    spec = models.FieldFMSpec(num_features=4 * 64, rank=4, num_fields=4,
                              bucket=64, init_std=0.1)
    params = spec.init(jax.random.key(0))
    t = time.perf_counter()
    return t, spec, params, PredictEngine(spec, params, buckets=(8, 64))


def test_engine_leaves_setup_run_install_and_warmup():
    t, spec, params, engine = small_engine()
    try:
        engine.warmup()
        records = since(t, "s")
        (own,) = [iv for iv in records if iv.name == "setup/run"]
        assert own.attrs == {"entry": "serve", "model": "FieldFMSpec"}
        install, warmup = check_run(records, own,
                                    ["setup/install", "setup/warmup"])
        assert install.attrs["tables"] == 4 and install.attrs["gen_id"] == 0
        assert install.attrs["resident_table_bytes"] == (
            engine.generation().held["resident_table_bytes"])
        assert warmup.attrs == {"buckets": 2, "nnz": 4}
        assert not [iv for iv in records if iv.name == "serve/warmup"]
        # Both buckets compiled under the warm-up.
        compiled = [iv for iv in since(t, "compile/backend")
                    if iv.parent_id == warmup.span_id]
        assert len(compiled) == 2
        # A second warmup() and a swap are not set-up runs.
        t2 = time.perf_counter()
        engine.warmup()
        engine.swap_generation(params, step=5)
        later = since(t2, "setup/")
        assert [iv.name for iv in later] == ["setup/warmup", "setup/install"]
        assert later[1].attrs["gen_id"] == 1
    finally:
        engine.close()


def test_run_directory_gets_setup_warmup_once(tmp_path):
    obs.configure(str(tmp_path / "run"), run_id="setup_spans")
    try:
        t, spec, params, engine = small_engine()
        try:
            engine.warmup()
        finally:
            engine.close()
        ring = {iv.span_id: iv for iv in since(t, "setup/")}
    finally:
        obs.shutdown(reason=None)
    (path,) = glob.glob(str(tmp_path / "run" / obs.TRACE_FILE))
    with open(path) as f:
        spans = [doc for doc in map(json.loads, f)
                 if doc.get("event") == "span"]
    names = [doc["name"] for doc in spans]
    assert "serve/warmup" not in names
    for name in ("setup/install", "setup/warmup", "setup/run"):
        assert names.count(name) == 1
    for doc in spans:
        if doc["name"].startswith("setup/"):
            iv = ring[doc["span_id"]]
            assert doc["dur_ms"] == pytest.approx(iv.dur_s * 1e3, abs=1e-3)


# ------------------------------------------------------- the compilations


def fresh_function(c=None):
    """A function no cache has seen: a constant of its own (or, given
    ``c``, the same program again as a new function object)."""
    c = float(int.from_bytes(os.urandom(4), "little")) if c is None else c

    def scaled_sum(x):
        return jnp.sum(jnp.tanh(x) * c + jnp.sqrt(jnp.abs(x)))

    return scaled_sum


def test_a_compilation_hangs_under_the_open_interval():
    compile_cache.enable()
    f = fresh_function()
    x = jnp.ones((8, 8), jnp.float32)
    t = time.perf_counter()
    with obs.interval("train/dispatch", step=7) as dispatch:
        jax.jit(f)(x).block_until_ready()
    compiled = since(t, "compile/")
    # jax reports a trace for each jnp function inside f's; one is kept.
    assert [iv.name for iv in compiled] == [
        "compile/trace", "compile/lower", "compile/backend"]
    for iv in compiled:
        assert iv.parent_id == dispatch.span_id
        assert "scaled_sum" in iv.attrs["fun_name"]
        assert dispatch.t0 <= iv.t0 <= iv.t1 <= dispatch.t1 + 1e-3
        assert iv.thread == threading.get_ident()
    assert compiled[2].attrs["cache_hit"] is False
    assert "saved_s" not in compiled[2].attrs
    assert dispatch.attrs == {"step": 7}


def test_a_warm_pass_reads_from_the_cache_and_the_counts_agree():
    compile_cache.enable()
    c = float(int.from_bytes(os.urandom(4), "little"))
    x = jnp.ones((8, 8), jnp.float32)
    jax.jit(fresh_function(c))(x).block_until_ready()   # populates the cache
    compile_cache.reset_stats()
    t = time.perf_counter()
    jax.jit(fresh_function(c))(x).block_until_ready()   # a process-like pass
    stats = compile_cache.cache_stats()
    backends = since(t, "compile/backend")
    (read,) = since(t, "compile/cache_read")
    (backend,) = backends
    assert backend.attrs["cache_hit"] is True
    assert isinstance(backend.attrs["saved_s"], float)
    assert backend.t0 <= read.t0 <= read.t1 <= backend.t1
    fresh = [iv for iv in backends if not iv.attrs["cache_hit"]]
    assert len(fresh) == stats["misses"] == 0
    assert stats["hits"] == stats["requests"] == 1
    # And a miss after it is counted by both again.
    jax.jit(fresh_function())(x).block_until_ready()
    fresh = [iv for iv in since(t, "compile/backend")
             if not iv.attrs["cache_hit"]]
    assert len(fresh) == compile_cache.cache_stats()["misses"] == 1


@pytest.mark.parametrize("event,duration,kw,recorded", [
    ("/jax/core/compile/some_later_duration", 0.5, {"fun_name": "f"}, None),
    (BACKEND, 0.5, {}, {"cache_hit": False}),
    (LOWER, 0.5, {"fun_name": 7, "more": "x"}, {"fun_name": "7"}),
    (TRACE, "soon", {"fun_name": "f"}, None),
    (READ, None, {}, None),
], ids=["unknown_event", "no_fun_name", "odd_metadata", "odd_duration",
        "no_duration"])
def test_listener_records_or_skips_and_never_raises(event, duration, kw,
                                                    recorded):
    t = time.perf_counter()
    compile_cache._on_duration(event, duration, **kw)
    got = ended_since(t, "compile/")
    if recorded is None:
        assert got == []
    else:
        (iv,) = got
        assert iv.attrs == recorded and iv.dur_s == pytest.approx(0.5)
        assert iv.t1 <= time.perf_counter()


def test_inner_traces_and_lowerings_fold_into_the_outer_one():
    """What jax announces while another trace or lowering is open on the
    thread is inside that one's duration: no interval of its own. A
    backend compile is always kept."""
    t = time.perf_counter()
    compile_cache._on_start(TRACE)
    for _ in range(3):
        compile_cache._on_start(TRACE)
        compile_cache._on_duration(TRACE, 0.001, fun_name="multiply")
    compile_cache._on_start(BACKEND)
    compile_cache._on_duration(BACKEND, 0.002, fun_name="jit(eager)")
    compile_cache._on_duration(TRACE, 0.01, fun_name="step")
    # A jax that announces no start: every duration is outermost.
    compile_cache._on_duration(LOWER, 0.02, fun_name="jit(step)")
    got = ended_since(t, "compile/")
    assert [(iv.name, iv.attrs["fun_name"]) for iv in got] == [
        ("compile/backend", "jit(eager)"), ("compile/trace", "step"),
        ("compile/lower", "jit(step)")]


def test_enable_twice_registers_one_listener():
    compile_cache.enable()
    compile_cache.enable()
    assert monitoring.get_event_duration_listeners().count(
        compile_cache._on_duration) == 1
    assert monitoring.get_event_listeners().count(
        compile_cache._on_event) == 1
    assert monitoring.get_scalar_listeners().count(
        compile_cache._on_start) == 1


def test_listener_cost_is_bounded_in_calls_and_bytes():
    """What the listeners cost is held by what does not depend on the
    machine's load: the Python and C calls one event makes, and the bytes
    it leaves (its record in the ring). At some 0.1 us a call an event
    costs microseconds, and a process sees a few hundred."""
    n = 500
    calls = 0

    def count(frame, event, arg):
        nonlocal calls
        if event in ("call", "c_call"):
            calls += 1

    def events():
        for k in range(n):
            compile_cache._on_start(BACKEND)
            compile_cache._on_event(compile_cache._REQUEST_EVENT)
            compile_cache._on_duration(BACKEND, 0.001, fun_name="jit(f)")
            compile_cache._on_duration("/jax/other", 0.001)

    events()                                   # warm: attribute caches
    sys.setprofile(count)
    try:
        events()
    finally:
        sys.setprofile(None)
    per_compile = calls / n
    tracemalloc.start()
    try:
        before = tracemalloc.get_traced_memory()[0]
        events()
        grown = (tracemalloc.get_traced_memory()[0] - before) / n
    finally:
        tracemalloc.stop()
    print(f"one compile's four events: {per_compile:.1f} calls, "
          f"{grown:.0f} bytes kept")
    assert per_compile <= 40
    assert grown <= 1024
