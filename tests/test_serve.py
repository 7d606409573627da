"""Serving runtime tests (ISSUE 12): the AOT micro-batched predict
engine, the hot-reload seam, and the sentinel-gated bench_serve ladder.

The load-bearing contracts:

- **zero compiles on the request path** — after ``warmup()`` the
  engine never issues another compile request (asserted via the PR-1
  compile-cache stats, not wall-clock);
- **coalescer exactness** — every submitted request is answered
  exactly once, padding never leaks across requests, and the latency
  budget bounds the coalescing wait;
- **the reload seam** — a failed reload (injected ``serve_reload``
  fault, corrupt chain tip, SIGKILL mid-reload in a subprocess)
  degrades to the old generation and converges on a later poll; the
  read-only :class:`ChainFollower` NEVER mutates the trainer's chain;
- **serving invariants** — :func:`chaos.audit_serve_events` holds
  seeded serving fault schedules (``serve_schedule``) to no-torn-swap
  / bounded-staleness / rc discipline;
- **bench_serve --smoke** — the bounded CPU ladder measures p50/p99 +
  QPS through the bucketed path, lands ``serve_bench`` ledger records,
  and promotes a serving headline through the keep-best gate.

The ``serve_request`` watchdog phase (deadline = SLO) is armed and
overrun here, which also satisfies the lint's phase-coverage rule.
"""

import json
import os
import subprocess
import sys
import threading
import time
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from fm_spark_tpu import models, obs
from fm_spark_tpu.checkpoint import ChainFollower, Checkpointer
from fm_spark_tpu.resilience import chaos, faults, watchdog
from fm_spark_tpu.resilience.watchdog import HangDetected
from fm_spark_tpu.serve import DEFAULT_BUCKETS, PredictEngine, ReloadFollower
from fm_spark_tpu.utils.logging import EventLog, read_events

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    monkeypatch.delenv(faults.ENV_PLAN, raising=False)
    monkeypatch.delenv(faults.ENV_STATE, raising=False)
    monkeypatch.delenv(watchdog.ENV_SPEC, raising=False)
    faults.clear()
    watchdog.clear()
    yield
    faults.clear()
    watchdog.clear()


def _spec():
    return models.FieldFMSpec(num_features=4 * 64, rank=4,
                              num_fields=4, bucket=64, init_std=0.1)


def _params(spec, scale: float = 1.0):
    p = spec.init(jax.random.key(0))
    if scale != 1.0:
        p = jax.tree_util.tree_map(lambda a: a * scale, p)
    return p


def _batch(spec, n, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, spec.bucket, (n, spec.num_fields)).astype(
        np.int32)
    vals = rng.random((n, spec.num_fields)).astype(np.float32)
    return ids, vals


def _direct(spec, params, ids, vals):
    return np.asarray(spec.predict(params, jax.numpy.asarray(ids),
                                   jax.numpy.asarray(vals)))


def _engine(spec, params, buckets=(1, 4, 16), budget_ms=50.0, **kw):
    eng = PredictEngine(spec, params, buckets=buckets,
                        latency_budget_ms=budget_ms, **kw)
    eng.warmup()
    return eng


def _counter(name):
    return obs.registry().counter(name).value


# NOTE: every test that pins cold-vs-warm compile-cache behaviour runs
# it in a SUBPROCESS with a cache directory of its own — the same policy
# (and reason) as tests/test_compile_cache: in-process, jit's dispatch
# cache would mask the persistent cache, and the session's shared cache
# (tests/conftest.py) is warm from whatever ran before.


# ------------------------------------------------------------- the engine


def test_score_matches_direct_predict_bitwise():
    """The offline path: bucketed AOT scoring (including the padding a
    non-bucket row count takes) is BIT-identical to the eager
    ``spec.predict`` — the cli-predict routing contract."""
    spec = _spec()
    params = _params(spec)
    eng = _engine(spec, params, buckets=(16,))
    try:
        for n in (1, 7, 16):  # full pad, partial pad, exact bucket
            ids, vals = _batch(spec, n, seed=n)
            assert np.array_equal(eng.score(ids, vals),
                                  _direct(spec, params, ids, vals))
    finally:
        eng.close()


def test_predict_chunks_wide_requests_and_preserves_order():
    spec = _spec()
    params = _params(spec)
    eng = _engine(spec, params, buckets=(1, 4, 16), budget_ms=1.0)
    try:
        ids, vals = _batch(spec, 40)  # 16 + 16 + 8 internal chunks
        assert np.array_equal(eng.predict(ids, vals),
                              _direct(spec, params, ids, vals))
    finally:
        eng.close()


def test_engine_rejects_fresh_shapes_and_oversize_submits():
    spec = _spec()
    params = _params(spec)
    eng = _engine(spec, params, buckets=(1, 4))
    try:
        ids, vals = _batch(spec, 2)
        with pytest.raises(ValueError, match="fresh shape"):
            eng.score(ids[:, :2], vals[:, :2])  # wrong width
        with pytest.raises(ValueError, match="bucket-max"):
            eng.submit(*_batch(spec, 8))  # > largest bucket
        with pytest.raises(ValueError, match="empty"):
            eng.score(ids[:0], vals[:0])
    finally:
        eng.close()


def test_coalescer_burst_answers_every_request_exactly_once():
    """Burst arrival: N distinct single-row requests offered
    concurrently are answered exactly once each with THEIR row's score
    (padding/coalescing never leaks across requests), in fewer
    micro-batches than requests."""
    spec = _spec()
    params = _params(spec)
    eng = _engine(spec, params, buckets=(1, 4, 16), budget_ms=100.0)
    try:
        n = 40
        ids, vals = _batch(spec, n)
        golden = _direct(spec, params, ids, vals)
        b0 = _counter("serve.batches_total")
        futures = [eng.submit(ids[i:i + 1], vals[i:i + 1])
                   for i in range(n)]
        results = [f.result(30) for f in futures]
        for i, r in enumerate(results):
            assert r.shape == (1,)
            assert np.array_equal(r, golden[i:i + 1]), i
        batches = _counter("serve.batches_total") - b0
        assert batches < n, (
            f"{batches} batches for {n} burst requests — "
            "the coalescer never coalesced")
    finally:
        eng.close()


def test_coalescer_trickle_respects_latency_budget():
    """A lone request is held at most ~the latency budget waiting for
    peers, then dispatched alone — the explicit latency/batching
    trade, bounded."""
    spec = _spec()
    params = _params(spec)
    budget_s = 0.05
    eng = _engine(spec, params, buckets=(1, 16),
                  budget_ms=budget_s * 1e3)
    try:
        ids, vals = _batch(spec, 1)
        eng.predict(ids, vals)  # first dispatch: queue drains
        t0 = time.perf_counter()
        out = eng.predict(ids, vals)
        elapsed = time.perf_counter() - t0
        assert out.shape == (1,)
        # Generous upper margin for CI jitter; the point is "bounded
        # by the budget + execute", not "a 2s stall".
        assert elapsed < budget_s + 1.0, elapsed
    finally:
        eng.close()


_ZERO_COMPILE_CHILD = """
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["FM_SPARK_OBS_DIR"] = "none"
import numpy as np
import jax
from fm_spark_tpu import models
from fm_spark_tpu.serve import PredictEngine
from fm_spark_tpu.utils import compile_cache

compile_cache.enable()
spec = models.FieldFMSpec(num_features=4 * 64, rank=4, num_fields=4,
                          bucket=64, init_std=0.1)
params = spec.init(jax.random.key(0))
p2 = jax.tree_util.tree_map(lambda a: a * 2.0, params)
eng = PredictEngine(spec, params, buckets=(1, 4),
                    latency_budget_ms=1.0)
warm = eng.warmup()
after_warmup = compile_cache.cache_stats()
rng = np.random.default_rng(0)
ids = rng.integers(0, 64, (3, 4)).astype(np.int32)
vals = rng.random((3, 4)).astype(np.float32)
eng.score(ids, vals)
eng.predict(ids, vals)
eng.swap_generation(p2, step=1)
eng.predict(ids, vals)   # post-swap: same executables
eng.close()
stats = compile_cache.cache_stats()
print(json.dumps({
    "fresh_at_warmup": warm["fresh_compiles"],
    "requests_after_warmup": stats["requests"]
                             - after_warmup["requests"],
}))
"""


def test_request_path_zero_compile_requests_after_warmup(tmp_path):
    """The AOT contract: warmup compiles (cold) or deserializes (warm
    process) every bucket executable; afterwards NO code path issues a
    compile request — not score, not the coalescer, not a post-swap
    dispatch. Cross-process, via the persistent cache, exactly like
    the train-side warm-start tests."""
    def run():
        out = subprocess.run(
            [sys.executable, "-c", _ZERO_COMPILE_CHILD],
            capture_output=True, text=True, timeout=240, cwd=REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cc")})
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    cold = run()
    assert cold["fresh_at_warmup"] > 0   # cold cache: real compiles
    assert cold["requests_after_warmup"] == 0, (
        "the request path consulted the compiler after warmup")
    warm = run()                         # new process, same cache dir
    assert warm["fresh_at_warmup"] == 0, warm
    assert warm["requests_after_warmup"] == 0


def test_serve_request_watchdog_converts_slow_batch_to_hang_detected():
    """The SLO watchdog: the ``serve_request`` phase armed at a tight
    deadline turns a slow micro-batch into a structured HangDetected
    delivered to every coalesced caller — and the worker survives to
    serve the next request."""
    spec = _spec()
    params = _params(spec)
    eng = _engine(spec, params, buckets=(1,), budget_ms=0.0)
    try:
        real = eng._compiled[1]

        def slow(p, i, v):
            time.sleep(0.08)
            return real(p, i, v)

        eng._compiled[1] = slow
        watchdog.configure({"serve_request": 0.01}, action="raise")
        ids, vals = _batch(spec, 1)
        fut = eng.submit(ids, vals)
        with pytest.raises(HangDetected, match="serve_request"):
            fut.result(30)
        assert _counter("serve.batch_failures_total") >= 1
        # The worker thread survived the failed batch:
        watchdog.clear()
        eng._compiled[1] = real
        assert eng.predict(ids, vals).shape == (1,)
    finally:
        eng.close()


# --------------------------------------------------------- the reload seam


def test_follower_hot_swap_serves_new_generation(tmp_path):
    spec = _spec()
    params = _params(spec)
    p2 = _params(spec, scale=2.0)
    ck = Checkpointer(str(tmp_path / "chain"), save_every=1,
                      async_save=False)
    ck.save(7, p2, {}, None, force=True)
    ck.close()
    eng = _engine(spec, params, buckets=(4,), budget_ms=0.0)
    fol = ReloadFollower(eng, str(tmp_path / "chain"), poll_s=0.05,
                         opt_state_example={})
    try:
        assert fol.poll_once() == "swapped"
        assert eng.generation().step == 7
        ids, vals = _batch(spec, 4)
        assert np.array_equal(eng.score(ids, vals),
                              _direct(spec, p2, ids, vals))
        assert fol.poll_once() == "fresh"
        assert int(obs.registry().gauge(
            "serve/staleness_steps").value or 0) == 0
    finally:
        fol.stop()
        eng.close()


def test_reload_fault_degrades_then_converges(tmp_path):
    """The degraded-serving drill: an injected ``serve_reload`` fault
    fails the reload attempt — the OLD generation keeps serving, the
    failure is journaled, the degraded gauge rises — and the next
    poll (fault exhausted) converges to the new generation."""
    spec = _spec()
    params = _params(spec)
    p2 = _params(spec, scale=3.0)
    journal_path = tmp_path / "serve_health.jsonl"
    ck = Checkpointer(str(tmp_path / "chain"), save_every=1,
                      async_save=False)
    ck.save(5, p2, {}, None, force=True)
    ck.close()
    eng = _engine(spec, params, buckets=(4,), budget_ms=0.0)
    fol = ReloadFollower(eng, str(tmp_path / "chain"), poll_s=0.05,
                         journal=EventLog(str(journal_path)),
                         opt_state_example={})
    try:
        faults.activate("serve_reload@1=error")
        ids, vals = _batch(spec, 4)
        golden_old = eng.score(ids, vals)
        assert fol.poll_once() == "failed"
        # Old generation keeps serving, bit-identically:
        assert np.array_equal(eng.score(ids, vals), golden_old)
        assert fol.degraded
        events = read_events(str(journal_path))
        assert any(e["event"] == "reload_failed" for e in events)
        # Next poll: the fault plan is exhausted; serving converges.
        assert fol.poll_once() == "swapped"
        assert eng.generation().step == 5
        assert not fol.degraded
        assert np.array_equal(eng.score(ids, vals),
                              _direct(spec, p2, ids, vals))
    finally:
        fol.stop()
        eng.close()


def test_follower_refuses_demoted_tip_and_converges_forward(tmp_path):
    """ISSUE 13: a generation judged bad AFTER publish (drift verdict
    → ``demote``: durable tombstone, ``last_good`` republished) must
    never be hot-loaded — the follower reports the quarantined tip as
    a degraded poll and keeps serving the prior generation, then
    converges FORWARD when a newer good save lands."""
    spec = _spec()
    chain = tmp_path / "chain"
    journal_path = tmp_path / "serve_health.jsonl"
    ck = Checkpointer(str(chain), save_every=1, async_save=False)
    ck.save(5, _params(spec, scale=2.0), {}, None, force=True)
    ck.save(9, _params(spec, scale=3.0), {}, None, force=True)
    ck.wait()
    # The drift sentry demotes the freshly published tip before any
    # follower loads it: tombstone durable, pointer republished.
    assert ck.demote(9, reason="drift verdict") is True
    assert ck.last_good_step() == 5
    eng = _engine(spec, _params(spec), buckets=(4,), budget_ms=0.0)
    fol = ReloadFollower(eng, str(chain), poll_s=0.05,
                         journal=EventLog(str(journal_path)),
                         opt_state_example={})
    try:
        ids, vals = _batch(spec, 4)
        # Follower restores the PRE-drift generation, never 9:
        assert fol.poll_once() == "swapped"
        assert eng.generation().step == 5
        assert np.array_equal(
            eng.score(ids, vals),
            _direct(spec, _params(spec, scale=2.0), ids, vals))
        events = read_events(str(journal_path))
        assert any(e["event"] == "checkpoint_demoted_skipped"
                   and e["step"] == 9 for e in events)
        # A newer good save converges serving forward past the veto.
        ck.save(12, _params(spec, scale=4.0), {}, None, force=True)
        ck.wait()
        assert fol.poll_once() == "swapped"
        assert eng.generation().step == 12
        # The artifact-only auditor proves no tombstoned generation
        # was ever installed.
        events = read_events(str(journal_path))
        assert chaos.audit_serve_events(
            events, tombstoned_steps=ck.tombstoned_steps()) == []
    finally:
        fol.stop()
        eng.close()
        ck.close()


def test_demotion_racing_reload_is_refused(tmp_path):
    """The nastiest interleaving (ISSUE 13): the demotion lands AFTER
    the follower restored the new generation but BEFORE the swap — the
    tombstone re-check at the swap boundary must win the race."""
    spec = _spec()
    chain = tmp_path / "chain"
    ck = Checkpointer(str(chain), save_every=1, async_save=False)
    ck.save(5, _params(spec, scale=2.0), {}, None, force=True)
    ck.save(9, _params(spec, scale=3.0), {}, None, force=True)
    ck.wait()
    journal_path = tmp_path / "serve_health.jsonl"
    eng = _engine(spec, _params(spec, scale=2.0), buckets=(4,),
                  budget_ms=0.0)
    eng.swap_generation(_params(spec, scale=2.0), 5)
    fol = ReloadFollower(eng, str(chain), poll_s=0.05,
                         journal=EventLog(str(journal_path)),
                         opt_state_example={})
    orig_restore = fol.chain.restore

    def restore_then_demote(*a, **kw):
        out = orig_restore(*a, **kw)
        ck.demote(9, reason="drift verdict racing the reload")
        return out

    fol.chain.restore = restore_then_demote
    try:
        assert fol.poll_once() == "demoted"
        assert eng.generation().step == 5  # never installed 9
        assert fol.degraded
        events = read_events(str(journal_path))
        assert any(e["event"] == "reload_failed"
                   and "demoted mid-reload" in str(e.get("error"))
                   for e in events)
        assert chaos.audit_serve_events(
            events, tombstoned_steps={9}) == []
    finally:
        fol.chain.restore = orig_restore
        fol.stop()
        eng.close()
        ck.close()


def test_audit_flags_swap_to_tombstoned_generation():
    """The no_tombstoned_generation invariant is non-vacuous: a
    journal showing a swap INTO a demoted step must fail the audit."""
    events = [{"event": "serve_swap", "step": 9, "gen_id": 1,
               "from_step": 5}]
    v = chaos.audit_serve_events(events, tombstoned_steps={9})
    assert [x["invariant"] for x in v] == ["no_tombstoned_generation"]
    assert chaos.audit_serve_events(events, tombstoned_steps={7}) == []


def test_follower_torn_last_good_is_retried_not_raised(tmp_path):
    """ISSUE 13 satellite: a torn/empty ``last_good.json`` read (a
    copied or damaged chain — an atomic-replace reader never sees a
    partial write, but the file CAN be empty on disk) must surface as
    'nothing published yet' and heal on the next poll, never raise."""
    spec = _spec()
    chain = tmp_path / "chain"
    ck = Checkpointer(str(chain), save_every=1, async_save=False)
    ck.save(3, _params(spec, scale=2.0), {}, None, force=True)
    ck.wait()
    # Tear the pointer: empty file, then junk bytes.
    lg = chain / "last_good.json"
    eng = _engine(spec, _params(spec), buckets=(4,), budget_ms=0.0)
    fol = ReloadFollower(eng, str(chain), poll_s=0.05,
                         opt_state_example={})
    try:
        for torn in (b"", b'{"st'):
            lg.write_bytes(torn)
            assert fol.chain.last_good_step() is None
            assert fol.poll_once() == "no_checkpoint"
        # The trainer's next atomic replace heals the pointer; the
        # very next poll serves it.
        lg.write_bytes(json.dumps({"step": 3}).encode())
        assert fol.poll_once() == "swapped"
        assert eng.generation().step == 3
    finally:
        fol.stop()
        eng.close()
        ck.close()


def _flip_step_bytes(chain_dir, step):
    import glob

    files = [p for p in glob.glob(
        os.path.join(str(chain_dir), str(step), "state", "**", "d", "*"),
        recursive=True) if os.path.isfile(p)]
    assert files, f"no array data files under step {step}"
    for p in files:
        with open(p, "r+b") as f:
            data = bytearray(f.read())
            for i in range(min(64, len(data))):
                data[i] ^= 0xFF
            f.seek(0)
            f.write(data)


def test_follower_walks_back_past_corrupt_tip(tmp_path):
    """Torn-``last_good`` walk-back through the follower: the pointer
    names a step whose bytes rotted — the follower restores the
    next-older VERIFIED step instead (first poll), and once the served
    generation is at the verified tip, further polls report the chain
    degraded rather than re-serving stale state."""
    spec = _spec()
    chain = tmp_path / "chain"
    ck = Checkpointer(str(chain), save_every=1, async_save=False)
    ck.save(2, _params(spec, scale=2.0), {}, None, force=True)
    ck.save(4, _params(spec, scale=4.0), {}, None, force=True)
    ck.close()
    _flip_step_bytes(chain, 4)  # last_good still points at 4
    eng = _engine(spec, _params(spec), buckets=(4,), budget_ms=0.0)
    journal_path = tmp_path / "serve_health.jsonl"
    fol = ReloadFollower(eng, str(chain), poll_s=0.05,
                         journal=EventLog(str(journal_path)),
                         opt_state_example={})
    try:
        assert fol.poll_once() == "swapped"
        assert eng.generation().step == 2  # walked back past 4
        events = read_events(str(journal_path))
        # The rotted tip is journaled either as a checksum mismatch or
        # as unreadable bytes (the flip can take out orbax's own
        # metadata before the checksum pass ever runs).
        assert any(e["event"] in ("checkpoint_corrupt",
                                  "checkpoint_unreadable")
                   and e["step"] == 4 for e in events)
        # Serving is as fresh as the VERIFIED chain allows; the torn
        # tip shows up as a degraded poll, never a torn generation.
        assert fol.poll_once() == "stale_chain"
        assert fol.degraded
    finally:
        fol.stop()
        eng.close()


def test_chain_follower_never_mutates_the_chain(tmp_path):
    """The read-only satellite: a follower walk (including a failed
    verification) leaves every byte of the chain directory exactly as
    the trainer wrote it — no manifest flush, no pointer write, no
    orbax metadata."""
    import hashlib

    spec = _spec()
    chain = tmp_path / "chain"
    ck = Checkpointer(str(chain), save_every=1, async_save=False)
    ck.save(1, _params(spec), {}, None, force=True)
    ck.save(3, _params(spec, scale=2.0), {}, None, force=True)
    ck.close()
    _flip_step_bytes(chain, 3)  # force a walk-back during the follow

    def snapshot():
        out = {}
        for root, _dirs, files in os.walk(chain):
            for f in files:
                p = os.path.join(root, f)
                with open(p, "rb") as fh:
                    out[os.path.relpath(p, chain)] = hashlib.sha256(
                        fh.read()).hexdigest()
        return out

    before = snapshot()
    fol = ChainFollower(str(chain))
    assert fol.last_good_step() == 3
    restored = fol.restore(_params(spec), {})
    fol.close()
    assert restored["step"] == 1
    assert snapshot() == before, (
        "the read-only follower changed bytes in the trainer's chain")


_SIGKILL_CHILD_TIMEOUT = 240


def test_sigkill_during_reload_drill_subprocess(tmp_path):
    """SIGKILL-mid-reload: a serving process dies (injected
    ``serve_reload`` exit — the kill window is inside the reload
    attempt, before any swap) with the expected rc; the chain is
    untouched, and the NEXT serving process converges to the newest
    generation on startup. rc discipline + convergence =
    :func:`chaos.audit_serve_events`'s contract, subprocess edition."""
    spec = _spec()
    chain = tmp_path / "chain"
    model_dir = tmp_path / "model"
    models.save_model(str(model_dir), spec, _params(spec))
    ck = Checkpointer(str(chain), save_every=1, async_save=False)
    ck.save(1, _params(spec, scale=2.0), {}, None, force=True)
    ck.wait()

    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "FM_SPARK_OBS_DIR": "none",
           "FM_SPARK_FAULTS": "serve_reload@1=exit:9"}
    argv = [sys.executable, "-m", "fm_spark_tpu.cli", "serve",
            "--model", str(model_dir), "--config", "criteo1tb_fm_r64",
            "--checkpoint-dir", str(chain), "--synthetic", "64",
            "--batch-size", "4", "--buckets", "1,4",
            "--reload-poll-s", "0.1", "--repeat", "1000",
            "--latency-budget-ms", "0"]
    proc = subprocess.Popen(argv, stdout=subprocess.PIPE, text=True,
                            cwd=REPO, env=env,
                            stderr=subprocess.DEVNULL)
    try:
        # Wait until the child is actually serving (its second line;
        # the first names the device), THEN publish the new generation
        # its poll will die reloading.
        assert '"device"' in proc.stdout.readline()
        line = proc.stdout.readline()
        assert '"serving": true' in line, line
        ck.save(2, _params(spec, scale=3.0), {}, None, force=True)
        ck.wait()
        rc = proc.wait(timeout=_SIGKILL_CHILD_TIMEOUT)
    finally:
        proc.kill()
        ck.close()
    assert rc == 9, f"expected the injected exit rc, got {rc}"
    assert chaos.audit_serve_events([], rc=rc, allowed_rcs=(9,)) == []

    # The chain survived the kill untouched (the follower died inside
    # a READ), and the next serving process's first poll converges to
    # the generation the dead one never reached.
    eng = _engine(spec, _params(spec), buckets=(1, 4), budget_ms=0.0)
    fol = ReloadFollower(eng, str(chain), poll_s=0.05,
                         opt_state_example={})
    try:
        assert fol.poll_once() == "swapped"
        assert eng.generation().step == 2
        assert int(obs.registry().gauge(
            "serve/staleness_steps").value or 0) == 0
    finally:
        fol.stop()
        eng.close()


# --------------------------------------------------- serving chaos drills


def test_serve_schedules_deterministic_and_cover_serving_faults():
    seen_points = set()
    for seed in range(30):
        a = chaos.serve_schedule(seed)
        b = chaos.serve_schedule(seed)
        assert a == b, "a schedule must be a pure function of its seed"
        assert a.scenario.startswith("serve_")
        for rule in a.rules:
            seen_points.add(rule.split("@")[0])
    # The serving campaign composes BOTH halves of the tentpole drill:
    # trainer-side commit faults and reload faults.
    assert {"serve_reload", "ckpt_commit"} <= seen_points


def test_audit_serve_events_invariants():
    ok = [{"kind": "serve_swap", "step": 3, "gen_id": 1},
          {"kind": "serve_swap", "step": 5, "gen_id": 2}]
    assert chaos.audit_serve_events(ok, final_staleness=0, rc=0) == []
    # One swap seen via two transports (journal + flight mirror) is
    # NOT a torn/duplicated swap.
    mirrored = [{"kind": "serve_swap", "step": 3, "gen_id": 1,
                 "from_step": 0},
                {"event": "serve_swap", "step": 3, "gen_id": 1,
                 "from_step": 0, "ts": 1.0},
                {"kind": "serve_swap", "step": 5, "gen_id": 2,
                 "from_step": 3}]
    assert chaos.audit_serve_events(mirrored) == []
    torn = chaos.audit_serve_events(
        [{"kind": "serve_swap", "step": 5, "gen_id": 1},
         {"kind": "serve_swap", "step": 4, "gen_id": 2}])
    assert any(v["invariant"] == "no_torn_swap" for v in torn)
    skipped = chaos.audit_serve_events(
        [{"kind": "serve_swap", "step": 3, "gen_id": 1},
         {"kind": "serve_swap", "step": 5, "gen_id": 3}])
    assert any(v["invariant"] == "no_torn_swap" for v in skipped)
    stale = chaos.audit_serve_events([], final_staleness=4,
                                     staleness_bound=0)
    assert any(v["invariant"] == "staleness_bounded" for v in stale)
    bad_rc = chaos.audit_serve_events([], rc=1, allowed_rcs=(0, 87))
    assert any(v["invariant"] == "rc_discipline" for v in bad_rc)
    journaless = chaos.audit_serve_events(
        [{"kind": "reload_failed", "error": "x"}])
    assert any(v["invariant"] == "degraded_journaled"
               for v in journaless)


def test_seeded_serve_drill_campaign_green(tmp_path):
    """A bounded in-process serving chaos campaign: seeded schedules
    (commit faults + reload faults) against the production
    engine/follower/checkpointer stack. Every response under load must
    be generation-uniform, and the run must end green under
    :func:`chaos.audit_serve_events` — converged, no torn swap."""
    spec = _spec()
    ids, vals = _batch(spec, 4)
    ids[:] = ids[:1]  # identical rows: a mixed-generation response
    vals[:] = 1.0     # would be visibly non-uniform

    for seed in (1, 2, 5, 9):
        sched = chaos.serve_schedule(seed)
        workdir = tmp_path / f"s{seed}"
        workdir.mkdir()
        journal_path = workdir / "serve_health.jsonl"
        chain = workdir / "chain"
        ck = Checkpointer(str(chain), save_every=1, async_save=False)
        ck.save(1, _params(spec, scale=2.0), {}, None, force=True)
        ck.wait()
        journal = EventLog(str(journal_path))
        eng = _engine(spec, _params(spec), buckets=(4,), budget_ms=0.0,
                      journal=journal)
        fol = ReloadFollower(eng, str(chain), poll_s=0.01,
                             journal=journal, opt_state_example={})
        torn = 0
        try:
            assert fol.poll_once() == "swapped"
            faults.activate(sched.plan)
            for k in range(2, 5):  # the trainer keeps publishing
                try:
                    ck.save(k, _params(spec, scale=float(k + 1)), {},
                            None, force=True)
                    ck.wait()
                except faults.FaultInjected:
                    pass  # the trainer's problem; serving must ride on
                for _ in range(3):
                    out = eng.predict(ids, vals)
                    if not np.all(out == out[0]):
                        torn += 1
                fol.poll_once()
            faults.clear()
            # Recovery: polls with no plan active must converge (the
            # chain self-heals its pending manifests at the next save
            # boundary; give it one).
            ck.save(6, _params(spec, scale=9.0), {}, None, force=True)
            ck.wait()
            deadline = time.monotonic() + 10
            while (fol.poll_once() != "fresh"
                   and time.monotonic() < deadline):
                time.sleep(0.01)
        finally:
            faults.clear()
            fol.stop()
            eng.close()
            ck.close()
        assert torn == 0, f"seed {seed}: mixed-generation response"
        final_staleness = int(obs.registry().gauge(
            "serve/staleness_steps").value or 0)
        events = read_events(str(journal_path))
        assert any(e["event"] == "serve_swap" for e in events), (
            "the drill never swapped — it exercised nothing")
        violations = chaos.audit_serve_events(
            events, final_staleness=final_staleness,
            staleness_bound=0, rc=0)
        assert violations == [], f"seed {seed}: {violations}"


# ------------------------------------------------------------ bench_serve


def _run_bench_serve(tmp_path, *extra):
    """One bench_serve smoke in a SUBPROCESS (it arms the persistent
    compile cache — see the module note — and subprocesses are what
    make the cold-vs-warm pair a real cross-process measurement)."""
    out_path = tmp_path / "serve_result.json"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench_serve.py"),
         "--smoke", "--art-dir", str(tmp_path / "art"),
         "--measured-path", str(tmp_path / "MEASURED.json"),
         "--requests", "12", "--out", str(out_path), *extra],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cc"),
             "FM_SPARK_OBS_DIR": "none"})
    assert out.returncode == 0, out.stderr[-2000:]
    with open(out_path) as f:
        return out.returncode, json.load(f)


def test_bench_serve_smoke_cold_then_warm(tmp_path):
    """The tier-1 serving leg: the bounded CPU smoke measures p50/p99
    + QPS through the bucketed AOT path, asserts zero fresh compiles
    after warmup, completes the reload-under-load drill with no torn
    swap and bounded staleness, lands ``serve_bench`` ledger records
    with full fingerprints, and seeds the MEASURED.json serving
    headline through the keep-best gate. A second (warm) process-run
    deserializes every executable: warm_start flips true."""
    from fm_spark_tpu.obs import PerfLedger

    rc, result = _run_bench_serve(tmp_path)
    assert rc == 0
    assert result["fresh_compiles_after_warmup"] == 0
    for rung in result["rungs"]:
        assert rung["p50_ms"] > 0 and rung["p99_ms"] >= rung["p50_ms"]
        assert rung["rows_per_sec"] > 0
        assert rung["sentinel"]["verdict"] in (
            "insufficient_history", "improved", "flat")
    drill = result["reload_drill"]
    assert drill["violations"] == []
    assert drill["torn_responses"] == 0
    assert drill["swaps"] >= 1
    assert drill["final_staleness_steps"] == 0
    # Ledger: one serve_bench record per rung, full provenance.
    ledger = PerfLedger(str(tmp_path / "art" / "obs" / "ledger.jsonl"))
    recs = ledger.records(kind="serve_bench", run_id=result["run_id"])
    assert len(recs) == len(result["rungs"])
    assert all(r["fingerprint"]["key"] and r["p99_ms"] is not None
               for r in recs)
    # MEASURED: the headline seeded through the gate.
    with open(tmp_path / "MEASURED.json") as f:
        measured = json.load(f)
    assert result["measured_updated"]
    assert (measured["serving"]["rate_samples_per_sec_per_chip"]
            == result["headline_rows_per_sec_per_chip"])
    assert "bench_serve.py" in measured["serving"]["source"]

    rc2, result2 = _run_bench_serve(tmp_path, "--skip-reload-drill")
    assert rc2 == 0
    assert result2["warm_start"], (
        "second run should deserialize every bucket executable from "
        "the persistent cache")
    assert result2["fresh_compiles_at_warmup"] == 0


def test_bench_serve_promote_refuses_invariant_violating_run(tmp_path):
    """A ladder whose own invariants failed (fresh compiles after
    warmup / reload-drill violation) must keep its rungs out of
    MEASURED.json no matter how good the number looks — the PERF.md
    round-16 rule. (Importing bench_serve is safe: the compile cache
    is only armed inside main().)"""
    import importlib.util

    spec_ = importlib.util.spec_from_file_location(
        "bench_serve_promote_test", os.path.join(REPO, "bench_serve.py"))
    mod = importlib.util.module_from_spec(spec_)
    sys.modules[spec_.name] = mod
    spec_.loader.exec_module(mod)
    args = SimpleNamespace(measured_path=str(tmp_path / "MEASURED.json"))
    headline = {"variant": "serve/x/b512",
                "sentinel": {"verdict": "improved"}}
    ok, reason = mod._promote(headline, 1e9, "cpu", args, run_ok=False)
    assert not ok and "invariants" in reason
    assert not os.path.exists(args.measured_path)
    ok, _ = mod._promote(headline, 1e3, "cpu", args, run_ok=True)
    assert ok and os.path.exists(args.measured_path)


def test_measured_serving_entry_schema(tmp_path):
    """The new optional MEASURED entry round-trips the validator."""
    from fm_spark_tpu.measured import load_measured, update_entry

    path = tmp_path / "MEASURED.json"
    base = json.load(open(os.path.join(REPO, "MEASURED.json")))
    with open(path, "w") as f:
        json.dump(base, f)
    update_entry("serving", rate=1234.5, variant="serve/x/b32",
                 source="bench_serve.py ladder", attachment="cpu",
                 date="2026-08-03", path=str(path))
    data = load_measured(str(path))
    assert data["serving"]["rate_samples_per_sec_per_chip"] == 1234.5


# ------------------------------------------------------------ CLI routing


def test_cli_predict_routes_through_engine_bit_identical(tmp_path):
    """The predict-routing satellite: ``cli predict`` output through
    the bucketed AOT engine is byte-identical to the pre-engine eager
    formula over the same batches."""
    from fm_spark_tpu import cli
    from fm_spark_tpu.data import iterate_once  # noqa: F401 (doc)

    spec = _spec()
    params = _params(spec)
    models.save_model(str(tmp_path / "m"), spec, params)
    out_path = tmp_path / "preds.txt"
    rc = cli.main(["predict", "--model", str(tmp_path / "m"),
                   "--synthetic", "100", "--batch-size", "32",
                   "--out", str(out_path)])
    assert rc == 0
    args = SimpleNamespace(synthetic=100, data=None, config=None,
                           batch_size=32)
    golden = []
    for bids, bvals, _, w in cli._batches_for_model(args, spec):
        preds = _direct(spec, params, bids, bvals)
        golden.extend(f"{float(p):.6g}" for p in preds[w > 0])
    assert out_path.read_text().splitlines() == golden


def test_cli_serve_smoke_from_model(tmp_path, capsys):
    """In-process ``cli serve``: warms up with the default buckets,
    answers a bounded synthetic stream, and emits the summary line
    with latency percentiles and reload accounting."""
    from fm_spark_tpu import cli

    spec = _spec()
    models.save_model(str(tmp_path / "m"), spec, _params(spec))
    rc = cli.main(["serve", "--model", str(tmp_path / "m"),
                   "--synthetic", "64", "--batch-size", "8",
                   "--buckets", "1,8", "--max-requests", "5",
                   "--latency-budget-ms", "0", "--reload-poll-s", "0"])
    assert rc == 0
    lines = capsys.readouterr().out.splitlines()
    summary = next(json.loads(ln)["serve_summary"] for ln in lines
                   if '"serve_summary"' in ln)
    assert summary["served_requests"] == 5
    assert summary["request_ms"]["count"] >= 5
    assert summary["request_ms"]["p99"] is not None
    assert summary["staleness_steps"] == 0
    assert not summary["degraded"]


def test_cli_serve_metrics_port_live_round_trip(tmp_path):
    """ISSUE 14 acceptance: while a ``cli serve`` loop is LIVE,
    ``--metrics-port`` serves valid Prometheus text on /metrics and a
    JSON liveness doc on /healthz — a curl-level HTTP round-trip from
    another process, no touching the daemon."""
    import urllib.request

    spec = _spec()
    models.save_model(str(tmp_path / "m"), spec, _params(spec))
    proc = subprocess.Popen(
        [sys.executable, "-m", "fm_spark_tpu", "serve",
         "--model", str(tmp_path / "m"),
         "--synthetic", "256", "--batch-size", "8",
         "--buckets", "1,8", "--latency-budget-ms", "0",
         "--reload-poll-s", "0", "--repeat", "1000000",
         "--obs-dir", "none", "--metrics-port", "0"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    try:
        port = None
        serving = False
        while True:
            line = proc.stdout.readline()
            if not line:
                break
            try:
                doc = json.loads(line)
            except ValueError:
                continue
            if "metrics_port" in doc:
                port = doc["metrics_port"]
            if doc.get("serving"):
                serving = True
                break
        assert port, "no metrics_port line from cli serve"
        assert serving, proc.stderr.read()[-2000:]

        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=15) as r:
            assert r.status == 200
            assert r.headers["Content-Type"].startswith("text/plain")
            text = r.read().decode()
        # Valid exposition text with the live serving gauges: the
        # engine published its generation before the first request.
        assert "# TYPE fm_spark_serve_generation_step gauge" in text
        assert "fm_spark_serve_generation_step 0" in text

        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=15) as r:
            hz = json.loads(r.read())
        assert hz["status"] == "ok"
        assert hz["generation_step"] == 0
        assert not hz["degraded"]
    finally:
        proc.terminate()
        try:
            proc.wait(30)
        except subprocess.TimeoutExpired:
            proc.kill()


def test_default_buckets_sane():
    assert DEFAULT_BUCKETS == (1, 8, 64, 512)


# ------------------------------------------- what importing the package loads

_IMPORT_PROBE = """
import json, sys
from fm_spark_tpu.serve import PredictEngine
import fm_spark_tpu.serve as serve
heavy = ("orbax.checkpoint", "fm_spark_tpu.checkpoint",
         "fm_spark_tpu.serve.reload")
out = {"loaded_by_the_engine": [m for m in heavy if m in sys.modules],
       "listed": "ReloadFollower" in dir(serve)}
# A scorer that holds a generation (models/rows.py chooses the form)
# and answers a request has loaded nothing of the trainer.
import jax, numpy as np
from fm_spark_tpu import models
spec = models.FieldFMSpec(num_features=4 * 64, num_fields=4, bucket=64,
                          rank=8)
eng = PredictEngine(spec, spec.init(jax.random.key(0)), buckets=(8,))
eng.warmup()
eng.score(np.zeros((3, 4), np.int32), np.ones((3, 4), np.float32))
eng.close()
trainer = ("fm_spark_tpu.sparse", "fm_spark_tpu.train", "optax")
out["trainer_loaded_by_a_serving_engine"] = [
    m for m in trainer if m in sys.modules]
from fm_spark_tpu.serve import ReloadFollower
from fm_spark_tpu.serve.reload import ReloadFollower as there
out["the_class"] = (ReloadFollower is there and isinstance(there, type)
                    and serve.ReloadFollower is there)
out["loaded_by_the_follower"] = [m for m in heavy if m in sys.modules]
out["in_all"] = "ReloadFollower" in serve.__all__
out["all_resolves"] = all(hasattr(serve, name) for name in serve.__all__)
try:
    serve.no_such_name
    out["unknown"] = "no error"
except AttributeError as e:
    out["unknown"] = str(e)
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def import_probe():
    """One fresh interpreter: what ``from fm_spark_tpu.serve import
    PredictEngine`` loads, and what asking for the follower then does."""
    out = subprocess.run(
        [sys.executable, "-c", _IMPORT_PROBE], capture_output=True,
        text=True, timeout=300, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.splitlines()[-1])


@pytest.mark.parametrize("key,want", [
    # A scorer that follows no checkpoint chain never loads the
    # checkpoint library (13-25 s of its start-up on the chip's host).
    ("loaded_by_the_engine", []),
    ("listed", True),
    # ... nor, for choosing the form it holds its tables in, the trainer
    # (until PR 31 serve/tables.py asked fm_spark_tpu.sparse).
    ("trainer_loaded_by_a_serving_engine", []),
    ("the_class", True),
    ("loaded_by_the_follower", ["orbax.checkpoint", "fm_spark_tpu.checkpoint",
                                "fm_spark_tpu.serve.reload"]),
    ("in_all", True),
    ("all_resolves", True),
    ("unknown", "module 'fm_spark_tpu.serve' has no attribute 'no_such_name'"),
])
def test_importing_the_engine_loads_no_checkpoint_library(
        import_probe, key, want):
    assert import_probe[key] == want
