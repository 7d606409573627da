"""Serving front-door + fleet tests (ISSUE 17): deadline-aware
admission, the multi-process replica fleet, seeded traffic replay, and
the fleet chaos auditor.

The load-bearing contracts:

- **shed before the coalescer** — an unpayable request is refused at
  admission (429 + Retry-After) and the backend's ``score`` is NEVER
  called for it; the shed counters the door reports are the sheds the
  clients observed;
- **exactly-once under replica loss** — a ``replica_kill`` fault
  (SIGKILL-equivalent ``os._exit`` mid-request, injected INSIDE the
  replica process) loses zero accepted requests: the fleet's dispatch
  retry answers each on a surviving replica exactly once, the dead
  replica is re-admitted after ``/healthz`` readiness, and
  :func:`chaos.audit_fleet` proves all of it from the tap alone;
- **parent-side dispatch faults** — an injected ``fleet_dispatch``
  error is absorbed by the retry (counted, answered);
- **seeded replay purity** — ``make_schedule`` and ``fleet_schedule``
  are pure functions of their seed, so a failing campaign entry IS
  its repro;
- **concurrent followers converge** — N independent ReloadFollowers
  polling one chain while the trainer advances + demotes all converge
  to the same non-tombstoned tip, and the read-only followers never
  write a byte into the trainer's chain.

The ``frontdoor_accept`` fault point and the ``frontdoor_request``
watchdog phase are armed here, which also satisfies the lint's
registry-coverage rule.
"""

import hashlib
import http.client
import json
import os
import time

import jax
import numpy as np
import pytest

from fm_spark_tpu import models, obs
from fm_spark_tpu.checkpoint import Checkpointer
from fm_spark_tpu.resilience import chaos, faults, watchdog
from fm_spark_tpu.resilience.chaos_audit import audit_fleet
from fm_spark_tpu.serve import (
    AdmissionController,
    FrontDoor,
    LocalBackend,
    PredictEngine,
    ReloadFollower,
    parse_classes,
)
from fm_spark_tpu.serve import loadgen
from fm_spark_tpu.serve.fleet import Fleet
from fm_spark_tpu.utils.logging import EventLog, read_events

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: Re-admission budget after a replica death: respawn + (cached)
#: warmup on a contended CI box. Generous on purpose — the assertion
#: is THAT the replica comes back, not how fast; bench_serve measures.
_READMIT_TIMEOUT_S = 240.0


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


def _post(port: int, doc, path: str = "/predict",
          timeout_s: float = 30.0):
    conn = http.client.HTTPConnection("127.0.0.1", port,
                                      timeout=timeout_s)
    try:
        body = doc if isinstance(doc, (bytes, str)) else json.dumps(doc)
        conn.request("POST", path, body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        payload = resp.read().decode()
        headers = dict(resp.getheaders())
        return resp.status, json.loads(payload or "{}"), headers
    finally:
        conn.close()


def _get(port: int, path: str, timeout_s: float = 10.0):
    conn = http.client.HTTPConnection("127.0.0.1", port,
                                      timeout=timeout_s)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, resp.read().decode()
    finally:
        conn.close()


def _predict_doc(spec, rows: int = 2, *, cls="interactive",
                 deadline_ms=8000.0, req_id="r0"):
    rng = np.random.default_rng(0)
    ids = rng.integers(0, spec.bucket,
                       (rows, spec.num_fields)).astype(int).tolist()
    vals = rng.random((rows, spec.num_fields)).astype(float).tolist()
    return {"id": req_id, "class": cls, "deadline_ms": deadline_ms,
            "ids": ids, "vals": vals}


def _stats_delta(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in after
            if k != "admission"}


class _CountingBackend:
    """Unit-test seam: counts ``score`` calls so shed-before-backend
    is assertable, answers instantly with a fixed generation."""

    def __init__(self, gen_step: int = 1):
        self.calls = 0
        self.gen_step = gen_step

    def score(self, ids, vals, deadline):
        self.calls += 1
        return ([0.0] * len(ids),
                {"generation_step": self.gen_step, "replica": 0})

    def healthz(self):
        return {"ready": True, "n_replicas": 1,
                "replicas": [{"replica": 0, "state": "ready",
                              "generation_step": self.gen_step}]}

    def close(self):
        pass


# ------------------------------------------------- admission control


def test_parse_classes_priority_is_spec_order():
    classes = parse_classes("interactive:64:500,batch:64:2000,"
                            "background:32:8000")
    assert [c.name for c in classes] == ["interactive", "batch",
                                         "background"]
    assert [c.priority for c in classes] == [0, 1, 2]
    assert classes[2].queue_cap == 32
    assert classes[0].default_deadline_ms == 500.0


@pytest.mark.parametrize("bad", [
    "",                          # empty spec
    "interactive:64",            # missing deadline
    "interactive:0:500",         # cap < 1
    "interactive:8:0",           # deadline <= 0
    ":8:500",                    # nameless
    "a:8:500,a:8:500",           # duplicate name
])
def test_parse_classes_rejects_malformed_specs(bad):
    with pytest.raises(ValueError):
        parse_classes(bad)


def test_admission_queue_cap_sheds_with_retry_after():
    adm = AdmissionController("interactive:2:500", service_est_ms=5.0)
    assert adm.admit("interactive", 500).admitted
    assert adm.admit("interactive", 500).admitted
    v = adm.admit("interactive", 500)
    assert v.decision == "shed_queue" and not v.admitted
    assert v.retry_after_ms > 0
    assert adm.snapshot()["inflight"]["interactive"] == 2
    adm.release("interactive")
    assert adm.admit("interactive", 500).admitted


def test_admission_sheds_unpayable_deadline_by_priority():
    """The deadline estimate is priority-aware: background queues
    behind everyone, interactive only behind itself — so under a
    background backlog the SAME deadline sheds background traffic
    while interactive still clears."""
    adm = AdmissionController("interactive:8:500,background:8:8000",
                              service_est_ms=10.0)
    for _ in range(4):
        assert adm.admit("background", 8000).admitted
    hi = adm.admit("interactive", 25.0)
    assert hi.admitted, hi  # est = 10ms * (0 ahead + 1) <= 25ms
    lo = adm.admit("background", 25.0)
    assert lo.decision == "shed_deadline"  # 10ms * 6 > 25ms
    assert lo.est_ms > 25.0
    assert lo.retry_after_ms >= 10.0


def test_admission_unknown_class_rejected_and_ewma_learns():
    adm = AdmissionController("interactive:8:500",
                              service_est_ms=100.0)
    assert adm.admit("nope", 500).decision == "rejected"
    assert adm.admit("interactive", 500).admitted
    adm.release("interactive", service_ms=10.0)
    assert adm.snapshot()["service_est_ms"] < 100.0


# ------------------------------------------------------ traffic replay


def test_make_schedule_is_pure_and_shapes_differ():
    for shape in loadgen.SHAPES:
        a = loadgen.make_schedule(shape, 3)
        b = loadgen.make_schedule(shape, 3)
        assert a == b  # frozen dataclasses: byte-identical replay
        assert a.events and a.shape == shape
        assert loadgen.make_schedule(shape, 4) != a
    # The payload is part of the purity contract too.
    sched = loadgen.make_schedule("diurnal", 1)
    p1 = loadgen.event_payload(sched.events[0], sched, nnz=4,
                               num_features=256)
    p2 = loadgen.event_payload(sched.events[0], sched, nnz=4,
                               num_features=256)
    assert p1 == p2


def test_schedule_shapes_encode_their_stress():
    diurnal = loadgen.make_schedule("diurnal", 0, deadline_ms=500)
    storm = loadgen.make_schedule("retry_storm", 0, deadline_ms=500)
    slow = loadgen.make_schedule("slow_clients", 0)
    # The storm over-offers with tighter deadlines and retries.
    assert storm.n_requests > diurnal.n_requests
    assert (max(e.deadline_ms for e in storm.events)
            < min(e.deadline_ms for e in diurnal.events))
    assert all(e.max_retries > 0 for e in storm.events)
    # A seeded third of slow clients stall mid-POST.
    stalled = [e for e in slow.events if e.slow_s > 0]
    assert stalled and len(stalled) < slow.n_requests


def test_fleet_schedule_is_pure_and_valid():
    seen = set()
    for seed in range(10):
        a = chaos.fleet_schedule(seed)
        assert a == chaos.fleet_schedule(seed)
        a.validate()
        assert a.shape in loadgen.SHAPES
        seen.add(a.scenario)
    assert seen == {f"fleet_{s}" for s in chaos._FLEET_SCENARIOS}


# -------------------------------------------- front door over HTTP


@pytest.fixture(scope="module")
def _eng():
    spec = _spec()
    eng = PredictEngine(spec, _params(spec), buckets=(1, 4),
                        latency_budget_ms=5.0)
    eng.warmup()
    yield spec, eng
    eng.close()


def test_frontdoor_sheds_before_the_backend_scores():
    """The tentpole invariant: a shed request NEVER reaches the
    backend — no coalescer slot, no compute, an explicit 429 with
    Retry-After. Both shed modes, then an admit to prove the door
    still works."""
    backend = _CountingBackend()
    door = FrontDoor(backend, admission=AdmissionController(
        "interactive:1:500", service_est_ms=50.0)).start()
    try:
        before = door.stats()
        # Unpayable deadline: est 50ms > 10ms — shed at admission.
        status, doc, headers = _post(door.port, _predict_doc(
            _spec(), deadline_ms=10.0))
        assert status == 429 and doc["error"] == "shed_deadline"
        assert doc["retry_after_ms"] > 0
        assert "Retry-After" in headers
        assert backend.calls == 0
        # Queue full: occupy the single slot, then knock again.
        assert door.admission.admit("interactive", 1000).admitted
        status, doc, _ = _post(door.port, _predict_doc(
            _spec(), deadline_ms=1000.0))
        assert status == 429 and doc["error"] == "shed_queue"
        assert backend.calls == 0
        door.admission.release("interactive")
        # And the door still answers payable traffic.
        status, doc, _ = _post(door.port, _predict_doc(
            _spec(), deadline_ms=1000.0))
        assert status == 200 and backend.calls == 1
        delta = _stats_delta(before, door.stats())
        assert delta["shed"] == 2
        assert delta["shed_queue"] == 1 and delta["shed_deadline"] == 1
        assert delta["answered"] == 1
    finally:
        door.stop()


def test_frontdoor_rejects_malformed_and_unknown_class():
    backend = _CountingBackend()
    door = FrontDoor(backend).start()
    try:
        status, doc, _ = _post(door.port, b"{not json")
        assert status == 400 and "malformed" in doc["error"]
        bad = _predict_doc(_spec(), cls="no-such-class")
        status, doc, _ = _post(door.port, bad)
        assert status == 400 and "unknown class" in doc["error"]
        assert backend.calls == 0
    finally:
        door.stop()


def test_frontdoor_accept_fault_is_an_explicit_500():
    """The ``frontdoor_accept`` drill point: an injected transport
    fault surfaces as a counted 500 — never a hang, never a silent
    drop — and the next request is clean."""
    backend = _CountingBackend()
    door = FrontDoor(backend).start()
    try:
        before = door.stats()
        faults.activate("frontdoor_accept@1=error")
        status, doc, _ = _post(door.port, _predict_doc(_spec()))
        assert status == 500 and "accept failed" in doc["error"]
        assert backend.calls == 0
        status, _, _ = _post(door.port, _predict_doc(_spec()))
        assert status == 200
        delta = _stats_delta(before, door.stats())
        assert delta["failed"] == 1 and delta["answered"] == 1
    finally:
        faults.clear()
        door.stop()


def test_frontdoor_deadline_propagates_to_engine_504(_eng):
    """An admitted request whose deadline expires inside the engine
    comes back as a 504 under the armed ``frontdoor_request`` watchdog
    phase, with the admission slot released."""
    spec, eng = _eng
    watchdog.configure("frontdoor_request=30")
    # alpha=0 pins the estimate: the first (successful) request must
    # not teach the EWMA a real service time, or the tiny-deadline
    # request below would be shed at admission instead of admitted.
    door = FrontDoor(LocalBackend(eng),
                     admission=AdmissionController(
                         service_est_ms=0.01, ewma_alpha=0.0)).start()
    try:
        before = door.stats()
        status, doc, _ = _post(door.port, _predict_doc(
            spec, deadline_ms=4000.0))
        assert status == 200 and len(doc["scores"]) == 2
        assert doc["generation_step"] == eng.generation().step
        # est 0.01ms admits it; a 0.05ms deadline then expires in
        # the coalescer before any dispatch.
        status, doc, _ = _post(door.port, _predict_doc(
            spec, deadline_ms=0.05))
        assert status == 504 and "deadline expired" in doc["error"]
        delta = _stats_delta(before, door.stats())
        assert delta["answered"] == 1 and delta["timeout"] == 1
        snap = door.admission.snapshot()
        assert all(n == 0 for n in snap["inflight"].values())
    finally:
        door.stop(close_backend=False)
        watchdog.clear()


def test_frontdoor_healthz_and_metrics(_eng):
    spec, eng = _eng
    door = FrontDoor(LocalBackend(eng)).start()
    try:
        status, body = _get(door.port, "/healthz")
        doc = json.loads(body)
        assert status == 200 and doc["ready"]
        assert doc["counters"]["accepted"] >= 0
        assert doc["admission"]["classes"]
        status, body = _get(door.port, "/metrics")
        assert status == 200 and "frontdoor" in body
    finally:
        door.stop(close_backend=False)


def test_loadgen_replay_audits_green(tmp_path):
    """A small seeded replay against the door: every scheduled request
    reaches a terminal outcome, the books close, and the fleet auditor
    grades the run green from the tap + counter deltas alone."""
    backend = _CountingBackend(gen_step=3)
    door = FrontDoor(backend, admission=AdmissionController(
        service_est_ms=0.5)).start()
    tap = str(tmp_path / "tap.jsonl")
    try:
        before = door.stats()
        sched = loadgen.make_schedule("diurnal", 0, duration_s=0.3,
                                      base_rps=40.0, rows=2,
                                      deadline_ms=4000.0)
        summary = loadgen.run_loadgen(
            "127.0.0.1", door.port, sched, tap, nnz=4,
            num_features=256, threads=4)
        assert summary["requests"] == sched.n_requests
        assert summary["by_outcome"].get("ok") == sched.n_requests
        delta = _stats_delta(before, door.stats())
        assert delta["answered"] == sched.n_requests
        violations = audit_fleet(
            read_events(tap), delta,
            expected_requests=sched.n_requests,
            tombstoned_steps=())
        assert violations == []
    finally:
        door.stop()


# ------------------------------------------------- the fleet auditor


def _tap(*recs):
    out = []
    for i, (rid, attempt, outcome, gen) in enumerate(recs):
        out.append({"event": "attempt", "req_id": rid,
                    "attempt": attempt, "outcome": outcome,
                    "gen_step": gen, "ts": float(i)})
    return out


def _counters(**kw):
    base = {k: 0 for k in ("accepted", "answered", "shed",
                           "shed_queue", "shed_deadline", "rejected",
                           "timeout", "failed", "retries")}
    base.update(kw)
    return base


def test_audit_fleet_green_on_clean_books():
    tap = _tap(("a", 1, "ok", 2), ("b", 1, "shed", None),
               ("b", 2, "ok", 2))
    counters = _counters(accepted=2, answered=2, shed=1,
                         shed_deadline=1)
    assert audit_fleet(tap, counters, expected_requests=2,
                       tombstoned_steps=(3,)) == []


def test_audit_fleet_flags_double_answer_and_drops():
    # Same (req_id, attempt) twice: an in-flight request answered
    # twice after a replica death.
    tap = _tap(("a", 1, "ok", 2), ("a", 1, "ok", 2))
    v = audit_fleet(tap, _counters(accepted=2, answered=2))
    assert any(x["invariant"] == "exactly_once_responses" for x in v)
    # Two ok's across attempts: retried after a success.
    tap = _tap(("a", 1, "ok", 2), ("a", 2, "ok", 2))
    v = audit_fleet(tap, _counters(accepted=2, answered=2))
    assert any("answered ok 2 times" in x["detail"] for x in v)
    # A scheduled request with no terminal outcome: silently dropped.
    v = audit_fleet(_tap(("a", 1, "ok", 2)),
                    _counters(accepted=1, answered=1),
                    expected_requests=2)
    assert any("silently dropped" in x["detail"] for x in v)


def test_audit_fleet_flags_open_books_and_shed_mismatch():
    tap = _tap(("a", 1, "ok", 2))
    v = audit_fleet(tap, _counters(accepted=2, answered=1))
    assert any(x["invariant"] == "accepted_accounting" for x in v)
    v = audit_fleet(tap, _counters(accepted=1, answered=1, shed=2,
                                   shed_queue=1))
    kinds = [x["invariant"] for x in v]
    assert kinds.count("shed_accounting") == 2  # split AND tap
    v = audit_fleet(tap, _counters(accepted=1, answered=1))
    assert v == []


def test_audit_fleet_flags_tombstoned_generation():
    tap = _tap(("a", 1, "ok", 4))
    v = audit_fleet(tap, _counters(accepted=1, answered=1),
                    tombstoned_steps=(4,))
    assert any(x["invariant"] == "no_tombstoned_generation"
               for x in v)


def test_audit_fleet_splits_replica_journal_at_incarnations():
    """A SIGKILLed replica's respawn restarts its generation sequence
    from the base model — monotonicity holds WITHIN an incarnation,
    never across the journal."""
    journal = [
        {"event": "replica_start", "replica": 0},
        {"event": "serve_swap", "step": 5, "gen_id": 2},
        {"event": "replica_start", "replica": 0},   # respawn
        {"event": "serve_swap", "step": 5, "gen_id": 2},  # re-reload
    ]
    counters = _counters()
    assert audit_fleet([], counters, replica_events={0: journal}) == []
    torn = [  # same incarnation, step going backwards: torn swap
        {"event": "replica_start", "replica": 0},
        {"event": "serve_swap", "step": 5, "gen_id": 2},
        {"event": "serve_swap", "step": 3, "gen_id": 3},
    ]
    v = audit_fleet([], counters, replica_events={0: torn})
    assert any(x["invariant"] == "no_torn_swap" for x in v)
    assert all("incarnation" in x["detail"] for x in v)


# ------------------------------------- the fleet, for real (processes)


def _wait_ready(fleet, want: int, timeout_s: float) -> None:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        docs = fleet.healthz()["replicas"]
        if sum(1 for d in docs if d["state"] == "ready") >= want:
            return
        time.sleep(0.25)
    raise AssertionError(
        f"fleet did not reach {want} ready replicas within "
        f"{timeout_s:.0f}s: {fleet.healthz()['replicas']}")


def test_fleet_sigkill_drill_loses_nothing_and_readmits(tmp_path):
    """THE acceptance drill: ``replica_kill`` (an ``os._exit`` inside
    whichever replica serves the Nth scored request — SIGKILL as seen
    from the parent) mid-burst loses zero accepted requests; every
    request is answered exactly once or failed explicitly, the dead
    replica is re-admitted after ``/healthz`` readiness, and a
    parent-side ``fleet_dispatch`` fault is absorbed by the same
    retry. Audited from the tap + counter deltas + replica journals."""
    spec = _spec()
    model_dir = str(tmp_path / "model")
    models.save_model(model_dir, spec, _params(spec, 0.1))
    state = str(tmp_path / "faults_state.json")
    health_path = str(tmp_path / "fleet_health.jsonl")
    journal = EventLog(health_path)
    fleet = Fleet(
        model_dir, n_replicas=2, work_dir=str(tmp_path / "work"),
        journal=journal, buckets="1,4",
        spawn_timeout_s=300.0,
        # The drill plan rides the REPLICA environment: the 4th scored
        # request across the fleet (shared cross-process fault state)
        # kills its replica mid-handling. So does the compile cache's
        # placement (the respawned replica warms from it).
        replica_env={faults.ENV_PLAN: "replica_kill@4=exit:9",
                     faults.ENV_STATE: state,
                     "JAX_COMPILATION_CACHE_DIR":
                         str(tmp_path / "cache")})
    fleet.start()
    door = FrontDoor(fleet, admission=AdmissionController(
        "interactive:32:8000,batch:16:8000,background:8:9000",
        service_est_ms=2.0), journal=journal).start()
    tap = str(tmp_path / "tap.jsonl")
    try:
        before = door.stats()
        sched = loadgen.make_schedule(
            "flash_crowd", 5, duration_s=0.6, base_rps=30.0,
            rows=2, deadline_ms=8000.0)
        assert sched.n_requests > 4  # the kill fires mid-burst
        summary = loadgen.run_loadgen(
            "127.0.0.1", door.port, sched, tap,
            nnz=spec.num_fields, num_features=spec.num_features,
            threads=6, attempt_timeout_s=60.0)
        delta = _stats_delta(before, door.stats())
        # Zero lost: every scheduled request answered exactly once.
        assert summary["by_outcome"].get("ok") == sched.n_requests
        assert delta["answered"] == sched.n_requests
        assert delta["retries"] >= 1  # the kill was absorbed in-flight
        replica_events = {}
        for rep in fleet.replicas:
            jpath = os.path.join(fleet.work_dir,
                                 f"replica_{rep.idx}.jsonl")
            if os.path.exists(jpath):
                replica_events[rep.idx] = read_events(jpath)
        violations = audit_fleet(
            read_events(tap), delta,
            expected_requests=sched.n_requests,
            tombstoned_steps=(), replica_events=replica_events)
        assert violations == []
        # The parent saw the death (rc=9, the injected exit code) ...
        downs = [e for e in read_events(health_path)
                 if e.get("event") == "replica_down"]
        assert any(e.get("rc") == 9 for e in downs), downs
        # ... and the replica is re-admitted: /healthz readiness,
        # then it serves again.
        _wait_ready(fleet, 2, _READMIT_TIMEOUT_S)
        assert fleet.healthz()["ready"]
        status, doc, _ = _post(door.port, _predict_doc(spec))
        assert status == 200
        # Parent-side dispatch fault: first attempt errors, the retry
        # answers — the client never sees the hiccup.
        before = door.stats()
        faults.activate("fleet_dispatch@1=error")
        status, doc, _ = _post(door.port, _predict_doc(spec))
        assert status == 200
        delta = _stats_delta(before, door.stats())
        assert delta["retries"] >= 1 and delta["answered"] == 1
    finally:
        faults.clear()
        door.stop()


def test_fleet_chaos_campaign_green(tmp_path):
    """Two seeded fleet schedules (kill-mid-flash-crowd, retry-storm
    + demote race) against one shared two-replica fleet: completed,
    audited green, with a measured recovery for the kill scenario."""
    entries = chaos.run_fleet_campaign(seeds=(0, 1),
                                       base_dir=str(tmp_path))
    assert [e["seed"] for e in entries] == [0, 1]
    for e in entries:
        assert e["outcome"] == "completed"
        assert e["verdict"] == "green", e["violations"]
        assert e["traffic"]["requests"] > 0
    kill = entries[0]
    assert kill["scenario"] == "fleet_kill_flash_crowd"
    assert kill["killed_replica"] is not None
    assert kill["recovery_s"] is not None and kill["recovery_s"] > 0
    storm = entries[1]
    assert storm["scenario"] == "fleet_retry_storm_demote"
    assert storm["demoted_step"] is not None


# ------------------------- N concurrent followers, one trainer chain


def test_concurrent_chain_followers_converge_nontombstoned(tmp_path):
    """Three independent ReloadFollowers (each with its own engine)
    poll ONE chain while the trainer advances and demotes. All three
    converge to the same non-tombstoned tip, none ever installs the
    deterministically-demoted step, and a byte-hash audit proves the
    read-only followers never wrote into the trainer's chain."""
    spec = _spec()
    params = _params(spec)
    chain_dir = str(tmp_path / "chain")
    ck = Checkpointer(chain_dir, save_every=1, async_save=False)
    ck.save(1, params, {}, None, force=True)
    ck.wait()

    journals = [EventLog(str(tmp_path / f"f{i}.jsonl"))
                for i in range(3)]
    # One journal per follower, shared with its engine: serve_swap is
    # the ENGINE's event, reload_failed the follower's — the audit
    # reads both from the same stream.
    engines = [PredictEngine(spec, params, buckets=(1,),
                             journal=journals[i]) for i in range(3)]
    followers = [
        ReloadFollower(eng, chain_dir, poll_s=0.02,
                       journal=journals[i])
        for i, eng in enumerate(engines)]
    try:
        # Deterministic demote: published, tombstoned, and only THEN
        # polled — every follower must refuse step 2.
        assert [f.poll_once() for f in followers] == ["swapped"] * 3
        ck.save(2, params, {}, None, force=True)
        ck.wait()
        ck.demote(2, reason="drill")
        for f in followers:
            assert f.poll_once() in ("fresh", "stale_chain")
        assert [e.generation().step for e in engines] == [1, 1, 1]

        # Concurrent: trainer advances while all three poll freely.
        for f in followers:
            f.start()
        for step in (3, 4, 5):
            ck.save(step, params, {}, None, force=True)
            ck.wait()
            time.sleep(0.05)
        stones = set(ck.tombstoned_steps())
        ck.close()

        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            if all(e.generation().step == 5 for e in engines):
                break
            time.sleep(0.05)
        steps = [e.generation().step for e in engines]
        assert steps == [5, 5, 5], steps
        assert 5 not in stones and 2 in stones

        # Byte-hash audit: the chain after the trainer's last write,
        # then several more poll rounds, must be bit-identical — the
        # followers are read-only.
        def snapshot():
            out = {}
            for root, _, files in os.walk(chain_dir):
                for name in files:
                    p = os.path.join(root, name)
                    with open(p, "rb") as fh:
                        out[os.path.relpath(p, chain_dir)] = (
                            hashlib.sha256(fh.read()).hexdigest())
            return out

        before = snapshot()
        time.sleep(0.3)  # ~15 poll rounds across 3 followers
        assert snapshot() == before
    finally:
        for f in followers:
            f.stop()
        for eng in engines:
            eng.close()
    # Every follower's journal passes the serve audit against the
    # demoted set: no torn swap, never a tombstoned generation.
    for i in range(3):
        events = read_events(str(tmp_path / f"f{i}.jsonl"))
        swaps = [e for e in events if e.get("event") == "serve_swap"]
        assert swaps and swaps[-1]["step"] == 5
        assert chaos.audit_serve_events(
            events, tombstoned_steps={2}) == []


def test_fleet_that_never_gets_ready_leaves_no_replica_behind(tmp_path):
    """A failed start() must stop what it started: on a TPU an orphaned
    replica keeps the chip after the launcher has exited (seen on the
    v5e, PR 21). The model directory is missing, so no replica can ever
    become ready."""
    fleet = Fleet(str(tmp_path / "no_such_model"), n_replicas=2,
                  work_dir=str(tmp_path / "work"), spawn_timeout_s=1.0)
    with pytest.raises(RuntimeError, match="fleet not ready"):
        fleet.start()
    assert all(rep.proc.poll() is not None for rep in fleet.replicas)
