"""Chaos campaign engine + deadline watchdogs (ISSUE 10).

Four layers, bottom-up: the per-phase deadline watchdog units (raise
and exit modes, production wiring at the ingest chunk read), the
seeded schedule generator's determinism/validity, the TIER-1 BOUNDED
SOAK — 25 fixed-seed multi-fault schedules through the invariant
auditor, every invariant green, inside a hard time budget — and the
acceptance drills: a deliberately-broken recovery path (the
``break_restore`` canary) is caught by the auditor and minimized to a
<= 2-rule reproducible plan; a SIGKILL mid-run with spool-compaction
pressure resumes exactly-once; native<->python ingest restores across
paths under a compound ``ingest_truncate`` + ``device_loss`` schedule.
"""

import dataclasses
import os
import signal
import time

import pytest

from fm_spark_tpu.resilience import chaos, faults, watchdog
from fm_spark_tpu.resilience.watchdog import (
    HANG_EXIT_RC,
    HangDetected,
    WatchdogTable,
)
from fm_spark_tpu.utils.logging import EventLog, read_events

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: The fixed tier-1 soak seed list (tools/chaos_drill.py runs the same
#: list): fixed so every CI round drills the SAME plans and a
#: regression bisects cleanly.
SOAK_SEEDS = tuple(range(25))
SOAK_BUDGET_S = 240.0
SOAK_PER_SCHEDULE_S = 30.0


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.delenv(faults.ENV_PLAN, raising=False)
    monkeypatch.delenv(faults.ENV_STATE, raising=False)
    monkeypatch.delenv(watchdog.ENV_SPEC, raising=False)
    monkeypatch.delenv(watchdog.ENV_ACTION, raising=False)
    faults.clear()
    watchdog.clear()
    yield
    faults.clear()
    watchdog.clear()


# ------------------------------------------------------------- watchdog


def test_watchdog_noop_when_unconfigured():
    assert not watchdog.active()
    ctx = watchdog.phase("step_window")
    ctx2 = watchdog.phase("ingest_chunk")
    assert ctx is ctx2  # the shared allocation-free no-op
    with ctx:
        pass


def test_watchdog_spec_parse_and_validation():
    assert watchdog.parse_spec("ingest_chunk=2;step_window=30.5") == {
        "ingest_chunk": 2.0, "step_window": 30.5}
    with pytest.raises(ValueError, match="phase"):
        watchdog.parse_spec("no_such_phase=2")
    with pytest.raises(ValueError):
        watchdog.parse_spec("ckpt_commit=0")
    with pytest.raises(ValueError):
        WatchdogTable({}, action="explode")


def test_watchdog_raise_mode_detects_finite_hang(tmp_path):
    journal_path = str(tmp_path / "j.jsonl")
    watchdog.configure({"ingest_chunk": 0.01}, action="raise",
                       journal=EventLog(journal_path))
    assert watchdog.active("ingest_chunk")
    assert not watchdog.active("step_window")  # unbudgeted phase
    with watchdog.phase("step_window"):
        time.sleep(0.03)  # no budget: never a verdict
    with pytest.raises(HangDetected) as exc:
        with watchdog.phase("ingest_chunk"):
            time.sleep(0.03)
    assert exc.value.phase == "ingest_chunk"
    assert exc.value.elapsed_s > exc.value.deadline_s
    events = read_events(journal_path)
    assert [e["event"] for e in events] == ["hang_detected"]
    assert events[0]["phase"] == "ingest_chunk"
    assert events[0]["deadline_s"] == 0.01


def test_watchdog_raise_mode_never_masks_primary_exception(tmp_path):
    table = watchdog.configure({"ckpt_commit": 0.01}, action="raise")
    with pytest.raises(ValueError, match="primary"):
        with watchdog.phase("ckpt_commit"):
            time.sleep(0.03)
            raise ValueError("primary")
    # The overrun is still recorded as evidence, just not raised over
    # the real failure.
    assert table.hangs_detected == 1


def test_watchdog_within_deadline_is_silent(tmp_path):
    journal_path = str(tmp_path / "j.jsonl")
    table = watchdog.configure({"ingest_chunk": 5.0}, action="raise",
                               journal=EventLog(journal_path))
    with watchdog.phase("ingest_chunk"):
        pass
    assert table.hangs_detected == 0
    assert read_events(journal_path) == []


def test_watchdog_exit_mode_monitor_bounds_a_real_hang(tmp_path):
    """Exit mode is the only way out of a phase that never returns: the
    monitor thread fires mid-phase and hard-exits with the distinct
    hang rc (stubbed here; the subprocess drill proves the real
    ``os._exit`` path end-to-end)."""
    exits = []
    journal_path = str(tmp_path / "j.jsonl")
    table = WatchdogTable({"step_window": 0.03}, action="exit",
                          journal=EventLog(journal_path),
                          poll_s=0.005, _exit=exits.append)
    with table.phase("step_window"):
        deadline = time.monotonic() + 2.0
        while not exits and time.monotonic() < deadline:
            time.sleep(0.005)  # "hung" until the monitor fires
    table.close()
    assert exits == [HANG_EXIT_RC]
    events = read_events(journal_path)
    assert events and events[0]["event"] == "hang_detected"
    assert events[0]["action"] == "exit"


def test_watchdog_env_configuration(monkeypatch):
    monkeypatch.setenv(watchdog.ENV_SPEC, "ingest_chunk=0.01")
    monkeypatch.setenv(watchdog.ENV_ACTION, "raise")
    watchdog.clear()  # force the env re-read
    with pytest.raises(HangDetected):
        with watchdog.phase("ingest_chunk"):
            time.sleep(0.03)


def test_hang_fault_at_chunk_read_is_detected_in_production_wiring(
        tmp_path):
    """The real call site: an injected finite hang on the ShardReader
    chunk read converts into HangDetected through the ``ingest_chunk``
    phase wired in data/stream.py."""
    from fm_spark_tpu.data.stream import ShardReader

    p = tmp_path / "s.svm"
    p.write_text("1 1:1.0\n0 2:1.0\n")
    watchdog.configure({"ingest_chunk": 0.02}, action="raise")
    faults.activate("ingest_truncate@1=hang:0.1")
    with pytest.raises(HangDetected, match="ingest_chunk"):
        ShardReader([str(p)]).next_line()


# ------------------------------------------------------------ generator


def test_schedule_generator_is_deterministic_and_valid():
    gen = chaos.ScheduleGenerator()
    a = gen.sample(range(40))
    b = chaos.ScheduleGenerator().sample(range(40))
    assert [s.plan for s in a] == [s.plan for s in b]
    for s in a:
        assert s.rules, "every schedule carries at least one rule"
        faults.FaultPlan.from_spec(s.plan)  # registry-valid, eagerly


def test_generator_covers_the_nasty_interleavings():
    scen = {s.scenario for s in chaos.ScheduleGenerator().sample(
        range(40))}
    # Every biased scenario class appears within a small seed range —
    # the soak really does compose faults, not rerun one shape.
    assert {"commit_loss", "recovery_storm", "corrupt_burst",
            "truncate_loss", "hang", "ingest_abort",
            "compound"} <= scen
    multi = [s for s in chaos.ScheduleGenerator().sample(range(40))
             if len(s.rules) > 1]
    assert len(multi) >= 20, "schedules must be MULTI-fault plans"


def test_oracle_matches_the_unfaulted_stream():
    cfg = chaos.DrillConfig()
    clean = chaos.Schedule(seed=-1, scenario="golden", rules=())
    taps = chaos.oracle_tap(clean, cfg)
    assert len(taps) == cfg.steps
    assert taps[0].split(",")[0] == "0"
    # 96 rows / 16 per batch: epoch boundary at batch 6 restarts ids.
    assert taps[6].split(",")[0] == "0"


# ------------------------------------------------- tier-1 bounded soak


def test_tier1_chaos_soak_25_schedules_all_invariants_green(tmp_path):
    """ISSUE 10 acceptance: the bounded tier-1 soak runs >= 25 seeded
    multi-fault schedules deterministically within its time budget with
    every invariant green."""
    verdict = chaos.run_campaign(
        SOAK_SEEDS, base_dir=str(tmp_path),
        time_budget_s=SOAK_BUDGET_S,
        per_schedule_timeout_s=SOAK_PER_SCHEDULE_S,
        minimize_failures=False)
    failing = [(e["seed"], e["scenario"], e["plan"], e["violations"])
               for e in verdict["schedules"]
               if e["verdict"] != "green"]
    assert verdict["n_schedules"] >= 25
    assert not verdict["budget_exhausted"], (
        f"soak blew its {SOAK_BUDGET_S:.0f}s budget "
        f"({verdict['total_s']:.1f}s)")
    assert verdict["all_green"], failing
    # The soak is genuinely adversarial: several scenario classes and
    # several distinct outcomes (completed / hang_detected /
    # ingest_aborted) all appear.
    scenarios = {e["scenario"] for e in verdict["schedules"]}
    outcomes = {e["outcome"] for e in verdict["schedules"]}
    assert len(scenarios) >= 5
    assert {"completed", "hang_detected", "ingest_aborted"} <= outcomes


def test_canary_broken_recovery_is_caught_and_minimized(tmp_path):
    """ISSUE 10 acceptance: a deliberately-broken recovery path (the
    restore canary stops rewinding the stream cursor) is CAUGHT by the
    auditor and delta-debugged to a <= 2-rule reproducible plan."""
    cfg = dataclasses.replace(chaos.DrillConfig(), break_restore=True)
    # Seed 3 is a recovery_storm (pinned by the deterministic
    # generator) — a stream-comparable schedule with recovery faults,
    # exactly what a broken restore must corrupt.
    sched = chaos.ScheduleGenerator(cfg).schedule(3)
    assert sched.scenario == "recovery_storm" and len(sched.rules) >= 2
    verdict = chaos.run_campaign([3], cfg=cfg, base_dir=str(tmp_path),
                                 minimize_failures=True)
    assert not verdict["all_green"]
    (failure,) = verdict["failures"]
    violated = {v["invariant"] for v in failure["violations"]}
    assert "exactly_once_stream" in violated
    assert "loss_continuity" in violated
    assert failure["minimized_rules"] <= 2
    minimized = failure["minimized_plan"]
    assert minimized and "device_loss" in minimized
    # The minimized plan is itself a valid, replayable fault plan.
    faults.FaultPlan.from_spec(minimized)


def test_campaign_budget_exhaustion_is_loud(tmp_path):
    verdict = chaos.run_campaign([1, 2, 3], base_dir=str(tmp_path),
                                 time_budget_s=0.0,
                                 minimize_failures=False)
    # The golden run spends the zero budget: every schedule is
    # recorded as skipped, and the campaign refuses to call itself
    # green.
    assert verdict["n_skipped"] == 3
    assert verdict["budget_exhausted"]
    assert not verdict["all_green"]


# -------------------------------- cross-path recovery (compound faults)


def _native_stream_ok() -> bool:
    from fm_spark_tpu.data.native_stream import native_stream_supported

    return native_stream_supported("libsvm", 3)


@pytest.mark.parametrize("first_native", [True, False])
def test_cross_path_recovery_under_compound_faults(tmp_path,
                                                   first_native):
    """ISSUE 10 satellite: a run that survives an ``ingest_truncate``
    device loss + mid-step device loss on ONE ingest path checkpoints,
    then resumes on the OTHER path (native<->python), and the combined
    record stream, loss curve, and final params are bit-identical to
    the clean run — the exactly-once cursor really is path-portable
    under compound faults."""
    if not _native_stream_ok():
        pytest.skip("native stream parser unavailable")
    from fm_spark_tpu import models
    from fm_spark_tpu.checkpoint import Checkpointer
    from fm_spark_tpu.data.native_stream import make_stream_batches
    from fm_spark_tpu.data.stream import RecordGuard, ShardReader
    from fm_spark_tpu.resilience.supervisor import (
        BackoffPolicy,
        Supervisor,
    )
    from fm_spark_tpu.train import FMTrainer, TrainConfig

    cfg = chaos.DrillConfig()
    shards = chaos.build_shards(str(tmp_path / "shards"), cfg)
    golden = chaos.golden_run(cfg, str(tmp_path / "golden"),
                              shard_paths=shards)
    spec = models.FMSpec(num_features=cfg.num_features, rank=cfg.rank,
                         init_std=0.05)
    ck_dir = str(tmp_path / "ck")

    def leg(native: bool, steps: int, plan: str):
        guard = RecordGuard(
            "quarantine",
            quarantine_dir=str(tmp_path / f"q{int(native)}"))
        source = chaos._TapSource(make_stream_batches(
            ShardReader(shards, chunk_bytes=cfg.chunk_bytes), "libsvm",
            cfg.batch_size, cfg.max_nnz, guard=guard,
            num_features=cfg.num_features,
            native_ingest=True if native else False))
        config = TrainConfig(num_steps=steps,
                             batch_size=cfg.batch_size,
                             learning_rate=cfg.learning_rate,
                             lr_schedule="constant", log_every=1,
                             seed=cfg.seed)
        ck = Checkpointer(ck_dir, save_every=cfg.save_every,
                          async_save=False)
        sup = Supervisor(policy=BackoffPolicy(initial=0.01, jitter=0.0),
                         probe=lambda: True, breaker_threshold=8,
                         sleep=lambda s: None)
        trainer = FMTrainer(spec, config)
        trainer.logger._stream = None
        faults.clear()
        if plan:
            faults.activate(plan)
        try:
            trainer.fit(source, checkpointer=ck, supervisor=sup)
        finally:
            faults.clear()
            ck.close()
        return trainer, source

    # Leg 1 on path A survives the compound schedule and commits
    # through step 12; leg 2 on path B resumes the SAME chain.
    t1, s1 = leg(first_native, steps=12,
                 plan="ingest_truncate@3=device_loss;"
                      "train_step@7=device_loss")
    assert t1.step_count == 12
    t2, s2 = leg(not first_native, steps=cfg.steps, plan="")
    assert t2.step_count == cfg.steps

    combined = s1.lines[:12] + s2.lines
    assert combined == golden.tap
    assert t2.loss_history == golden.loss_history
    assert chaos._params_sums(t2.params) == golden.params_sums
    # The stream cursor is path-portable byte-for-byte (tap_len is the
    # wrapper's own bookkeeping — leg 2 only recorded its own batches).
    final = {k: v for k, v in s2.state().items() if k != "tap_len"}
    want = {k: v for k, v in golden.cursor.items() if k != "tap_len"}
    assert final == want


# ------------------- SIGKILL during flight-spool compaction (driven by
# ------------------- the chaos engine's subprocess runner)


def test_sigkill_during_spool_compaction_is_exactly_once(tmp_path):
    """ISSUE 10 satellite: the chaos engine SIGKILLs a drill mid-run
    with the flight ring sized so the spool is compacting (2N
    threshold), respawns it, and proves (a) exactly-once: the stitched
    record stream, loss curve, and final params are bit-identical to
    the clean run; (b) the spool survived the kill parseable with a
    monotonic, duplicate-free seq; (c) the checkpoint chain restores
    through last_good."""
    cfg = chaos.DrillConfig(flight_capacity=4)
    golden = chaos.golden_run(cfg, str(tmp_path / "golden"))
    result = chaos.run_schedule_subproc(
        "", cfg, str(tmp_path / "kill"), kill_at_step=9)
    assert result.outcome == "completed", (result.error, result.rcs)
    assert result.rcs[0] == -signal.SIGKILL  # the kill really landed
    assert result.rcs[-1] == 0               # rc discipline to the end
    assert result.resumed_at[0] == 0 and result.resumed_at[1] > 0

    # (a) exactly-once across the process death.
    assert chaos.stitch_taps(result) == golden.tap
    assert result.loss_history == golden.loss_history
    assert result.params_sums == golden.params_sums

    # (b) the spool: parseable after SIGKILL, seq monotonic and
    # duplicate-free ACROSS the respawn (the recorder seeds its seq
    # from the spool tail), and genuinely compacted (bounded to ~2N
    # lines while total recorded seq ran past it).
    from fm_spark_tpu.obs import read_spool

    spool = read_spool(os.path.join(str(tmp_path / "kill"), "obs",
                                    "flight.jsonl"))
    seqs = [e["seq"] for e in spool]
    assert seqs and seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    assert len(seqs) <= 2 * cfg.flight_capacity
    assert max(seqs) >= len(seqs)  # older lines were compacted away

    # (c) chain integrity, judged exactly like the campaign auditor.
    assert chaos._audit_chain(result, cfg) == []


@pytest.mark.slow
def test_subproc_timeout_bounds_a_silent_hang(tmp_path):
    """A hang at a point with NO watchdog budget emits nothing — the
    per-attempt timeout must still bound it (a blocking stdout read
    alone would wait out the full 3600s default hang)."""
    cfg = chaos.DrillConfig()
    t0 = time.monotonic()
    result = chaos.run_schedule_subproc(
        "ingest_truncate@1=hang", cfg, str(tmp_path / "silent"),
        attempts=1, timeout_s=10.0)
    assert result.outcome == "attempt_timeout"
    assert time.monotonic() - t0 < 60.0


@pytest.mark.slow
def test_soak_subprocess_hang_drill_exits_hang_rc_and_resumes(tmp_path):
    """Long-mode drill (tools/chaos_drill.py --soak): a REAL
    never-returning hang on the ingest chunk read is bounded by the
    exit-mode watchdog (rc 87), journaled, and the respawned attempt
    completes the run exactly-once. (Default flight ring: a capacity-4
    ring would compact the attempt-0 hang event away before the drill
    ends — the SIGKILL test owns the compaction-pressure variant.)"""
    cfg = chaos.DrillConfig()
    golden = chaos.golden_run(cfg, str(tmp_path / "golden"))
    result = chaos.run_schedule_subproc(
        "ingest_truncate@2=hang:300", cfg, str(tmp_path / "hang"),
        watchdog_spec="ingest_chunk=1.5")
    assert result.outcome == "completed", (result.error, result.rcs)
    assert result.rcs[0] == HANG_EXIT_RC
    assert chaos.stitch_taps(result) == golden.tap
    from fm_spark_tpu.obs import read_spool

    spool = read_spool(os.path.join(str(tmp_path / "hang"), "obs",
                                    "flight.jsonl"))
    assert any(e.get("kind") == "hang_detected" for e in spool)


# ----------------------------------------------------- drill CLI verdict


def test_chaos_drill_cli_writes_verdict_and_exits_green(tmp_path,
                                                        capsys):
    import importlib.util
    import json
    import sys

    spec = importlib.util.spec_from_file_location(
        "chaos_drill_tool", os.path.join(REPO, "tools",
                                         "chaos_drill.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)

    rc = mod.main(["--schedules", "2", "--no-minimize",
                   "--work-dir", str(tmp_path / "work"),
                   "--out", str(tmp_path / "obs")])
    assert rc == 0
    out = capsys.readouterr().out
    assert "ALL GREEN" in out
    run_dirs = os.listdir(str(tmp_path / "obs"))
    assert len(run_dirs) == 1
    with open(os.path.join(str(tmp_path / "obs"), run_dirs[0],
                           "chaos_verdict.json")) as f:
        verdict = json.load(f)
    assert verdict["n_schedules"] == 2 and verdict["all_green"]
    assert verdict["run_id"] == run_dirs[0]
    assert verdict["mode"] == "bounded"
    # Every entry names its seed + plan: the verdict IS the repro.
    for e in verdict["schedules"]:
        assert e["plan"] and isinstance(e["seed"], int)


# --------------------------------- drift/rollback drills (ISSUE 13)


def test_drift_schedules_deterministic_and_cover_the_class():
    gen = [chaos.drift_schedule(s) for s in chaos.DRIFT_TIER1_SEEDS]
    again = [chaos.drift_schedule(s) for s in chaos.DRIFT_TIER1_SEEDS]
    assert [s.plan for s in gen] == [s.plan for s in again]
    scenarios = {s.scenario for s in gen}
    # The five tier-1 seeds cover the whole failure class: the clean
    # protocol, the eval crash (online_eval), the commit-window crash
    # (ckpt_commit), the mid-demotion crash (ckpt_demote), and
    # rollback under quarantine ingest corruption (ingest_corrupt).
    assert scenarios == {"drift_clean_drift", "drift_eval_fault",
                         "drift_commit_fault", "drift_demote_fault",
                         "drift_rollback_corruption"}
    for s in gen:
        s.validate()  # every plan parses against the registry


def test_tier1_drift_campaign_all_invariants_green(tmp_path):
    """ISSUE 13 acceptance: the five seeded drift/rollback schedules
    run the PRODUCTION online loop (label-flip drift, streaming day
    shards, FTRL, crash-consistent chain) under fault plans, and the
    artifact auditor proves — for every schedule — completion across
    respawns, the sentry firing at the first drifted day, demotion
    tombstones + a never-vetoed last_good, the exactly-once per-day
    record stream, and byte-identical final params vs the clean run."""
    entries = chaos.run_drift_campaign(base_dir=str(tmp_path))
    failing = [(e["seed"], e["scenario"], e["violations"])
               for e in entries if e["verdict"] != "green"]
    assert len(entries) == 5
    assert not failing, failing
    assert all(e["rollbacks"] >= 1 for e in entries)
    assert all(e["demoted"] for e in entries)
