"""End-to-end fault-injected bench scenarios on the CPU backend (ISSUE 2
acceptance): a sweep that suffers an injected INIT HANG (child 1, caught
by the watchdog → rc=3 → parent retry) and then a MID-SWEEP DEVICE LOSS
(child 2, leg 2 — retried by the per-leg supervisor) still exits 0 with
a non-null parseable artifact and a health journal recording every
transition; a ``--resume-sweep`` restart then runs ONLY the remaining
legs. The all-attempts-dead path is covered too: the error JSON must
transport the best-known headline via its ``last_measured`` block.

Model ``fm_kaggle`` at batch 128 is the cheapest registered sweep (same
choice as tests/test_bench_fast_first.py); the two sweeps share one
compile cache so the resume restart is a warm re-entry — exactly the
production pairing the flag was built for.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def _run_bench(args, env, timeout):
    return subprocess.run(
        [sys.executable, BENCH] + args,
        capture_output=True, text=True, cwd=REPO, timeout=timeout,
        # Designed sleeps (parent/supervisor backoffs) shrink 4x by
        # default here — every assertion below is about BEHAVIOR
        # (events journaled, retries counted, verdicts classified),
        # never about how long a backoff waited. Deadlines, watchdog
        # windows, and measured durations are NOT scaled
        # (fm_spark_tpu/utils/sleeps.py). Override the env to rehearse
        # production timing.
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "FM_SPARK_TEST_SLEEP_SCALE": os.environ.get(
                 "FM_SPARK_TEST_SLEEP_SCALE", "0.25"),
             **env},
    )


def _last_json(stdout):
    lines = [ln for ln in stdout.strip().splitlines()
             if ln.startswith("{")]
    assert lines, f"no JSON line on stdout:\n{stdout[-2000:]}"
    return json.loads(lines[-1])


def _health_events(art, model):
    """The run's health journal, merged across attempts. Since ISSUE 7
    the journal lives under the per-run telemetry convention
    (<artifacts>/obs/<run_id>/health_<model>.jsonl); the old flat path
    is still read for back-compat."""
    paths = sorted((art / "obs").glob(f"*/health_{model}.jsonl"),
                   key=lambda p: p.stat().st_mtime)
    old = art / f"health_{model}.jsonl"
    if old.exists():
        paths.insert(0, old)
    assert paths, f"no health journal for {model} under {art}"
    events = []
    for p in paths:
        with open(p) as f:
            events.extend(json.loads(ln) for ln in f if ln.strip())
    return events


def test_sweep_survives_init_hang_then_device_loss_and_resumes(tmp_path):
    art = tmp_path / "art"
    common = ["--fast-first", "--model", "fm_kaggle",
              "--batch", "128", "--steps", "2",
              "--artifacts-dir", str(art)]

    # Phase 1: child 1's backend init hangs (watchdog exits it rc=3),
    # the parent retries, child 2 loses the device on sweep leg 2 and
    # the supervisor retries the leg. The run must still exit 0 with a
    # complete, parseable sweep.
    proc = _run_bench(
        common + ["--attempts", "2", "--attempt-timeout", "300",
                  "--total-deadline", "420", "--init-timeout", "8"],
        env={
            "FM_SPARK_FAULTS":
                "backend_init@1=hang:120;sweep_leg@2=device_loss",
            "FM_SPARK_FAULTS_STATE": str(tmp_path / "faults_state.json"),
        },
        timeout=460,
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    final = _last_json(proc.stdout)
    assert final["value"] is not None and final["value"] > 0
    assert final.get("error") is None
    assert final["legs_completed"] >= 2

    # The watchdog-killed child printed its provisional error line, and
    # that line already transported the best-known headline.
    first = json.loads(
        [ln for ln in proc.stdout.splitlines() if ln.startswith("{")][0])
    if first.get("error"):
        assert first["last_measured"]["value"] > 0
        assert first["last_measured"]["stale"] is True

    # ISSUE 7 acceptance: the result JSON carries the run's telemetry
    # block — step-time percentiles across the completed legs (the
    # fast-first leg included), the ingest-rate field, and the fault
    # timeline with the injected device loss the supervisor retried.
    assert final["run_id"]
    tel = final["telemetry"]
    assert tel["run_id"] == final["run_id"]
    st = tel["step_time_ms"]
    assert st["count"] >= final["legs_completed"]
    assert all(st[p] is not None and st[p] > 0
               for p in ("p50", "p95", "p99"))
    assert "ingest_rows_per_sec" in tel
    assert "device_memory" in tel
    kinds = [e["kind"] for e in tel["fault_events"]]
    assert "failure" in kinds and "backoff" in kinds

    # ISSUE 9 acceptance: the result JSON carries the promoted leg's
    # sentinel verdict block plus a verdict per completed leg, and the
    # per-run ledger recorded every leg with the injected-device-loss
    # weather on the retried one.
    assert final["sentinel"]["verdict"] in (
        "improved", "flat", "regressed", "attachment_transient",
        "insufficient_history")
    assert set(final["all_verdicts"]) == set(final["all_variants"])
    ledger_path = art / "obs" / "ledger.jsonl"
    assert ledger_path.exists()
    rows = [json.loads(ln) for ln in
            ledger_path.read_text().splitlines()]
    rows = [r for r in rows if r.get("run_id") == final["run_id"]]
    legs = [r for r in rows if r.get("kind") == "bench_leg"]
    assert len(legs) == final["legs_completed"]
    # ISSUE 14: every completed leg ALSO landed one cost_attribution
    # record (measured step time x bytes-moved model).
    cost = [r for r in rows if r.get("kind") == "cost_attribution"]
    assert len(cost) == final["legs_completed"]
    # Leg 2 survived a retried device loss: its fingerprint records the
    # weather; the other legs were clean.
    healths = [r["fingerprint"]["attachment_health"] for r in legs]
    assert "flaky" in healths and "healthy" in healths

    # ...and obs_report renders a report straight from this run's obs
    # dir: per-leg phase rows, the step-time percentile table, and the
    # retry narrative, all from one directory.
    run_dir = art / "obs" / final["run_id"]
    assert run_dir.is_dir()
    report = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "obs_report.py"),
         str(run_dir)],
        capture_output=True, text=True, cwd=REPO, timeout=60,
    )
    assert report.returncode == 0, report.stderr[-2000:]
    assert final["run_id"] in report.stdout
    assert "bench/leg" in report.stdout
    assert "step_time_ms" in report.stdout
    assert "failure" in report.stdout and "backoff" in report.stdout

    # Health journal: init timeout on child 1; child 2 came up, lost the
    # device on a leg, probed, backed off, and retried. Both attempts
    # share the parent-minted run id, so ONE journal holds the story.
    events = _health_events(art, "fm_kaggle")
    names = [e["event"] for e in events]
    assert "backend_init_timeout" in names
    assert "backend_init_up" in names
    assert "failure" in names and "backoff" in names
    fail = next(e for e in events if e["event"] == "failure")
    assert "InjectedDeviceLoss" in fail["error"]
    assert fail["retryable"] is True

    # Phase 2: --resume-sweep restart with a truncated artifact (as if
    # the window died after leg 1) runs ONLY the remaining legs, warm
    # through the compile cache both runs inherit (tests/conftest.py
    # places one for the session).
    sweep_path = art / "sweep_fm_kaggle.jsonl"
    records = sweep_path.read_text().strip().splitlines()
    n_total = len(records)
    assert n_total >= 2
    sweep_path.write_text(records[0] + "\n")
    kept = json.loads(records[0])

    proc2 = _run_bench(
        common + ["--resume-sweep", "--attempts", "1",
                  "--attempt-timeout", "240", "--total-deadline", "300"],
        env={}, timeout=330,
    )
    assert proc2.returncode == 0, proc2.stderr[-2000:]
    final2 = _last_json(proc2.stdout)
    assert final2["value"] is not None
    assert final2["resumed_legs"] == 1
    assert final2["legs_completed"] == n_total
    assert kept["variant"] in final2["all_variants"]
    # The banked leg's sentinel verdict rides the resume (reloaded from
    # its sweep record, never re-judged against its own history).
    assert final2["all_verdicts"][kept["variant"]] == kept["verdict"]
    # Only the remaining legs were re-measured and appended.
    new_records = [json.loads(ln) for ln in
                   sweep_path.read_text().strip().splitlines()]
    assert len(new_records) == n_total
    assert [r["variant"] for r in new_records].count(kept["variant"]) == 1


def test_error_artifact_carries_last_measured(tmp_path):
    """A round where EVERY attempt dies before measuring still emits a
    machine-readable best-known headline (the satellite: VERDICT r5
    next-round #1 — a dead-attachment round must degrade, not null)."""
    proc = _run_bench(
        ["--attempts", "2", "--attempt-timeout", "60",
         "--total-deadline", "110", "--artifacts-dir",
         str(tmp_path / "art")],
        env={
            "FM_SPARK_FAULTS":
                "backend_init@1=exit:3;backend_init@2=exit:3",
            "FM_SPARK_FAULTS_STATE": str(tmp_path / "faults_state.json"),
        },
        timeout=150,
    )
    assert proc.returncode == 1
    final = _last_json(proc.stdout)
    assert final["value"] is None
    assert "rc=3" in final["error"]
    last = final["last_measured"]
    # The carried record is MEASURED.json's headline, provenance intact.
    assert last["value"] > 0 and last["stale"] is True
    assert last["variant"] and last["date"]
    assert "MEASURED.json" in last["provenance"]


def test_resume_sweep_never_loads_degraded_leg_records(tmp_path):
    """A degraded (shrunk-denominator) leg record must not ride
    --resume-sweep into a fresh, undegraded payload: its inflated
    per-chip rate would win max() without the degraded stamp and slip
    past the MEASURED.json keep-best guard. Degraded legs re-measure."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_mod", BENCH)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    art = tmp_path / "art"
    art.mkdir()
    records = [
        {"variant": "a", "value": 100.0, "device": "cpu", "ts": 5.0,
         "dt_s": 1.0, "loss": 0.5},
        {"variant": "b", "value": 400.0, "device": "cpu", "ts": 6.0,
         "dt_s": 1.0, "loss": 0.5, "degraded": True, "chips": 2},
    ]
    with open(art / "sweep_fm.jsonl", "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")
    out = bench._completed_legs(str(art), "fm", {"a", "b"},
                                device_kind="cpu")
    assert set(out) == {"a"}  # the degraded leg is re-measured


def test_parent_classifies_permanent_and_stops_early(tmp_path):
    """ISSUE 4 satellite: identical consecutive child failures (the
    BENCH_r05 rc=3 run) classify PERMANENT — the parent stops burning
    its deadline on further attempts/backoffs and the error JSON
    surfaces ``permanent: true``."""
    proc = _run_bench(
        ["--attempts", "5", "--attempt-timeout", "60",
         "--total-deadline", "240",
         "--artifacts-dir", str(tmp_path / "art")],
        env={
            "FM_SPARK_FAULTS": ";".join(
                f"backend_init@{i}=exit:3" for i in range(1, 6)),
            "FM_SPARK_FAULTS_STATE": str(tmp_path / "faults_state.json"),
        },
        timeout=280,
    )
    assert proc.returncode == 1
    final = _last_json(proc.stdout)
    assert final["value"] is None
    assert final["permanent"] is True
    # Stopped at the classification threshold (3 identical), not the
    # attempt budget (5): attempts 4 and 5 never ran.
    assert "classified permanent after 3" in final["error"]
    assert "attempt 4" not in final["error"]
    assert "skipping backoff" in proc.stderr  # 2-identical probe fast path


def test_elastic_degraded_sweep_completes_on_shrunk_mesh(tmp_path):
    """ISSUE 4 acceptance: an injected PERMANENT device loss (three
    identical consecutive failures on the leg) with ``--elastic`` on a
    forced 8-device CPU host completes the measurement on a shrunk mesh
    and emits a valid result JSON with ``degraded: true`` and per-chip
    throughput re-normalized to the 4 survivors — instead of an
    error-only artifact."""
    art = tmp_path / "art"
    proc = _run_bench(
        ["--model", "fm_kaggle", "--batch", "128", "--steps", "2",
         "--elastic", "--max-shrinks", "2",
         "--attempts", "1", "--attempt-timeout", "300",
         "--total-deadline", "420", "--artifacts-dir", str(art)],
        env={
            "FM_SPARK_FAULTS":
                "sweep_leg@1=device_loss;sweep_leg@2=device_loss;"
                "sweep_leg@3=device_loss",
            "FM_SPARK_FAULTS_STATE": str(tmp_path / "faults_state.json"),
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        },
        timeout=460,
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    final = _last_json(proc.stdout)
    assert final["value"] is not None and final["value"] > 0
    assert final.get("error") is None
    assert final["degraded"] is True
    assert final["chips"] == 4 and final["shrinks"] == 1

    # The health journal narrates the whole degradation: the three
    # identical failures, the shrink 8 -> 4, and the re-armed breaker.
    events = _health_events(art, "fm_kaggle")
    names = [e["event"] for e in events]
    assert names.count("failure") == 3
    assert "supervisor_reset" in names
    shrink = next(e for e in events if e["event"] == "mesh_shrink")
    assert shrink["from_chips"] == 8 and shrink["to_chips"] == 4

    # The per-leg sweep record carries the degraded provenance, and the
    # rate is normalized per SURVIVING chip.
    with open(art / "sweep_fm_kaggle.jsonl") as f:
        rec = json.loads(f.readline())
    assert rec["degraded"] is True and rec["chips"] == 4
    # value == steps*batch/dt/4 survivors. dt_s is persisted rounded to
    # 3 decimals and a warm CPU leg can run in single-digit ms, so the
    # bound is loose — it only needs to rule out the WRONG denominator
    # (a /8 normalization would miss by a factor of 2).
    assert abs(rec["value"] * 4 * rec["dt_s"] / (2 * 128) - 1) < 0.25


def test_retried_leg_never_double_appends_ledger_record(tmp_path):
    """ISSUE 9 crash window: an attempt can die AFTER the sentinel
    appended a leg's ledger record but BEFORE _persist_incremental
    banked it — the retried (--resume-sweep, like every parent
    respawn) attempt then re-measures the leg. The re-measured rate
    must be judged WITHOUT appending a duplicate (run_id, variant)
    row it would then be judged against."""
    from fm_spark_tpu.obs import ledger as lg

    art = tmp_path / "art"
    run_id = "20260801-000000-ptest"
    label = "float32/scatter_add/cd-bf16/b128"
    metric = "kaggle_fm_rank32_1Mfeat_samples_per_sec_per_chip"
    led = lg.PerfLedger(str(art / "obs" / "ledger.jsonl"))
    led.append({
        "kind": "bench_leg", "leg": metric, "run_id": run_id,
        "variant": label, "value": 31000.0, "unit": "samples/sec/chip",
        "sentinel": {"verdict": "insufficient_history",
                     "reason": "aborted-attempt record",
                     "n_history": 0, "median": None, "mad": None,
                     "z": None, "cohort": "exact"},
        "fingerprint": lg.measurement_fingerprint(
            variant=label, model="fm_kaggle", batch=128, steps=2),
    })
    proc = _run_bench(
        ["--fast-first", "--model", "fm_kaggle", "--batch", "128",
         "--steps", "2", "--attempts", "1", "--attempt-timeout", "300",
         "--total-deadline", "380", "--artifacts-dir", str(art),
         "--run-id", run_id, "--resume-sweep"],
        env={}, timeout=420,
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    final = _last_json(proc.stdout)
    rows = [json.loads(ln) for ln in
            (art / "obs" / "ledger.jsonl").read_text().splitlines()]
    mine = [r for r in rows if r.get("run_id") == run_id
            and r.get("variant") == label
            and r.get("kind") == "bench_leg"]
    assert len(mine) == 1, "duplicate (run_id, variant) ledger record"
    # The cost_attribution append rides the same dedup (ISSUE 14): a
    # resumed leg never lands a second cost record either.
    cost_mine = [r for r in rows if r.get("run_id") == run_id
                 and r.get("variant") == label
                 and r.get("kind") == "cost_attribution"]
    assert len(cost_mine) <= 1, "duplicate cost_attribution record"
    # The re-measured rate was judged fresh (against a history of just
    # the aborted attempt's row — insufficient) without re-appending.
    assert final["all_verdicts"][label] == "insufficient_history"
    # The OTHER legs were measured fresh and appended normally.
    others = [r for r in rows if r.get("run_id") == run_id
              and r.get("variant") != label
              and r.get("kind") == "bench_leg"]
    assert len(others) == final["legs_completed"] - 1


@pytest.mark.slow
def test_sigterm_mid_sweep_salvages_with_faults_active(tmp_path):
    """The SIGTERM fault injection composes with the salvage path: the
    `sigterm` action fired from INSIDE the child mid-sweep must still
    leave the parent's salvaged result line and an exit 0 (the
    fast-first SIGTERM contract, driven deterministically by the fault
    layer instead of an external kill)."""
    art = tmp_path / "art"
    proc = _run_bench(
        ["--fast-first", "--model", "fm_kaggle", "--batch", "128",
         "--steps", "2", "--artifacts-dir", str(art),
         "--attempts", "1", "--attempt-timeout", "300",
         "--total-deadline", "400"],
        env={
            # Kill the PARENT (the process group leader of the pipeline
            # the driver would kill) after the child's 2nd leg starts;
            # the child's own stdout already carried leg 1's line.
            "FM_SPARK_FAULTS": "sweep_leg@2=sigterm",
            "FM_SPARK_FAULTS_STATE": str(tmp_path / "faults_state.json"),
        },
        timeout=430,
    )
    # The sigterm lands in the CHILD process (the injection point runs
    # there), which dies without a further result line; the parent sees
    # a child death after leg 1 completed and salvages it.
    final = _last_json(proc.stdout)
    assert final["value"] is not None and final["value"] > 0
    assert (art / "keepbest_fm_kaggle.json").exists()
