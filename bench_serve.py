#!/usr/bin/env python
"""Serving latency/throughput ladder: p50/p99 + QPS/chip, sentinel-gated.

The measurement half of the ISSUE 12 serving runtime. Runs the
production :class:`fm_spark_tpu.serve.PredictEngine` through a ladder
of request sizes — batch-1 (pure latency) up through bucket-max (pure
throughput) — plus the two serving-specific legs no training bench
covers:

- **cold vs warm cache**: warmup is timed with compile-cache stats
  around it, so "a warm process never compiles on the request path" is
  a measured number (``fresh_compiles_after_warmup`` must be 0), not a
  claim;
- **reload-under-load**: a writer thread advances a real checkpoint
  chain while closed-loop requests flow; every response is checked for
  generation uniformity (the no-torn-swap invariant), and the run is
  held to :func:`fm_spark_tpu.resilience.chaos.audit_serve_events`.

Every ladder rung lands in the PR-9 perf ledger as a ``serve_bench``
record (full measurement fingerprint, p50/p99 + QPS/chip) and is judged
by the sentinel against its own cohort — serving legs have their own
leg names, so they never share a trailing band with training legs. The
bucket-max rung is the serving headline: on an improved/flat verdict
it promotes into MEASURED.json's ``serving`` entry through the same
keep-best gate bench.py uses (a CPU smoke can seed the entry but never
clobber a TPU-attachment number).

Usage::

    python bench_serve.py                      # full CPU/TPU ladder
    python bench_serve.py --smoke              # bounded tier-1 leg
    python bench_serve.py --buckets 1,8,64,512 --requests 500
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

_REPO = os.path.dirname(os.path.abspath(__file__))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def _percentile(sorted_vals: list[float], p: float) -> float:
    """Exact interpolated percentile over a SORTED sample (the ladder
    keeps every latency, so no histogram coarseness here)."""
    if not sorted_vals:
        return float("nan")
    if len(sorted_vals) == 1:
        return sorted_vals[0]
    rank = p * (len(sorted_vals) - 1)
    lo = int(rank)
    hi = min(lo + 1, len(sorted_vals) - 1)
    frac = rank - lo
    return sorted_vals[lo] * (1 - frac) + sorted_vals[hi] * frac


def _build_engine(args):
    import jax

    from fm_spark_tpu import models
    from fm_spark_tpu.serve import PredictEngine

    spec = models.FieldFMSpec(
        num_features=args.fields * args.bucket, rank=args.rank,
        num_fields=args.fields, bucket=args.bucket, init_std=0.05,
    )
    params = spec.init(jax.random.key(0))
    engine = PredictEngine(
        spec, params, buckets=args.bucket_list,
        latency_budget_ms=args.latency_budget_ms,
    )
    return spec, params, engine


def _run_rung(engine, rows: int, requests: int, rng) -> dict:
    """One ladder rung, two traffic shapes:

    - **trickle** (sequential closed loop) measures what one caller
      sees — p50/p99 include the coalescer's latency-budget wait, so
      the percentiles are honest for the configured budget;
    - **burst** (all requests offered concurrently) measures
      throughput with the micro-batcher actually coalescing — QPS and
      rows/s come from here.
    """
    nnz = engine.nnz
    bucket = engine.spec.bucket
    ids = rng.integers(0, bucket, (rows, nnz)).astype("int32")
    vals = rng.random((rows, nnz)).astype("float32")
    lat = []
    for _ in range(requests):
        t0 = time.perf_counter()
        engine.predict(ids, vals)
        lat.append((time.perf_counter() - t0) * 1e3)
    lat.sort()
    t_burst = time.perf_counter()
    futures = [engine.submit(ids, vals) for _ in range(requests)]
    for f in futures:
        f.result(120)
    burst_s = time.perf_counter() - t_burst
    return {
        "rows_per_request": rows,
        "requests": requests,
        "p50_ms": round(_percentile(lat, 0.50), 4),
        "p99_ms": round(_percentile(lat, 0.99), 4),
        "mean_ms": round(sum(lat) / len(lat), 4),
        "qps": round(requests / burst_s, 2),
        "rows_per_sec": round(rows * requests / burst_s, 2),
        "burst_s": round(burst_s, 3),
    }


def _reload_drill(args, spec, params, engine, run_dir, journal_path
                  ) -> dict:
    """Reload-under-load: a writer advances a real checkpoint chain
    while closed-loop requests flow. Identical request rows per call
    make generation mixing visible: with generation-k params scaled by
    (k+1), every response must be row-uniform (one generation) and the
    observed value set a subset of the planted ones."""
    import numpy as np

    import jax
    from fm_spark_tpu.checkpoint import Checkpointer
    from fm_spark_tpu.resilience import chaos
    from fm_spark_tpu.serve import ReloadFollower
    from fm_spark_tpu.utils.logging import EventLog, read_events

    chain_dir = os.path.join(run_dir, "serve_chain")
    journal = EventLog(journal_path)
    # The drill's engine journals its swaps into the SAME stream the
    # auditor reads — without this, the no-torn-swap monotonicity
    # audit would iterate over zero serve_swap events and be vacuous.
    engine.journal = journal
    gens = args.reload_gens
    scale = lambda k: jax.tree_util.tree_map(
        lambda a: a * float(k + 1), params)

    ck = Checkpointer(chain_dir, save_every=1, async_save=False)
    ck.save(1, scale(0), {}, None, force=True)
    ck.wait()

    follower = ReloadFollower(engine, chain_dir, poll_s=args.poll_s,
                              journal=journal, opt_state_example={})
    assert follower.poll_once() == "swapped"  # generation 1 installed

    rng = np.random.default_rng(7)
    nnz = engine.nnz
    ids = rng.integers(0, spec.bucket, (4, nnz)).astype("int32")
    ids[:] = ids[:1]  # identical rows → per-generation-constant scores
    vals = np.ones((4, nnz), "float32")

    stop = threading.Event()

    def writer():
        for k in range(1, gens):
            time.sleep(args.reload_write_gap_s)
            ck.save(k + 1, scale(k), {}, None, force=True)
            ck.wait()
        stop.set()

    wt = threading.Thread(target=writer, daemon=True)
    follower.start()
    wt.start()
    torn = 0
    responses = 0
    t0 = time.perf_counter()
    while not stop.is_set() and time.perf_counter() - t0 < 60:
        out = engine.predict(ids, vals)
        responses += 1
        if not np.all(out == out[0]):
            torn += 1  # rows from different generations in ONE response
    wt.join(timeout=30)
    # Convergence: the follower must reach the chain tip (bounded
    # staleness after the writer stops).
    deadline = time.monotonic() + 30
    while (engine.generation().step < gens
           and time.monotonic() < deadline):
        time.sleep(args.poll_s)
    follower.stop()
    ck.close()
    from fm_spark_tpu import obs

    final_staleness = int(obs.gauge("serve/staleness_steps").value or 0)
    violations = chaos.audit_serve_events(
        read_events(journal_path), final_staleness=final_staleness,
        staleness_bound=0)
    if torn:
        violations.append({"invariant": "no_torn_swap",
                           "detail": f"{torn} mixed-generation "
                                     "response(s) observed"})
    return {
        "generations": gens,
        "responses_under_load": responses,
        "swaps": follower.reloads,
        "reload_failures": follower.failures,
        "final_step": engine.generation().step,
        "final_staleness_steps": final_staleness,
        "torn_responses": torn,
        "violations": violations,
    }


def _fleet_stats_delta(before: dict, after: dict) -> dict:
    return {k: int(after.get(k) or 0) - int(before.get(k) or 0)
            for k in ("accepted", "answered", "shed", "shed_queue",
                      "shed_deadline", "rejected", "timeout",
                      "failed", "retries")}


def _fleet_ladder(args, run_dir: str
                  ) -> tuple[list[dict], list[dict]]:
    """Fleet rungs (ISSUE 17): aggregate QPS, p99 under shed, and
    replica-loss recovery time for an ``--fleet N`` replica fleet
    behind the production front door, driven by the seeded traffic
    replayer. Each rung is its own ``serve_bench`` leg — its own
    sentinel cohort, never compared against the single-engine ladder
    (a fleet multiplies processes, not chips) — and fleet rungs NEVER
    promote into MEASURED.json. Every rung's tap + counter delta is
    held to :func:`chaos.audit_fleet` (exactly-once, closed books,
    shed accounting)."""
    import jax

    from fm_spark_tpu import models
    from fm_spark_tpu.resilience import chaos
    from fm_spark_tpu.serve import loadgen
    from fm_spark_tpu.serve.fleet import Fleet
    from fm_spark_tpu.serve.frontdoor import (
        AdmissionController,
        FrontDoor,
    )
    from fm_spark_tpu.utils.logging import EventLog, read_events

    n = args.fleet
    fleet_dir = os.path.join(run_dir, "fleet")
    spec = models.FieldFMSpec(
        num_features=args.fields * args.bucket, rank=args.rank,
        num_fields=args.fields, bucket=args.bucket, init_std=0.05)
    params = spec.init(jax.random.key(0))
    model_dir = os.path.join(fleet_dir, "model")
    models.save_model(model_dir, spec, params)
    fleet = Fleet(
        model_dir, n_replicas=n,
        work_dir=os.path.join(fleet_dir, "work"),
        journal=EventLog(os.path.join(run_dir, "fleet_health.jsonl")),
        buckets=args.fleet_buckets,
        latency_budget_ms=args.latency_budget_ms)
    fleet.start()
    door = FrontDoor(fleet,
                     admission=AdmissionController(
                         service_est_ms=2.0)).start()
    rows = max(int(b) for b in args.fleet_buckets.split(","))
    kw = dict(nnz=args.fields, num_features=spec.num_features)
    rungs: list[dict] = []
    violations: list[dict] = []
    try:
        # ---- rung 1: aggregate QPS (comfortable deadlines, no shed)
        sched = loadgen.make_schedule(
            "diurnal", 0, duration_s=args.fleet_duration_s,
            base_rps=args.fleet_rps, rows=rows, deadline_ms=8000.0)
        tap = os.path.join(fleet_dir, "tap_qps.jsonl")
        before = door.stats()
        t0 = time.perf_counter()
        loadgen.run_loadgen("127.0.0.1", door.port, sched, tap,
                            threads=16, **kw)
        elapsed = time.perf_counter() - t0
        counters = _fleet_stats_delta(before, door.stats())
        violations += chaos.audit_fleet(
            read_events(tap), counters,
            expected_requests=sched.n_requests)
        s = loadgen.summarize_tap(tap)
        n_ok = s["by_outcome"].get("ok", 0)
        rungs.append({
            "leg": f"fleet_qps_n{n}",
            "requests": sched.n_requests, "ok": n_ok,
            "value": round(n_ok * rows / elapsed, 2),
            "qps": round(n_ok / elapsed, 2),
            "p50_ms": s["ok_p50_ms"], "p99_ms": s["ok_p99_ms"],
            "counters": counters,
        })

        # ---- rung 2: p99 under shed — a retry storm with an
        # unpayable SLO, so admission sheds BEFORE the coalescer;
        # the rung is only honest if the clients' observed sheds
        # match the door's books (audit_fleet's shed_accounting).
        sched = loadgen.make_schedule(
            "retry_storm", 1, duration_s=args.fleet_duration_s,
            base_rps=args.fleet_rps * 2, rows=rows,
            deadline_ms=args.fleet_shed_deadline_ms)
        tap = os.path.join(fleet_dir, "tap_shed.jsonl")
        before = door.stats()
        loadgen.run_loadgen("127.0.0.1", door.port, sched, tap,
                            threads=16, **kw)
        counters = _fleet_stats_delta(before, door.stats())
        violations += chaos.audit_fleet(
            read_events(tap), counters,
            expected_requests=sched.n_requests)
        s = loadgen.summarize_tap(tap)
        p99 = s["ok_p99_ms"]
        rungs.append({
            "leg": f"fleet_p99_shed_n{n}",
            "requests": sched.n_requests,
            "ok": s["by_outcome"].get("ok", 0),
            # Sentinel semantics: lower value = regressed, so the
            # rung's value is answers-per-second at p99 (faster p99
            # under shed pressure = better).
            "value": round(1e3 / p99, 2) if p99 == p99 and p99 > 0
            else 0.0,
            "p99_ms": p99,
            "shed": counters["shed"],
            "shed_fired": counters["shed"] > 0,
            "counters": counters,
        })

        # ---- rung 3: recovery time after a replica SIGKILL under
        # load — kill to every live replica back through the
        # readiness gate.
        sched = loadgen.make_schedule(
            "diurnal", 2, duration_s=max(1.0, args.fleet_duration_s),
            base_rps=args.fleet_rps, rows=rows, deadline_ms=8000.0)
        tap = os.path.join(fleet_dir, "tap_recovery.jsonl")
        before = door.stats()
        lg = threading.Thread(
            target=loadgen.run_loadgen,
            args=("127.0.0.1", door.port, sched, tap),
            kwargs=dict(threads=8, **kw), daemon=True)
        lg.start()
        time.sleep(0.3 * sched.duration_s)
        with fleet._lock:
            ready = [r for r in fleet.replicas
                     if r.state == "ready" and r.proc is not None]
        killed = None
        t_kill = time.monotonic()
        if ready:
            killed = ready[0].idx
            os.kill(ready[0].proc.pid, 9)
        lg.join()
        recovery_s = None
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            h = fleet.healthz()
            live = [r for r in h["replicas"]
                    if r["state"] not in ("retired", "parked")]
            if live and all(r["state"] == "ready" for r in live):
                recovery_s = round(time.monotonic() - t_kill, 3)
                break
            time.sleep(0.05)
        counters = _fleet_stats_delta(before, door.stats())
        violations += chaos.audit_fleet(
            read_events(tap), counters,
            expected_requests=sched.n_requests)
        if recovery_s is None:
            violations.append({
                "invariant": "staleness_bounded",
                "detail": "fleet never re-admitted a ready replica "
                          "set after the SIGKILL drill"})
        rungs.append({
            "leg": f"fleet_recovery_n{n}",
            "requests": sched.n_requests,
            "killed_replica": killed,
            "recovery_s": recovery_s,
            # 1/recovery so the sentinel's lower-is-regressed rule
            # reads correctly (slower recovery = lower value).
            "value": (round(1.0 / recovery_s, 4)
                      if recovery_s else 0.0),
            "counters": counters,
        })
    finally:
        door.stop()
    return rungs, violations


def _promote(headline: dict, rate_per_chip: float, device: str,
             args, run_ok: bool) -> tuple[bool, str]:
    """The serving keep-best gate (mirrors bench.py's _emit_final
    rules, minus the TPU-only clause — serving has no carried TPU
    number yet, so a first CPU measurement may SEED the entry; it may
    never replace a different-attachment one, and a TPU number always
    outranks a CPU seed). ``run_ok`` is the ladder's own verdict
    (zero fresh compiles after warmup, reload drill green): a run
    that violated its invariants measured the wrong program and its
    rungs stay out of MEASURED.json — the PERF.md round-16 rule."""
    from fm_spark_tpu.measured import load_measured, update_entry
    from fm_spark_tpu.obs import keepbest_allowed

    if not run_ok:
        return False, ("ladder invariants violated (fresh compiles "
                       "after warmup, or a reload-drill violation) — "
                       "rungs stay out of MEASURED.json")
    if not keepbest_allowed(headline.get("sentinel")):
        return False, (
            f"sentinel verdict "
            f"{(headline.get('sentinel') or {}).get('verdict')!r} — "
            "only improved/flat promote")
    try:
        prev_entry = load_measured(args.measured_path).get("serving")
    except (OSError, ValueError):
        prev_entry = None
    is_tpu = "tpu" in device.lower()
    if prev_entry is not None:
        prev_tpu = "tpu" in str(prev_entry.get("attachment", "")).lower()
        if prev_tpu and not is_tpu:
            return False, ("recorded serving rate is a TPU "
                           "measurement; a CPU run never clobbers it")
        same_class = prev_tpu == is_tpu
        if same_class and rate_per_chip <= prev_entry[
                "rate_samples_per_sec_per_chip"]:
            return False, (
                f"measured {rate_per_chip:.0f} <= recorded "
                f"{prev_entry['rate_samples_per_sec_per_chip']:.0f}")
    update_entry(
        "serving",
        rate=rate_per_chip,
        variant=headline["variant"],
        source="bench_serve.py ladder, metric "
               "serve_scored_rows_per_sec_per_chip",
        attachment=device,
        date=time.strftime("%Y-%m-%d", time.gmtime()),
        path=args.measured_path,
    )
    return True, "MEASURED.json serving entry updated"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="bench_serve")
    ap.add_argument("--buckets", default="1,8,64,512",
                    help="comma-separated padded-batch buckets (the "
                         "ladder runs one rung per bucket)")
    ap.add_argument("--requests", type=int, default=300,
                    help="closed-loop requests per ladder rung")
    ap.add_argument("--latency-budget-ms", type=float, default=2.0,
                    dest="latency_budget_ms")
    ap.add_argument("--fields", type=int, default=16)
    ap.add_argument("--bucket", type=int, default=4096,
                    help="per-field hash bucket (model shape)")
    ap.add_argument("--rank", type=int, default=16)
    ap.add_argument("--reload-gens", type=int, default=4,
                    dest="reload_gens",
                    help="checkpoint generations the reload-under-load "
                         "drill publishes")
    ap.add_argument("--reload-write-gap-s", type=float, default=0.3,
                    dest="reload_write_gap_s")
    ap.add_argument("--poll-s", type=float, default=0.05, dest="poll_s")
    ap.add_argument("--skip-reload-drill", action="store_true",
                    dest="skip_reload_drill")
    ap.add_argument("--fleet", type=int, default=0,
                    help="also run the N-replica fleet rungs "
                         "(aggregate QPS, p99 under shed, replica-"
                         "loss recovery) behind the front door")
    ap.add_argument("--fleet-buckets", default="1,8",
                    dest="fleet_buckets",
                    help="padded-batch buckets for fleet replicas "
                         "(kept small: replica warmup is per-process)")
    ap.add_argument("--fleet-rps", type=float, default=80.0,
                    dest="fleet_rps",
                    help="base offered load for the fleet rungs")
    ap.add_argument("--fleet-duration-s", type=float, default=1.5,
                    dest="fleet_duration_s")
    ap.add_argument("--fleet-shed-deadline-ms", type=float,
                    default=120.0, dest="fleet_shed_deadline_ms",
                    help="base deadline for the shed rung (the retry-"
                         "storm shape tightens it 4x — unpayable by "
                         "construction)")
    ap.add_argument("--slo-ms", type=float, default=None, dest="slo_ms",
                    help="arm the serve_request watchdog at this "
                         "deadline (overrun = structured HangDetected)")
    ap.add_argument("--art-dir", default=os.path.join(_REPO, "artifacts"),
                    dest="art_dir")
    ap.add_argument("--measured-path", default=None, dest="measured_path",
                    help="MEASURED.json to promote into (default: the "
                         "repo's)")
    ap.add_argument("--run-id", default=None, dest="run_id")
    ap.add_argument("--out", default=None,
                    help="also write the result JSON here")
    ap.add_argument("--smoke", action="store_true",
                    help="bounded CPU smoke: small model, short rungs "
                         "(the tier-1 leg)")
    args = ap.parse_args(argv)

    if args.smoke:
        args.buckets = "1,8,32"
        args.requests = min(args.requests, 40)
        args.fields = min(args.fields, 8)
        args.bucket = min(args.bucket, 512)
        args.rank = min(args.rank, 8)
        args.reload_gens = min(args.reload_gens, 3)
        args.reload_write_gap_s = min(args.reload_write_gap_s, 0.2)
        args.fleet_duration_s = min(args.fleet_duration_s, 1.0)
        args.fleet_rps = min(args.fleet_rps, 50.0)
    args.bucket_list = tuple(sorted(
        {int(b) for b in args.buckets.split(",") if b}))

    if args.fleet > 0:
        from fm_spark_tpu.serve.fleet import refuse_on_tpu

        refuse_on_tpu(f"bench_serve.py --fleet {args.fleet}")

    from fm_spark_tpu import obs
    from fm_spark_tpu.resilience import watchdog
    from fm_spark_tpu.utils import compile_cache

    run_id = args.run_id or obs.new_run_id()
    run_dir = os.path.join(args.art_dir, "obs", run_id)
    obs.configure(run_dir, run_id=run_id)
    cache_dir = compile_cache.enable()
    if args.slo_ms is not None:
        watchdog.configure({"serve_request": args.slo_ms / 1e3},
                           action="raise")

    import numpy as np

    import jax

    device = jax.devices()[0].device_kind
    n_chips = 1  # the engine dispatches on one chip (ROADMAP item 2
    # is the multi-chip serving story)

    spec, params, engine = _build_engine(args)
    cold_stats = compile_cache.cache_stats()
    warm = engine.warmup()
    warm_start = warm["fresh_compiles"] == 0

    rng = np.random.default_rng(0)
    rungs = [_run_rung(engine, rows, args.requests, rng)
             for rows in args.bucket_list]
    after_stats = compile_cache.cache_stats()
    fresh_after_warmup = (after_stats["misses"]
                          - warm["cache_stats"]["misses"])

    journal_path = os.path.join(run_dir, "serve_health.jsonl")
    reload_drill = None
    if not args.skip_reload_drill:
        reload_drill = _reload_drill(args, spec, params, engine,
                                     run_dir, journal_path)
    engine.close()

    fleet_rungs: list[dict] = []
    fleet_violations: list[dict] = []
    if args.fleet > 0:
        fleet_rungs, fleet_violations = _fleet_ladder(args, run_dir)

    # ------------------------------------------------- ledger + sentinel
    from fm_spark_tpu.obs import (
        PerfLedger,
        Sentinel,
        default_ledger_path,
        measurement_fingerprint,
    )
    from fm_spark_tpu.obs.ledger import runtime_versions

    ledger = PerfLedger(default_ledger_path(args.art_dir))
    sentinel = Sentinel(ledger)
    versions = runtime_versions()
    model_variant = f"fm{args.fields}x{args.bucket}r{args.rank}"
    for rung in rungs:
        b = rung["rows_per_request"]
        variant = (f"serve/{model_variant}/b{b}"
                   f"/budget{args.latency_budget_ms:g}ms")
        rung["variant"] = variant
        fingerprint = measurement_fingerprint(
            variant=variant, model="field_fm", batch=b,
            rank=args.rank,
            extra={"buckets": list(args.bucket_list),
                   "latency_budget_ms": args.latency_budget_ms,
                   "nnz": args.fields},
            device_kind=device, n_chips=n_chips,
            jax_version=versions["jax_version"],
            libtpu_version=versions["libtpu_version"],
        )
        rung["sentinel"] = sentinel.observe({
            "kind": "serve_bench",
            "leg": f"serve_qps_b{b}",
            "run_id": run_id,
            "fingerprint": fingerprint,
            "value": rung["rows_per_sec"] / n_chips,
            "p50_ms": rung["p50_ms"],
            "p99_ms": rung["p99_ms"],
            "qps": rung["qps"],
            "variant": variant,
            "warm_start": warm_start,
            "fresh_compiles_after_warmup": fresh_after_warmup,
        })

    # Fleet rungs: own leg names = own sentinel cohorts. They ride
    # the same ledger kind but are NEVER candidates for promotion —
    # the promotion gate below only ever sees the single-engine
    # headline.
    for rung in fleet_rungs:
        variant = (f"serve/fleet{args.fleet}/{model_variant}"
                   f"/{rung['leg']}")
        rung["variant"] = variant
        fingerprint = measurement_fingerprint(
            variant=variant, model="field_fm",
            batch=max(int(b) for b in args.fleet_buckets.split(",")),
            rank=args.rank,
            extra={"n_replicas": args.fleet,
                   "fleet_buckets": args.fleet_buckets,
                   "latency_budget_ms": args.latency_budget_ms,
                   "nnz": args.fields},
            device_kind=device, n_chips=n_chips,
            jax_version=versions["jax_version"],
            libtpu_version=versions["libtpu_version"],
        )
        rung["sentinel"] = sentinel.observe({
            "kind": "serve_bench",
            "leg": rung["leg"],
            "run_id": run_id,
            "fingerprint": fingerprint,
            "value": rung["value"],
            "variant": variant,
            **{k: rung[k] for k in ("p99_ms", "recovery_s", "shed")
               if k in rung},
        })

    headline = rungs[-1]  # bucket-max rung = the throughput headline
    rate_per_chip = round(headline["rows_per_sec"] / n_chips, 2)
    run_ok = (fresh_after_warmup == 0
              and not (reload_drill and reload_drill["violations"])
              and not fleet_violations)
    promoted, promote_reason = _promote(headline, rate_per_chip,
                                        device, args, run_ok)

    obs.export_snapshot()
    result = {
        "bench": "serve",
        "run_id": run_id,
        "obs_dir": run_dir,
        "device": device,
        "chips": n_chips,
        "buckets": list(args.bucket_list),
        "latency_budget_ms": args.latency_budget_ms,
        "compile_cache_dir": cache_dir,
        "warmup_s": warm["seconds"],
        "warm_start": warm_start,
        "fresh_compiles_at_warmup": warm["fresh_compiles"],
        "fresh_compiles_after_warmup": fresh_after_warmup,
        "rungs": rungs,
        "fleet": ({"n_replicas": args.fleet, "rungs": fleet_rungs,
                   "violations": fleet_violations}
                  if args.fleet > 0 else None),
        "reload_drill": reload_drill,
        "headline_rows_per_sec_per_chip": rate_per_chip,
        "measured_updated": promoted,
        "measured_reason": promote_reason,
    }
    line = json.dumps(result)
    print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    obs.shutdown()
    return 0 if run_ok else 1


if __name__ == "__main__":
    sys.exit(main())
