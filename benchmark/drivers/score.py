"""The scoring cells: ``PredictEngine(spec, params)`` at its defaults
(buckets, latency budget), fed by the benchmark's open-loop generator
through ``submit()`` — what an ad server calling the scorer in-process
sees. No model is loaded from disk: ``spec.init`` makes the parameters on
the device from the seed, in one jitted call, in the type they are served
in.

Set-up: parameters, ``warmup()`` (compiles every bucket), a seeded sample
of rows checked against the plain reference as logits, the run's
schedule, and ``warm_seconds`` of the same traffic. Then the window: the
schedule plays for ``--seconds``; a request unanswered ``grace_seconds``
after it is a failure. After it, a seeded share of the window's answers
is re-checked against the reference, which also proves each answer went
to its own request.
"""

from __future__ import annotations

import importlib
import time

import numpy as np

from benchmark import loadgen, synthetic
from benchmark.drivers.registry import registry_config
from benchmark.harness import Context, Result, log, start_trace, trace_span

COUNTERS = ("serve.requests_total", "serve.batches_total",
            "serve.rows_total", "serve.padded_rows_total")
WARM_STREAM, WINDOW_STREAM, CHECK_STREAM = 1, 2, 3


def build(ctx: Context):
    """``(engine, scorer, pool_ids, pool_vals, notes)``: the engine warm,
    ``scorer(ids, vals)`` the reference's logits on the same parameters."""
    import jax
    import jax.numpy as jnp

    from fm_spark_tpu.serve import PredictEngine
    from fm_spark_tpu.utils import compile_cache

    config, mix = ctx.cell.config, ctx.cell.mix
    model = config["model"]
    clock = time.perf_counter
    t = [clock()]
    took = {}

    def lap(name):
        t.append(clock())
        took[name] = round(t[-1] - t[-2], 2)

    cfg = registry_config(config)
    spec = cfg.spec()
    notes = {"compile_cache": compile_cache.enable(), "build": took}
    lap("registry_s")
    params = jax.jit(spec.init)(jax.random.key(ctx.seed))
    jax.block_until_ready(params)
    lap("init_s")
    engine = PredictEngine(spec, params)
    lap("engine_s")
    warm = engine.warmup()
    notes["warmup"] = {k: warm[k] for k in ("buckets", "fresh_compiles")}
    lap("warmup_s")
    pool_ids, pool_vals = synthetic.zipf_pool(
        int(mix["ids"]["pool_rows"]), model["num_fields"], model["bucket"],
        ctx.seed)
    lap("pool_s")

    ref = importlib.import_module(f"benchmark.reference.{config['reference']}")
    rank = model["rank"]

    @jax.jit
    def _scores(params, ids, vals):
        rows = [params["vw"][f][ids[:, f]].astype(jnp.float32)
                for f in range(ids.shape[1])]
        return ref.scores(rows, params["w0"], vals, rank)

    block = int(mix["check_rows"])

    def scorer(ids, vals):
        """Reference logits, in fixed blocks so that one program serves
        every check."""
        out = []
        with jax.default_matmul_precision("highest"):
            for lo in range(0, len(ids), block):
                i, v = ids[lo:lo + block], vals[lo:lo + block]
                pad = block - len(i)
                if pad:
                    i = np.concatenate([i, np.zeros((pad, i.shape[1]), i.dtype)])
                    v = np.concatenate([v, np.zeros((pad, v.shape[1]), v.dtype)])
                out.append(np.asarray(_scores(params, i, v))[:block - pad])
        return np.concatenate(out)

    return engine, scorer, pool_ids, pool_vals, notes


def logit_error(probabilities, want) -> float:
    """``max |logit(p) - want| / max |want|`` (chip_smoke's measure: the
    logit of a float32 probability resolves the score to ~3e-7)."""
    p = np.asarray(probabilities, np.float64)
    got = np.log(p / (1.0 - p))
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _counters() -> dict:
    from fm_spark_tpu import obs

    return {name: obs.registry().peek(name) or 0.0 for name in COUNTERS}


def schedule(ctx: Context, seconds: float, stream: int,
             requests_per_s: float | None = None) -> loadgen.Schedule:
    mix = ctx.cell.mix
    return loadgen.make_schedule(
        requests_per_s=requests_per_s or float(mix["requests_per_s"]),
        seconds=seconds, seed=ctx.seed,
        schedule_seed=int(mix["schedule_seed"]), stream=stream,
        rows_per_request=mix["rows_per_request"],
        pool_rows=int(mix["ids"]["pool_rows"]))


def play_window(ctx: Context, engine, pool_ids, pool_vals,
                sched: loadgen.Schedule, traced_box: dict | None = None):
    """One played schedule with the counters and compile misses around
    it: ``(played, counter deltas)``."""
    from fm_spark_tpu.utils import compile_cache

    mix = ctx.cell.mix

    def during(t0):
        import jax

        after, length = trace_span(mix, sched.seconds)
        time.sleep(max(t0 + after - time.perf_counter(), 0.0))
        start_trace(ctx.trace_dir)
        t_on = time.perf_counter()
        time.sleep(length)
        t_off = time.perf_counter()
        jax.profiler.stop_trace()
        traced_box["seconds"] = t_off - t_on

    before, misses = _counters(), compile_cache.cache_stats()["misses"]
    played = loadgen.play(
        engine.submit, pool_ids, pool_vals, sched,
        grace_seconds=float(mix["grace_seconds"]),
        during=during if traced_box is not None else None)
    after = _counters()
    deltas = {k: after[k] - before[k] for k in COUNTERS}
    deltas["compile_misses"] = (compile_cache.cache_stats()["misses"]
                                - misses)
    return played, deltas


def window_stats(sched: loadgen.Schedule, played: loadgen.Played) -> dict:
    """Latency from the instant each request was DUE, rows answered
    inside the window, and how late the generator ran."""
    due = played.t0 + sched.due
    answered = ~np.isnan(played.done)
    latency_ms = (played.done - due)[answered] * 1e3
    late_ms = (played.sent - due)[~np.isnan(played.sent)] * 1e3
    in_window = answered & (played.done <= played.t0 + sched.seconds)
    return {
        "requests": len(sched),
        "answered": int(answered.sum()),
        "p50_ms": loadgen.percentile(latency_ms, 50),
        "p90_ms": loadgen.percentile(latency_ms, 90),
        "p99_ms": loadgen.percentile(latency_ms, 99),
        "max_ms": float(latency_ms.max()) if len(latency_ms) else None,
        "rows_per_s": float(sched.rows[in_window].sum()) / sched.seconds,
        "offered_rows_per_s": sched.total_rows / sched.seconds,
        "late_p99_ms": loadgen.percentile(late_ms, 99),
        "late_max_ms": float(late_ms.max()) if len(late_ms) else None,
        "backlog_at_end": int((~in_window).sum()),
    }


def run(ctx: Context) -> Result:
    mix = ctx.cell.mix
    t_build = time.perf_counter()
    engine, scorer, pool_ids, pool_vals, notes = build(ctx)
    t_check = time.perf_counter()
    notes["build"]["imports_s"] = round(
        t_check - t_build - sum(notes["build"].values()), 2)
    try:
        # Seeded sample of pool rows, straight through score(): logits
        # against the reference on the same parameters.
        rng = np.random.default_rng((ctx.seed, CHECK_STREAM))
        pick = rng.integers(0, len(pool_ids), int(mix["check_rows"]))
        sample_err = logit_error(
            np.concatenate([engine.score(pool_ids[pick[lo:lo + 512]],
                                         pool_vals[pick[lo:lo + 512]])
                            for lo in range(0, len(pick), 512)]),
            scorer(pool_ids[pick], pool_vals[pick]))
        warm = schedule(ctx, float(mix["warm_seconds"]), WARM_STREAM)
        sched = schedule(ctx, ctx.seconds, WINDOW_STREAM)
        t_warm = time.perf_counter()
        loadgen.play(engine.submit, pool_ids, pool_vals, warm,
                     grace_seconds=float(mix["grace_seconds"]))
        traced = {} if ctx.trace_dir is not None else None
        t_open = time.perf_counter()
        played, deltas = play_window(ctx, engine, pool_ids, pool_vals,
                                     sched, traced)
    finally:
        engine.close()
    setup_s = t_open - ctx.t_start
    notes["setup_split"] = {
        "backend_s": round(t_build - ctx.t_start, 2),
        "build_s": round(t_check - t_build, 2),
        "check_schedule_s": round(t_warm - t_check, 2),
        "warm_traffic_s": round(t_open - t_warm, 2)}
    stats = window_stats(sched, played)

    # After the window: every answer has its request's length, and a
    # seeded share is recomputed by the reference.
    n = len(sched)
    lengths_ok = all(len(a) == r
                     for a, r in zip(played.answers, sched.rows.tolist())
                     if a is not None)
    answered = [i for i in range(n) if played.answers[i] is not None]
    share = max(1, int(round(float(mix["recheck_share"]) * n)))
    again = rng.choice(answered, size=min(share, len(answered)),
                       replace=False) if answered else []
    recheck_err = float("nan")
    if len(again):
        rows = np.concatenate([np.arange(sched.offset[i],
                                         sched.offset[i] + sched.rows[i])
                               for i in again])
        recheck_err = logit_error(
            np.concatenate([played.answers[i] for i in again]),
            scorer(pool_ids[rows], pool_vals[rows]))
    failed = n - stats["answered"]
    tol = float(mix["score_rtol"])
    correct = (failed == 0 and lengths_ok and deltas["compile_misses"] == 0
               and sample_err <= tol and recheck_err <= tol
               and deltas["serve.requests_total"] == n)
    if stats["late_p99_ms"] > 1.0 or stats["late_max_ms"] > 10.0:
        log(f"the generator ran late (p99 {stats['late_p99_ms']:.3f} ms, "
            f"max {stats['late_max_ms']:.1f} ms): this run's latencies "
            "are partly its own")
    log(f"requests in the window: {n}; answered {stats['answered']}; "
        f"errors {played.errors[:3]}")
    notes.update(stats=stats, sample_logit_rel_err=sample_err,
                 recheck_logit_rel_err=recheck_err,
                 rechecked_requests=int(len(again)), counters=deltas)
    return Result(
        correct=bool(correct), attempted=n, failed=int(failed),
        setup_s=setup_s,
        end_to_end={"score_p50_ms": stats["p50_ms"],
                    "score_rows_per_s": stats["rows_per_s"]},
        counters=deltas, log={"stats": stats},
        traced=traced if traced else None, notes=notes)
