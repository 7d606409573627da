#!/usr/bin/env python
"""Tiered embedding-store ladder: 10M → 100M → 1B features (ISSUE 16).

Prices the ``fm_spark_tpu/embed`` memory hierarchy per feature-axis
decade: each rung trains the tiered flat-FM path over a skewed,
drifting id stream (the CTR access pattern the hot tier exists for) and
stamps gathered-rows/s, hot-tier hit rate, HBM watermark, and host RSS
into the ledger as an ``embed_bench`` record with its own sentinel
cohort — tiered legs are NEVER compared against in-HBM legs (a tiered
rows/s prices host↔HBM traffic the in-HBM path does not have; PERF.md
round 20). A ``cost_attribution`` record per rung carries the
bytes-moved model for the transfer term: measured h2d+d2h bytes from
the store's own counters over the timed window.

Honesty contracts, enforced in code:

- the 100M/1B rungs use the LAZY cold store — host RSS tracks the
  TOUCHED bucket set, not the feature axis (``host_bytes`` is stamped
  per rung so "bounded host RSS" is a number, not a claim);
- blocking misses are counted and timed (``stall_ms``) — a rung whose
  prefetcher missed its window shows it;
- the first rung (10M by default, every rung ≤ ``--parity-max``) runs
  a DIFFERENTIAL leg: the same batches through the untiered in-HBM
  sparse step, asserted BITWISE equal to the tiered merged view —
  ``parity_ok`` gates the process exit code.

Usage::

    python bench_embed.py                  # 10M → 100M → 1B ladder
    python bench_embed.py --scale tiny     # CPU tier-1 smoke (seconds)
    python bench_embed.py --decades 10000000,100000000
"""

from __future__ import annotations

import argparse
import json
import os
import resource
import sys
import time

_REPO = os.path.dirname(os.path.abspath(__file__))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

#: Ladder decades (full scale): the feature-axis sizes the paper's CTR
#: workloads actually run, and the honesty floor for ROADMAP item 2.
FULL_DECADES = (10_000_000, 100_000_000, 1_000_000_000)
#: --scale tiny: the tier-1 CPU smoke — same code path, seconds not
#: minutes (two "decades" so the ladder loop itself is exercised).
TINY_DECADES = (204_800, 2_048_000)


def _human(n: int) -> str:
    if n % 1_000_000_000 == 0:
        return f"{n // 1_000_000_000}B"
    if n % 1_000_000 == 0:
        return f"{n // 1_000_000}M"
    if n % 1_000 == 0:
        return f"{n // 1_000}k"
    return str(n)


def _rss_bytes() -> int:
    # ru_maxrss is KiB on Linux (bytes on macOS; this ladder is a
    # Linux/TPU-host tool and the field is labeled).
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def _batch_stream(n_features: int, bucket_rows: int, steps: int,
                  batch: int, nnz: int, working_buckets: int,
                  drift_every: int, seed: int):
    """Deterministic skewed id stream with a drifting working set.

    Each step draws its buckets zipf-style from a window of
    ``working_buckets`` buckets; the window base advances by one bucket
    every ``drift_every`` steps. Total touched buckets ≈ working set +
    drift — BOUNDED, whatever the feature axis, which is what keeps the
    lazy cold store's host RSS flat across decades.
    """
    import numpy as np

    n_buckets = n_features // bucket_rows
    rng = np.random.default_rng(np.random.SeedSequence([seed, n_features]))
    # Zipf-ish rank weights over the window (finite, normalized).
    ranks = np.arange(1, working_buckets + 1, dtype=np.float64)
    probs = (1.0 / ranks) / np.sum(1.0 / ranks)
    for i in range(steps):
        base = (i // drift_every) % max(n_buckets - working_buckets, 1)
        b = rng.choice(working_buckets, size=(batch, nnz), p=probs) + base
        ids = (b * bucket_rows
               + rng.integers(0, bucket_rows, (batch, nnz))).astype(
                   np.int64)
        vals = rng.standard_normal((batch, nnz)).astype(np.float32)
        labels = (rng.random(batch) < 0.3).astype(np.float32)
        weights = np.ones(batch, np.float32)
        yield ids, vals, labels, weights


def _run_rung(nominal: int, args, run_id: str) -> dict:
    """One ladder rung: tiered training over a skewed stream, plus the
    bitwise differential leg when the axis is small enough to hold an
    untiered table."""
    import jax.numpy as jnp
    import numpy as np

    from fm_spark_tpu import embed, obs, sparse
    from fm_spark_tpu.models.fm import FMSpec
    from fm_spark_tpu.train import TrainConfig

    # Hashed spaces round up for free: pad the axis to a whole number
    # of buckets so every decade works at any --bucket-rows. The leg
    # keeps the NOMINAL decade name (the cohort identity).
    n_features = -(-nominal // args.bucket_rows) * args.bucket_rows

    spec = FMSpec(num_features=n_features, rank=args.rank)
    cfg = TrainConfig(
        num_steps=args.steps, batch_size=args.batch,
        learning_rate=0.05, lr_schedule="constant", seed=args.seed,
        optimizer=args.optimizer, embed_tier="require",
        hot_rows=args.hot_buckets * args.bucket_rows,
        embed_bucket_rows=args.bucket_rows)
    # Parity gates on the NOMINAL decade (the padding above must not
    # knock the 10M rung out of its differential leg).
    parity = nominal <= args.parity_max
    trainer = embed.TieredTrainer(
        spec, cfg, cold="dense" if parity else "lazy")

    def stream():
        return _batch_stream(
            n_features, args.bucket_rows, args.steps, args.batch,
            args.nnz, args.working_buckets, args.drift_every, args.seed)

    pf = embed.BucketPrefetcher(stream(), trainer.store,
                                depth=args.prefetch)
    t0 = time.perf_counter()
    try:
        for ids, vals, labels, weights in pf:
            trainer.step_batch(ids, jnp.asarray(vals),
                               jnp.asarray(labels), jnp.asarray(weights))
    finally:
        pf.close()
    dt = time.perf_counter() - t0

    st = trainer.store.stats()
    mem = obs.device_memory_snapshot() or {}
    rows = args.steps * args.batch * args.nnz
    rung = {
        "leg": f"embed_rows_{_human(nominal)}",
        "num_features": n_features,
        "nominal_features": nominal,
        "cold_mode": "dense" if parity else "lazy",
        "steps": args.steps,
        "rows_gathered": rows,
        "seconds": round(dt, 4),
        "rows_per_sec": round(rows / dt, 2),
        "examples_per_sec": round(args.steps * args.batch / dt, 2),
        "hit_rate": round(st["hit_rate"], 6),
        "evictions": st["evictions"],
        "misses": st["misses"],
        "stall_ms": round(st["stall_ms"], 3),
        "prefetch_issued": st["prefetch_issued"],
        "bytes_h2d": st["bytes_h2d"],
        "bytes_d2h": st["bytes_d2h"],
        "hbm_peak_bytes": mem.get("peak_bytes_in_use"),
        "host_rss_bytes": _rss_bytes(),
        "cold_host_bytes": trainer.store.cold.host_bytes(),
        "touched_buckets": trainer.store.cold.touched_buckets(),
        "parity_checked": parity,
        "parity_ok": None,
    }

    if parity:
        # Differential leg: the SAME stream through the untiered
        # in-HBM step; merged tiered view must match BITWISE.
        import jax

        cfg_off = TrainConfig(
            num_steps=args.steps, batch_size=args.batch,
            learning_rate=0.05, lr_schedule="constant", seed=args.seed,
            optimizer=args.optimizer)
        params = spec.init(jax.random.key(args.seed))
        if args.optimizer == "sgd":
            step = sparse.make_sparse_sgd_step(spec, cfg_off)
            for i, (ids, vals, labels, weights) in enumerate(stream()):
                params, _ = step(params, i, jnp.asarray(ids),
                                 jnp.asarray(vals), jnp.asarray(labels),
                                 jnp.asarray(weights))
        else:
            from fm_spark_tpu import optim

            step = optim.make_sparse_adaptive_step(spec, cfg_off)
            slots = optim.init_adaptive_slots(args.optimizer, spec,
                                              params)
            if args.optimizer == "ftrl":
                slots = optim.seed_ftrl_slots(slots, params, 0.05, 1.0)
            for ids, vals, labels, weights in stream():
                params, slots, _ = step(
                    params, slots, jnp.asarray(ids), jnp.asarray(vals),
                    jnp.asarray(labels), jnp.asarray(weights))
        merged = trainer.merged_params()
        rung["parity_ok"] = all(
            np.array_equal(np.asarray(merged[k]), np.asarray(params[k]))
            for k in ("w0", "w", "v"))
    return rung


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="bench_embed")
    ap.add_argument("--decades", default=None,
                    help="comma-separated feature-axis sizes (default: "
                         "the 10M,100M,1B ladder; --scale tiny "
                         "overrides)")
    ap.add_argument("--scale", default="full", choices=["full", "tiny"],
                    help="'tiny' = the bounded CPU smoke the tier-1 "
                         "suite runs (same code path, small axis)")
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--batch", type=int, default=512)
    ap.add_argument("--nnz", type=int, default=8)
    ap.add_argument("--rank", type=int, default=8)
    ap.add_argument("--optimizer", default="sgd",
                    choices=["sgd", "ftrl", "adagrad"])
    ap.add_argument("--bucket-rows", type=int, default=1024,
                    dest="bucket_rows")
    ap.add_argument("--hot-buckets", type=int, default=48,
                    dest="hot_buckets",
                    help="hot-tier capacity in buckets (hot_rows = "
                         "this * --bucket-rows)")
    ap.add_argument("--working-buckets", type=int, default=32,
                    dest="working_buckets",
                    help="per-step zipf window in buckets (must be <= "
                         "--hot-buckets: a batch's working set must "
                         "fit the hot tier)")
    ap.add_argument("--drift-every", type=int, default=1,
                    dest="drift_every",
                    help="steps between one-bucket drifts of the zipf "
                         "window (default 1: over the default 40 steps "
                         "the touched set outgrows the hot tier, so "
                         "every rung exercises real eviction churn)")
    ap.add_argument("--prefetch", type=int, default=2,
                    help="BucketPrefetcher depth (>=2 = double-buffer)")
    ap.add_argument("--parity-max", type=int, default=10_000_000,
                    dest="parity_max",
                    help="run the bitwise tiered-vs-untiered "
                         "differential on rungs up to this many "
                         "features (dense cold mode)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--art-dir", default=os.path.join(_REPO, "artifacts"),
                    dest="art_dir")
    ap.add_argument("--run-id", default=None, dest="run_id")
    ap.add_argument("--out", default=None,
                    help="also write the result JSON here")
    args = ap.parse_args(argv)

    if args.scale == "tiny":
        args.steps = min(args.steps, 12)
        args.batch = min(args.batch, 64)
        args.rank = min(args.rank, 4)
        args.bucket_rows = min(args.bucket_rows, 256)
        args.hot_buckets = min(args.hot_buckets, 8)
        args.working_buckets = min(args.working_buckets, 6)
        # Drift fast enough that the smoke crosses hot capacity and
        # exercises the evict/flush path, not just the install path.
        args.drift_every = min(args.drift_every, 2)
        args.parity_max = min(args.parity_max, 400_000)
    if args.decades:
        decades = tuple(int(d) for d in args.decades.split(",") if d)
    else:
        decades = TINY_DECADES if args.scale == "tiny" else FULL_DECADES
    if args.working_buckets > args.hot_buckets:
        raise SystemExit(
            f"--working-buckets {args.working_buckets} > --hot-buckets "
            f"{args.hot_buckets}: a batch working set larger than the "
            "hot tier cannot be made resident")
    for d in decades:
        if args.hot_buckets * args.bucket_rows >= d:
            raise SystemExit(
                f"hot tier ({args.hot_buckets * args.bucket_rows} rows)"
                f" >= decade {d}: nothing to tier at that rung")

    from fm_spark_tpu import obs
    from fm_spark_tpu.utils import compile_cache

    run_id = args.run_id or obs.new_run_id()
    run_dir = os.path.join(args.art_dir, "obs", run_id)
    obs.configure(run_dir, run_id=run_id)
    compile_cache.enable()

    import jax

    device = jax.devices()[0].device_kind

    rungs = []
    for d in decades:
        rung = _run_rung(d, args, run_id)
        rungs.append(rung)
        print(json.dumps({"rung": rung["leg"],
                          "rows_per_sec": rung["rows_per_sec"],
                          "hit_rate": rung["hit_rate"],
                          "host_rss_bytes": rung["host_rss_bytes"]}),
              flush=True)

    # --------------------------------------------------- ledger + sentinel
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
    for rung in rungs:
        variant = (f"embed/{_human(rung['num_features'])}"
                   f"/r{args.rank}/{args.optimizer}"
                   f"/hot{args.hot_buckets}x{args.bucket_rows}")
        rung["variant"] = variant
        fingerprint = measurement_fingerprint(
            variant=variant, model="fm", batch=args.batch,
            rank=args.rank,
            extra={"bucket_rows": args.bucket_rows,
                   "hot_buckets": args.hot_buckets,
                   "working_buckets": args.working_buckets,
                   "drift_every": args.drift_every,
                   "prefetch": args.prefetch, "nnz": args.nnz,
                   "cold_mode": rung["cold_mode"]},
            device_kind=device, n_chips=1,
            jax_version=versions["jax_version"],
            libtpu_version=versions["libtpu_version"],
        )
        rung["sentinel"] = sentinel.observe({
            "kind": "embed_bench",
            "leg": rung["leg"],
            "run_id": run_id,
            "fingerprint": fingerprint,
            "value": rung["rows_per_sec"],
            "unit": "rows/s",
            "hit_rate": rung["hit_rate"],
            "evictions": rung["evictions"],
            "stall_ms": rung["stall_ms"],
            "hbm_peak_bytes": rung["hbm_peak_bytes"],
            "host_rss_bytes": rung["host_rss_bytes"],
            "cold_host_bytes": rung["cold_host_bytes"],
            "parity_ok": rung["parity_ok"],
            "variant": variant,
        })
        # Bytes-moved cost model for the host↔HBM transfer term: the
        # store's own h2d/d2h counters over the timed window (measured
        # bucket traffic, not a guess at it).
        bytes_moved = rung["bytes_h2d"] + rung["bytes_d2h"]
        ledger.append({
            "kind": "cost_attribution",
            "leg": f"cost/{rung['leg']}",
            "run_id": run_id,
            "variant": variant,
            "value": round(bytes_moved / rung["seconds"] / 1e9, 3),
            "unit": "GB/s(model)",
            "step_ms": round(rung["seconds"] * 1e3 / args.steps, 3),
            "bytes_per_step": bytes_moved // args.steps,
            "families": {"h2d_bucket_install": rung["bytes_h2d"],
                         "d2h_evict_flush": rung["bytes_d2h"]},
            "assumptions": [
                "bytes = store-counted bucket transfers (install + "
                "dirty evict flush), all planes",
                "blocking-miss stalls counted in stall_ms, not "
                "subtracted from the timed window",
            ],
            "fingerprint": fingerprint,
        })

    parity_ok = all(r["parity_ok"] is not False for r in rungs)
    parity_run = any(r["parity_checked"] for r in rungs)
    obs.export_snapshot()
    result = {
        "bench": "embed",
        "run_id": run_id,
        "obs_dir": run_dir,
        "device": device,
        "decades": list(decades),
        "optimizer": args.optimizer,
        "hot_rows": args.hot_buckets * args.bucket_rows,
        "bucket_rows": args.bucket_rows,
        "rungs": rungs,
        "parity_checked": parity_run,
        "parity_ok": parity_ok,
    }
    line = json.dumps(result)
    print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    obs.shutdown()
    return 0 if parity_ok else 1


if __name__ == "__main__":
    sys.exit(main())
