#!/usr/bin/env python3
"""Find the knee of a scoring cell, once, on the chip.

    python3 benchmark/sweep.py --workload fm_r64.score_steady --seed 1 \
        --seconds 8 --rates 2000,4000,6000,8000

One process builds the cell's engine as ``run.py`` does and plays the
cell's traffic mix at each rate in turn, lowest first, printing one JSON
line per rate: offered and answered rows/s, latency from the due instant,
how late the generator ran and the backlog at the window's end. The knee
is the highest rate at which answered rows/s still equals offered and
the backlog at the end is a handful; the cell's ``requests_per_s`` is
then written, as a number, into ``cells/<cell>.json``. A sweep is not a
result: it prints no metric line and is not part of any check.
"""

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", required=True,
                    help="requests/s, comma-separated")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    from benchmark.drivers import score
    from benchmark.harness import (
        Context, load_cell, memory_peak_bytes, require_chips)

    cell = load_cell(args.workload, rehearse=args.rehearse)
    device = require_chips(cell, rehearse=args.rehearse)
    ctx = Context(cell=cell, seed=args.seed, seconds=args.seconds,
                  t_start=T_START, trace_dir=None)
    engine, _scorer, pool_ids, pool_vals, notes = score.build(ctx)
    print(json.dumps({"device": device, "setup": notes}), flush=True)
    try:
        for stream, rate in enumerate(
                sorted(float(r) for r in args.rates.split(","))):
            sched = score.schedule(ctx, args.seconds, 100 + stream,
                                    requests_per_s=rate)
            played, deltas = score.play_window(ctx, engine, pool_ids,
                                               pool_vals, sched)
            stats = score.window_stats(sched, played)
            # Past the knee a window leaves a queue behind; the next
            # rate must not inherit it (answers come in order).
            t_drain = time.perf_counter()
            engine.predict(pool_ids[:1], pool_vals[:1], timeout=600.0)
            stats["drain_s"] = round(time.perf_counter() - t_drain, 2)
            batches = deltas["serve.batches_total"] or 1.0
            print(json.dumps({
                "requests_per_s": rate, **stats,
                "rows_per_batch": deltas["serve.rows_total"] / batches,
                "batches_per_s": batches / args.seconds,
                "compile_misses": deltas["compile_misses"],
                "errors": len(played.errors),
                "rehearsal": args.rehearse}), flush=True)
    finally:
        engine.close()
    print(json.dumps({"memory_peak_bytes": memory_peak_bytes()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
