"""Input-pipeline benchmark: packed dir -> StreamingBatches -> device.

Measures the part bench.py deliberately excludes (its data is
device-resident): sustained host-side feed rate from a Criteo-shaped
packed directory through the production loader stack, against the
north-star requirement of ~1.25M samples/sec/chip (BASELINE.md; SURVEY.md
S7 hard part #1 — "input pipeline at 10M samples/s" across 8 chips).

Prints ONE JSON line with the end-to-end rate (loader + field-local id
conversion + host->device transfer, prefetched), plus stderr rows for
each pipeline stage so regressions are attributable:

  stage 1  PackedBatches        memmap read + chunk-shuffled gather
  stage 2  + field_local        the FieldFM id conversion (cli layer)
  stage 3  + device_put         blocking transfer, no prefetch
  stage 4  + Prefetcher         stage 3 with the producer thread hiding
                                assembly+transfer behind the consumer

Streaming-ingest ladder (ISSUE 6 — raw dirty-tolerant TEXT, not the
preprocessed binary; the rates that close ROADMAP open item 2):

  stream_py                 StreamBatches, per-line Python parse (the
                            PR-4 hardened path — round-9's ~1.2k rows/s)
  stream_native             NativeStreamBatches, C++ chunk parse with
                            identical guard/cursor semantics
  stream_native+prefetch    + Prefetcher producer thread parsing chunk
                            N+1 while batch N is consumed, device_put
                            double-buffered

A ``streaming_rows_per_sec`` block lands in the output JSON so the win
stays attributable against the in-memory ``packed_batches`` stage.

Synthesizes its own packed data AND text shards (one-time, reused
across runs via --data-dir) so it never depends on real Criteo being
present.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

METRIC = "input_pipeline_samples_per_sec"
TARGET_PER_CHIP = 10_000_000 / 8


def _log(msg):
    print(f"bench_input: {msg}", file=sys.stderr, flush=True)


def synthesize_packed(path: str, rows: int, num_fields: int = 39,
                      bucket: int = 1 << 18, seed: int = 0,
                      chunk: int = 1 << 20) -> None:
    """Write a Criteo-shaped packed dir (per-field-offset ids, int8
    labels, store_vals=False — the criteo.preprocess layout)."""
    from fm_spark_tpu.data import PackedWriter

    rng = np.random.default_rng(seed)
    offs = (np.arange(num_fields, dtype=np.int64) * bucket)[None, :]
    with PackedWriter(path, num_fields, store_vals=False) as w:
        for start in range(0, rows, chunk):
            n = min(chunk, rows - start)
            ids = (rng.integers(0, bucket, size=(n, num_fields),
                                dtype=np.int64) + offs).astype(np.int32)
            labels = (rng.random(n) < 0.25).astype(np.int8)
            w.append(ids, labels)


def synthesize_tsv_fast(path: str, rows: int, seed: int = 0,
                        vocab_per_field: int = 1000,
                        missing_rate: float = 0.05,
                        chunk: int = 100_000) -> None:
    """Criteo-shaped synthetic TSV, vectorized (data/criteo.py's
    synthesize_tsv is a per-value Python loop — fine for 6k bench rows,
    too slow for the multi-million-row streaming ladder)."""
    from fm_spark_tpu.data.criteo import NUM_CAT, NUM_INT

    rng = np.random.default_rng(seed)
    with open(path, "wb") as f:
        for start in range(0, rows, chunk):
            n = min(chunk, rows - start)
            label = (rng.random(n) < 0.25).astype(np.int8)
            ints = (rng.zipf(1.5, size=(n, NUM_INT)) - 1).astype(np.int64)
            cats = rng.zipf(1.3, size=(n, NUM_CAT)) % vocab_per_field
            miss = rng.random((n, NUM_INT + NUM_CAT)) < missing_rate
            out = []
            for r in range(n):
                cols = [b"1" if label[r] else b"0"]
                cols += [b"" if miss[r, c] else str(ints[r, c]).encode()
                         for c in range(NUM_INT)]
                cols += [b"" if miss[r, NUM_INT + c] else
                         b"%08x" % int(cats[r, c]) for c in range(NUM_CAT)]
                out.append(b"\t".join(cols))
            f.write(b"\n".join(out) + b"\n")


def _text_shards(data_dir: str, rows: int, n_shards: int = 3):
    """Create/reuse the streaming ladder's text shards under data_dir."""
    tdir = os.path.join(data_dir, "text")
    meta = os.path.join(tdir, "meta.json")
    paths = [os.path.join(tdir, f"shard{s}.tsv") for s in range(n_shards)]
    if os.path.exists(meta):
        with open(meta) as f:
            if json.load(f).get("rows") == rows:
                return paths
    os.makedirs(tdir, exist_ok=True)
    _log(f"synthesizing {rows} text rows into {tdir}...")
    t0 = time.perf_counter()
    per = rows // n_shards
    for s, p in enumerate(paths):
        synthesize_tsv_fast(p, per + (rows - per * n_shards
                                      if s == n_shards - 1 else 0), seed=s)
    with open(meta, "w") as f:
        json.dump({"rows": rows}, f)
    _log(f"text synthesized in {time.perf_counter() - t0:.1f}s")
    return paths


def _rate(make_iter, seconds: float, batch: int,
          consume=lambda b: None) -> float:
    """Sustained samples/sec of ``next(it)`` + ``consume(batch)``."""
    it = make_iter()
    # Warm the first batch (memmap page-in, jit of nothing, thread spin-up).
    consume(next(it))
    n = 0
    t0 = time.perf_counter()
    while (dt := time.perf_counter() - t0) < seconds:
        consume(next(it))
        n += batch
    rate = n / dt
    if hasattr(it, "close"):
        it.close()
    return rate


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=4_000_000,
                    help="synthetic dataset size (rows)")
    ap.add_argument("--batch", type=int, default=1 << 17,
                    help="batch size (matches bench.py's headline)")
    ap.add_argument("--seconds", type=float, default=8.0,
                    help="measurement window per stage")
    ap.add_argument("--data-dir", default="/tmp/fmtpu_bench_input",
                    help="packed dir to create/reuse")
    ap.add_argument("--prefetch-depth", type=int, default=4)
    ap.add_argument("--compact-cap", type=int, default=0, dest="compact_cap",
                    help="with --host-dedup: measure the COMPACT aux "
                         "(ops/scatter.compact_aux) at this static "
                         "per-field capacity instead of the full-B aux")
    ap.add_argument("--host-dedup", action="store_true", dest="host_dedup",
                    help="add the DedupAuxBatches stage (per-batch argsort "
                         "+ segment maps on the host) — the feed-rate cost "
                         "of TrainConfig.host_dedup")
    ap.add_argument("--no-stream", action="store_true", dest="no_stream",
                    help="skip the streaming-ingest ladder (text "
                         "synthesis + stream_py/stream_native stages)")
    ap.add_argument("--stream-rows", type=int, default=1_500_000,
                    dest="stream_rows",
                    help="synthetic text rows for the streaming ladder "
                         "(3 shards; epochs cycle if the window drains "
                         "them)")
    ap.add_argument("--stream-py-batch", type=int, default=2048,
                    dest="stream_py_batch",
                    help="batch size for the stream_py stage only (the "
                         "pure-Python parser is ~3 orders of magnitude "
                         "slower; a headline-sized batch would blow the "
                         "measurement window)")
    ap.add_argument("--stream-py-seconds", type=float, default=6.0,
                    dest="stream_py_seconds",
                    help="measurement window for the stream_py stage")
    args = ap.parse_args()
    if args.compact_cap and not args.host_dedup:
        ap.error("--compact-cap requires --host-dedup")

    num_fields, bucket = 39, 1 << 18

    meta = os.path.join(args.data_dir, "meta.json")
    need = True
    if os.path.exists(meta):
        with open(meta) as f:
            need = json.load(f).get("num_examples") != args.rows
    if need:
        _log(f"synthesizing {args.rows} rows into {args.data_dir}...")
        t0 = time.perf_counter()
        import shutil

        if os.path.isdir(args.data_dir):
            shutil.rmtree(args.data_dir)
        synthesize_packed(args.data_dir, args.rows, num_fields, bucket)
        _log(f"synthesized in {time.perf_counter() - t0:.1f}s")

    import jax

    dev = jax.devices()[0]
    _log(f"device: {dev.device_kind}")

    from fm_spark_tpu.cli import StreamingBatches
    from fm_spark_tpu.data import PackedBatches, PackedDataset, Prefetcher

    ds = PackedDataset(args.data_dir)

    def raw():
        return PackedBatches(ds, args.batch, seed=1)

    def with_field_local_unfused():
        # The pre-round-5 production path: conversion as a second
        # full-batch pass in the StreamingBatches wrapper. Kept as a
        # stage so the fused win stays attributable.
        return StreamingBatches(PackedBatches(ds, args.batch, seed=1),
                                bucket=bucket)

    def with_field_local():
        # The production path: conversion fused into the (native when
        # available) row gather inside PackedBatches.
        return PackedBatches(ds, args.batch, seed=1, bucket=bucket)

    def put_block(b):
        jax.block_until_ready(jax.device_put(b))

    from fm_spark_tpu.data import DedupAuxBatches

    source = (
        (lambda: DedupAuxBatches(with_field_local(),
                                 cap=args.compact_cap))
        if args.host_dedup else with_field_local
    )
    from fm_spark_tpu import native

    _log(f"native gather: {native.available()}")
    stages = [
        ("packed_batches", raw, lambda b: None),
        ("+field_local_unfused", with_field_local_unfused, lambda b: None),
        ("+field_local", with_field_local, lambda b: None),
    ]
    if args.host_dedup:
        stages.append(("+dedup_aux", source, lambda b: None))
    stages += [
        ("+device_put", source, put_block),
        ("+prefetcher", lambda: Prefetcher(source(),
                                           depth=args.prefetch_depth,
                                           place=jax.device_put),
         lambda b: jax.block_until_ready(b)),
    ]
    rates = {}
    for name, make, consume in stages:
        r = _rate(make, args.seconds, args.batch, consume)
        rates[name] = r
        _log(f"{name:16s} {r:12.0f} samples/s "
             f"({r / TARGET_PER_CHIP:.2f}x one chip's need)")

    streaming = None
    if not args.no_stream:
        # Streaming-ingest ladder (ISSUE 6): raw text through the
        # hardened ShardReader/RecordGuard path, priced per parser.
        from fm_spark_tpu.data import NativeStreamBatches, ShardReader
        from fm_spark_tpu.data.stream import StreamBatches, line_parser
        from fm_spark_tpu.data.native_stream import native_stream_supported
        from fm_spark_tpu.data.criteo import NUM_FIELDS

        paths = _text_shards(args.data_dir, args.stream_rows)
        nf = NUM_FIELDS * bucket

        def stream_py():
            return StreamBatches(
                ShardReader(paths), line_parser("criteo", bucket),
                args.stream_py_batch, NUM_FIELDS, num_features=nf)

        def stream_native():
            return NativeStreamBatches(
                ShardReader(paths, chunk_bytes=1 << 22), "criteo",
                args.batch, NUM_FIELDS, num_features=nf, bucket=bucket)

        streaming = {}
        r = _rate(stream_py, args.stream_py_seconds, args.stream_py_batch)
        streaming["stream_py"] = r
        _log(f"{'stream_py':22s} {r:12.0f} rows/s (per-line Python parse)")
        if native_stream_supported("criteo", NUM_FIELDS, bucket):
            r = _rate(stream_native, args.seconds, args.batch)
            streaming["stream_native"] = r
            _log(f"{'stream_native':22s} {r:12.0f} rows/s")
            r = _rate(
                lambda: Prefetcher(stream_native(),
                                   depth=args.prefetch_depth,
                                   place=jax.device_put),
                args.seconds, args.batch,
                lambda b: jax.block_until_ready(b))
            streaming["stream_native+prefetch"] = r
            _log(f"{'stream_native+prefetch':22s} {r:12.0f} rows/s")
        else:
            _log("stream_native SKIPPED (native chunk parser unavailable)")
        streaming = {k: round(v, 1) for k, v in streaming.items()}
        # Exit-ratio stage (ROADMAP item 2): the in-memory PackedBatches
        # rate AT THE STREAMING BATCH SIZE is the ladder's denominator —
        # re-measured here (not reused from the samples/s ladder above)
        # so the round-10 0.075x-on-2-cores figure re-prices cleanly on
        # any host, and stamped with the cores the parse actually had.
        streaming["packed_batches"] = round(
            _rate(raw, min(args.seconds, 4.0), args.batch), 1)
        try:
            cores = len(os.sched_getaffinity(0))
        except AttributeError:  # non-Linux
            cores = os.cpu_count() or 1
        streaming["cores_used"] = cores
        best_stream = next(
            (streaming[kk] for kk in ("stream_native+prefetch",
                                      "stream_native") if kk in streaming),
            None)
        if best_stream is not None:
            streaming["speedup_vs_py"] = round(
                best_stream / streaming["stream_py"], 1)
            streaming["vs_packed_batches"] = round(
                best_stream / streaming["packed_batches"], 4)
            _log(f"{'exit ratio':22s} {streaming['vs_packed_batches']:12}"
                 f" x of in-memory PackedBatches on {cores} core(s)")

    end_to_end = rates["+prefetcher"]
    payload = {
        "metric": METRIC,
        "value": round(end_to_end, 1),
        "unit": "samples/sec",
        "vs_baseline": round(end_to_end / TARGET_PER_CHIP, 4),
        "stages": {k: round(v, 1) for k, v in rates.items()},
    }
    if streaming is not None:
        payload["streaming_rows_per_sec"] = streaming
    print(json.dumps(payload))


if __name__ == "__main__":
    main()
