"""Headline benchmark: Criteo-shaped FM training throughput on TPU.

Prints ONE JSON line:
  {"metric": "...", "value": N, "unit": "...", "vs_baseline": N,
   "platform": "tpu", "device": "<device_kind>", "n_devices": N, ...}
or, if no measurement completed, ONE JSON line with an "error" key so
the driver records a diagnosable artifact instead of a bare traceback.

Config mirrors the north-star setting (BASELINE.json:5,9): FM rank 64,
39 fields (13 int + 26 categorical), 10.2M hashed features (39 x 262144
per-field buckets). Baseline = the driver target of 10M samples/sec on a
v5e-8 -> 1.25M samples/sec/chip; ``vs_baseline`` = measured-per-chip /
target-per-chip, so >= 1.0 beats the 8-chip target at equal per-chip rate.

What is measured: the full fused sparse-SGD train step (forward, analytic
backward -- the reference's computeGradient rule -- and in-place scatter
update) on the field-partitioned table layout (models/field_fm.py explains
the measured XLA gather/scatter cliffs that motivate it). Many steps are
rolled into one compiled ``fori_loop`` program so per-dispatch host
overhead is amortized, matching production use where the host only feeds
data. Data is device-resident; the host input pipeline is benchmarked
separately by ``bench_input.py``.

One process per chip: the measurement runs in a CHILD process (``--inner``)
and this parent never initialises a JAX backend (pinned in
tests/test_chip_contract.py) -- a parent that had touched JAX would hold
the chip and the child would fail or hang. The child's first stdout line
names the device (platform, device_kind, count) and every result line
carries it; the child prints stage heartbeats to stderr so a slow first
compile is distinguishable from a hang.

The parent is also a supervisor (ROADMAP Design 6 decides how much of it
the current machine still needs): a hard wall-clock timeout per attempt,
retry with backoff, and
  * ``--total-deadline`` (default 1500s) bounds the WHOLE parent run;
    attempt timeouts are clamped to the remaining budget.
  * The child runs an init watchdog: a backend init that has not finished
    within ``--init-timeout`` (default 240s) prints a provisional error
    JSON and exits early instead of burning the full attempt timeout.
  * A provisional error JSON is printed after EVERY failed attempt, so
    the final stdout line is parseable no matter where an outer kill
    lands.
  * SIGTERM/SIGINT in the parent (what ``timeout(1)`` sends) emits the
    best-so-far result line -- or the error JSON -- before exiting; the
    parent streams the child's stdout live so a mid-sweep cumulative-best
    line is salvageable at any instant.

Warm start:
  * jax's persistent compilation cache (utils/compile_cache) is on in
    the child without a flag -- at ``JAX_COMPILATION_CACHE_DIR`` when
    set, else ``<checkout>/.jax_compile_cache`` -- so a SECOND bench
    process deserializes every compiled step instead of recompiling.
  * ``--fast-first`` runs a TIERED sweep: leg 1 is the recorded winner
    variant (MEASURED.json), AOT-precompiled against abstract shapes
    before the tables are even initialized, and its non-provisional
    result JSON is emitted before any remaining leg starts.
  * Every completed leg streams to ``--artifacts-dir`` as it lands
    (``sweep_<model>.jsonl`` + atomically-replaced
    ``keepbest_<model>.json``), so a run killed mid-window leaves the
    best-so-far metric instead of null; a SIGTERM'd parent that
    salvaged any result line exits 0.

Timing note: a device->host transfer of the loss is the fence the timed
window ends on (it cannot return before the step that produced it).
"""

import argparse
import json
import os
import re
import subprocess
import sys
import threading
import time

# Per-model metric + per-chip target (--model). The tracked headline is
# the FM row (BASELINE.json:2); the FFM row exists so a chip window can
# REFRESH MEASURED.json's config-4 rate (carried from round 3 otherwise)
# with one command: `python bench.py --model ffm`.
METRICS = {
    "fm": ("criteo_fm_rank64_10Mfeat_samples_per_sec_per_chip",
           10_000_000 / 8),
    "ffm": ("avazu_ffm_rank16_samples_per_sec_per_chip", None),
    "deepfm": ("criteo_deepfm_rank16_samples_per_sec_per_chip", None),
    # Config 2 (BASELINE.json:8): FM rank-32, Criteo-Kaggle, 39x32768
    # ~= 1.28M hashed features. Its own metric so its rate can never
    # conflate with the rank-64/10M headline.
    "fm_kaggle": ("kaggle_fm_rank32_1Mfeat_samples_per_sec_per_chip",
                  None),
}
# Per-model DEFAULT rank: an explicit --rank override changes the
# program being measured, so it is stamped into the variant label
# (same provenance rule as a non-default --batch).
DEFAULT_RANK = {"fm": 64, "ffm": 16, "deepfm": 16, "fm_kaggle": 32}
# metric name -> MEASURED.json entry rewritten on a successful sweep
METRIC_ENTRY = {
    METRICS["fm"][0]: "headline",
    METRICS["ffm"][0]: "ffm_avazu",
    METRICS["deepfm"][0]: "deepfm_criteo",
    METRICS["fm_kaggle"][0]: "fm_kaggle",
}
METRIC, TARGET_PER_CHIP = METRICS["fm"]
UNIT = "samples/sec/chip"

# The run id shared by the parent and every child attempt (ISSUE 7):
# all of a run's telemetry — trace/metrics/flight streams AND the
# health_<model>.jsonl journal — lands under ONE per-run directory,
# <artifacts>/obs/<run_id>/, and the id is echoed in the result JSON
# (error lines included) so consumers can find the evidence.
_RUN_ID = None


def _gen_run_id():
    """Parent-side run-id mint (no fm_spark_tpu import: the parent must
    stay light — the package pulls jax)."""
    return time.strftime("%Y%m%d-%H%M%S", time.gmtime()) + f"-p{os.getpid()}"


def _obs_run_dir(art_dir, run_id):
    return os.path.join(art_dir, "obs", run_id)


def _renormalize_results(results, prev_chips, n_chips):
    """Re-normalize banked per-chip rates onto the surviving-chip
    denominator after an elastic shrink, so ``max()`` ranks every leg
    on comparable figures (a post-shrink leg must not win on a smaller
    divisor). Entries are ``(rate, label, dt, loss)``."""
    if prev_chips == n_chips:
        return list(results)
    return [(r * prev_chips / n_chips, label, dt, loss)
            for r, label, dt, loss in results]


def default_variants(model, batch):
    """The default sweep's staged A/B grid: ``(head, tail)`` lists of
    ``(label, (param_dtype, compute_dtype), TrainConfig)``.

    ``head`` goes BEFORE the fp32/scatter_add reference variant, ordered
    by salvage value (a flaky attachment dying mid-sweep keeps the
    prefix): the MEASURED-BEST composed variant first (1,422,411 on
    2026-07-31 — floor-cap + gfull + segtotal, PERF.md round-5 table),
    the cap-ladder legs as the ongoing A/B, the two single-lever
    legs, the round-3 winner closing the 2x2 grid, and the secondary
    probe (devaux = the multi-chip-composable denominator). ``tail``
    goes after it
    (the dtype ladder).

    Module-level (not inlined in inner_main) so tests can pin the
    label<->TrainConfig consistency that the measurement's provenance
    depends on; imports TrainConfig lazily so the PARENT bench process
    never pulls in jax.
    """
    from fm_spark_tpu.train import TrainConfig

    # Compact capacity must bound the bench batch's max per-field unique
    # count (Zipf 1.3, seed 0: 11,990 at B=131072; 20,109 at B=262144 —
    # both under batch/10, rounded up to segtotal's 512 tile). The
    # historical 16384 stays the default-batch cap; larger batches scale
    # it, or the compact variants would die on compact_overflow='error'.
    bound = max(512, ((batch // 10) + 511) // 512 * 512)
    cap = min(max(16384, bound), batch)
    if model == "deepfm":
        # Config 5's optimizer (dense Adam head) with the measured-best
        # FM table levers (criteo-sized tables sit ABOVE the gather
        # cliffs, same as the FM headline), plus the composed-kernel
        # A/B at config 5's own shape (measured a LOSER there — narrow
        # rank-16 rows, PERF.md — kept as the drift sentinel).
        base = dict(learning_rate=1e-3, lr_schedule="constant",
                    optimizer="adam", sparse_update="dedup_sr",
                    host_dedup=True, compact_cap=cap)
        return [], [
            (f"bfloat16/dedup_sr/compact{cap}/cd-bf16",
             ("bfloat16", "bfloat16"), TrainConfig(**base)),
            (f"bfloat16/dedup_sr/compact{cap}/cd-bf16/gfull/segtotal",
             ("bfloat16", "bfloat16"),
             TrainConfig(**base, gfull_fused=True, segtotal_pallas=True)),
        ]
    if model == "ffm":
        # Measured winner first (816,553 on 2026-07-31): fp32 storage +
        # bf16 COMPUTE buffers + plain scatter_add — the cd-bf16 lever
        # halves the [B, F, F, k] sel-buffer traffic (FFM's dominant
        # term) while the fp32 tables keep scatter_add exact, so no
        # SR/dedup machinery is needed. NO compact variants: the
        # compact lever measured a LOSER on avazu's 24MB tables
        # (PERF.md: the tables sit under every gather cliff, so
        # cap-lane compaction only adds passes); bf16 STORAGE +
        # dedup_sr measured a 2x loser for the same reason (kept as
        # the drift sentinel).
        ffm_base = dict(learning_rate=0.05, lr_schedule="constant",
                        optimizer="sgd")
        return [
            ("float32/scatter_add/cd-bf16", ("float32", "bfloat16"),
             TrainConfig(**ffm_base, sparse_update="scatter_add")),
            # Round-5 staged A/B (unpriced — needs a chip window): the
            # sel-blocked body never materializes the [B, F, F, k]
            # sel/dsel/dv tensors, the step's dominant HBM traffic
            # (the cd-bf16 lever, which halves exactly those bytes,
            # measured +23% — so the expected effect is of that order
            # if the step is still sel-bandwidth-bound).
            ("float32/scatter_add/cd-bf16/selblk",
             ("float32", "bfloat16"),
             TrainConfig(**ffm_base, sparse_update="scatter_add",
                         sel_blocked=True)),
            # ISSUE 8: the sel-blocked body as Pallas kernels — the
            # [T, F, k] sel/dsel pair GUARANTEED tile-resident instead
            # of fusion-dependent (ops/pallas_fused.ffm_sel_*; bit-
            # exact fp32 vs the XLA selblk body). 'require' so a
            # no-Pallas attachment skips rather than silently pricing
            # the XLA body under this label.
            ("float32/scatter_add/cd-bf16/selblk-pallas",
             ("float32", "bfloat16"),
             TrainConfig(**ffm_base, sparse_update="scatter_add",
                         sel_blocked=True, fused_embed="require")),
        ], [
            ("bfloat16/dedup_sr", ("bfloat16", "bfloat16"),
             TrainConfig(**ffm_base, sparse_update="dedup_sr")),
        ]
    if model == "fm_kaggle":
        # Config 2: small tables — candidates from BOTH measured
        # regimes: the avazu winner form (bf16 compute over exact fp32
        # storage, no dedup machinery) and the criteo winner form
        # (bf16 storage + SR + compact; cap 16384 bounds the measured
        # 10,711 max per-field unique at B=131072). The on-chip sweep
        # decides; fp32/scatter_add is the reference variant between
        # head and tail.
        kbase = dict(learning_rate=0.05, lr_schedule="constant",
                     optimizer="sgd")
        return [
            ("float32/scatter_add/cd-bf16", ("float32", "bfloat16"),
             TrainConfig(**kbase, sparse_update="scatter_add")),
            (f"bfloat16/dedup_sr/compact{cap}/cd-bf16",
             ("bfloat16", "bfloat16"),
             TrainConfig(**kbase, sparse_update="dedup_sr",
                         host_dedup=True, compact_cap=cap)),
        ], [
            ("bfloat16/dedup_sr", ("bfloat16", "bfloat16"),
             TrainConfig(**kbase, sparse_update="dedup_sr")),
        ]
    # FM headline (PERF.md "the compact lever": scatter cost is
    # per-lane even for dropped lanes, so cap-lane compaction wins; cap
    # 16384 bounds the measured max per-field unique count (~12k) on
    # the bench's Zipf batch).
    base = dict(learning_rate=0.05, lr_schedule="constant",
                optimizer="sgd", sparse_update="dedup_sr",
                host_dedup=True, compact_cap=cap)
    # Tight-cap measured a WINNER (2026-07-31 on-chip A/B: 1,398,617 at
    # cap 13312 vs 1,383,925 at 16384, +1.1% — the ~19% cap-lane
    # shrinkage priced across the gather/expand/scatter/segtotal
    # passes), so the tight composed variant now runs FIRST (salvage
    # order = measured best first) with the historical cap as the
    # ongoing A/B leg. The bound is MEASURED only at 131072 and 262144;
    # at other batches a too-tight cap makes the aux build raise
    # CompactCapOverflow, which the sweep's per-variant guard turns
    # into a logged skip (not a sweep abort).
    tight = min(bound, cap)
    # MEASURED WINNER (1,422,411 = 1.138x, 2026-07-31): cap 12288 = the
    # bench batch's measured max per-field unique (11,990 at Zipf 1.3,
    # seed 0) rounded to segtotal's 512 tile — the FLOOR of the cap
    # lever. The one-window cap ladder: 16384 -> 1.387M (+1.5%) ->
    # 13312 -> 1.407M (+1.1%) -> 12288 -> 1.422M. The floor is only
    # KNOWN at the measured batch; anywhere else floor_cap falls back
    # to the formula cap (otherwise an overflowing cap would just
    # waste the slot: the host-aux probe raises CompactCapOverflow at
    # build, and a compact-device leg poisons its loss to -inf — both
    # now skipped, never priced). One definition so the probe and
    # devaux legs can never measure different caps.
    floor_cap = 12288 if batch == 1 << 17 else cap
    ranked = []
    if floor_cap < tight:
        ranked.append(
            (f"bfloat16/dedup_sr/compact{floor_cap}/cd-bf16/gfull"
             "/segtotal",
             dict(compact_cap=floor_cap, gfull_fused=True,
                  segtotal_pallas=True)))
    if tight < cap:
        ranked.append(
            (f"bfloat16/dedup_sr/compact{tight}/cd-bf16/gfull/segtotal",
             dict(compact_cap=tight, gfull_fused=True,
                  segtotal_pallas=True)))
    ranked += [
        (f"bfloat16/dedup_sr/compact{cap}/cd-bf16/gfull/segtotal",
         dict(gfull_fused=True, segtotal_pallas=True)),
    ]
    # Fused Pallas backward (ISSUE 8, ROADMAP item 4): the challenger
    # for the sel/dsel/dv HBM traffic the round-5 cd-bf16 probe priced
    # at +23% — g_full rebuilt on-chip from the sorted scalar streams +
    # the VMEM-resident urows block and segment-summed in the SAME
    # kernel, subsuming gfull+segtotal for the update stage. Staged
    # right after the composed winners (the round-5 selblk pattern):
    # a dying window prices the incumbent first, the challenger next.
    # fused_embed='require' so an attachment that cannot serve the
    # kernel SKIPS the leg (construction raises PallasUnavailable, the
    # per-variant guard logs it) instead of silently measuring the XLA
    # path under a fused label — the fallback-never-keep-bests rule.
    ranked.insert(1, (
        f"bfloat16/dedup_sr/compact{floor_cap}/cd-bf16/fusedbwd",
        dict(compact_cap=floor_cap, fused_embed="require")))
    ranked += [
        (f"bfloat16/dedup_sr/compact{cap}/cd-bf16/gfull",
         dict(gfull_fused=True)),
        (f"bfloat16/dedup_sr/compact{cap}/cd-bf16/segtotal",
         dict(segtotal_pallas=True)),
        (f"bfloat16/dedup_sr/compact{cap}/cd-bf16", {}),
        # devaux = the multi-chip-composable denominator (in-step aux
        # build; the only compact form that composes with scale-out —
        # PERF.md round 3). Measured at the floor cap WITH the composed
        # kernels so the multi-chip projection's discount is priced
        # against the same lever stack as the headline, not the bare
        # cd-bf16 base.
        (f"bfloat16/dedup_sr/compact{floor_cap}/devaux/cd-bf16"
         "/gfull/segtotal",
         dict(host_dedup=False, compact_device=True,
              compact_cap=floor_cap,
              gfull_fused=True, segtotal_pallas=True)),
    ]
    head = [
        (label, ("bfloat16", "bfloat16"),
         TrainConfig(**{**base, **extra}))
        for label, extra in ranked
    ]
    tail = [
        (f"{dt}/{su}/compact{cap}", (dt, None),
         TrainConfig(learning_rate=0.05, lr_schedule="constant",
                     optimizer="sgd", sparse_update=su,
                     host_dedup=True, compact_cap=cap))
        for su, dt in (("dedup", "float32"), ("dedup_sr", "bfloat16"))
    ]
    return head, tail


def _set_model(model: str) -> None:
    global METRIC, TARGET_PER_CHIP
    METRIC, TARGET_PER_CHIP = METRICS[model]


def _artifacts_dir(args) -> str:
    """Where the incremental sweep artifacts land (``--artifacts-dir``,
    default ``artifacts/`` next to this script)."""
    d = args.artifacts_dir or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "artifacts"
    )
    os.makedirs(d, exist_ok=True)
    return d


def _persist_incremental(dirpath, model, best_payload, leg_record):
    """Persist the sweep's state AS IT LANDS (warm-start tiering, ISSUE
    1): append this leg's measurement to ``sweep_<model>.jsonl`` and
    atomically replace ``keepbest_<model>.json`` with the cumulative
    best — so a bench killed mid-window (flaky attachment, outer
    timeout) leaves the best-so-far metric on disk instead of nothing.
    Best-effort by contract: persistence must never kill the sweep."""
    try:
        with open(os.path.join(dirpath, f"sweep_{model}.jsonl"), "a") as f:
            f.write(json.dumps(leg_record) + "\n")
        tmp = os.path.join(dirpath, f".keepbest_{model}.tmp")
        with open(tmp, "w") as f:
            f.write(json.dumps(best_payload) + "\n")
        os.replace(tmp, os.path.join(dirpath, f"keepbest_{model}.json"))
    except OSError as e:
        _log(f"[inner] incremental artifact write failed: {e!r}")


def _completed_legs(art_dir, model, labels, device_kind,
                    since: float = 0.0):
    """``--resume-sweep`` support: variant-label → last completed leg
    record from this model's ``sweep_<model>.jsonl``. Filtered to (a)
    labels in THIS sweep's grid — a changed grid or shape stamp
    re-measures, it never resumes a stale label; (b) records measured
    on THIS device kind — a CPU smoke sweep's rates must never ride a
    resume into an on-chip payload (where the keep-best path could
    stamp them TPU); (c) records stamped at/after ``since`` — the
    parent's own-start filter for auto-resume on retry, without which a
    retry would "resume" legs measured in a prior round's window.
    Best-effort: an unreadable artifact just means a full re-measure."""
    path = os.path.join(art_dir, f"sweep_{model}.jsonl")
    out = {}
    try:
        with open(path) as f:
            for line in f:
                # Per-record guard: one malformed record (ts: null, a
                # bool value, a non-dict line) skips that record, never
                # the whole resume — degraded artifacts are exactly
                # this path's operating condition.
                try:
                    rec = json.loads(line)
                    v = rec.get("value")
                    if (isinstance(v, bool)
                            or not (isinstance(v, (int, float)) and v > 0)):
                        continue
                    if rec.get("variant") not in labels:
                        continue
                    if rec.get("device") != device_kind:
                        continue
                    if float(rec.get("ts") or 0.0) < since:
                        continue
                    if rec.get("degraded"):
                        # A shrunk-denominator salvage rate must not
                        # ride a resume into an undegraded payload —
                        # the restarted process may have full capacity
                        # back, so the leg is simply re-measured.
                        continue
                    out[rec["variant"]] = rec
                except (AttributeError, TypeError, ValueError):
                    continue
    except OSError:
        pass
    return out


def _recorded_winner(metric: str):
    """The measured-best variant label recorded for this metric in
    MEASURED.json, or None — the fast-first tier measures it FIRST so
    the highest-value leg is in the can before the sweep's A/B legs
    start."""
    try:
        from fm_spark_tpu.measured import load_measured

        entry = METRIC_ENTRY.get(metric)
        return load_measured()[entry]["variant"] if entry else None
    except Exception:
        return None


def _log(msg):
    print(f"bench: {msg}", file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
# Child: the actual measurement. Runs in its own process so a hung/poisoned
# backend init can be killed and retried by the parent.
# --------------------------------------------------------------------------

def _last_measured_block():
    """The best PREVIOUSLY recorded on-chip rate for the current metric
    (MEASURED.json), provenance-stamped and marked stale — attached to
    every error JSON so even a dead-attachment round transports the
    best-known headline machine-readably (VERDICT r5 next-round #1)
    instead of a bare null. None when no record exists; best-effort by
    the final-line contract (an unreadable MEASURED.json must not break
    error emission)."""
    try:
        from fm_spark_tpu.measured import load_measured

        entry = METRIC_ENTRY.get(METRIC)
        if entry is None:
            return None
        rec = load_measured().get(entry)
        if rec is None:
            return None
        return {
            "value": rec["rate_samples_per_sec_per_chip"],
            "unit": UNIT,
            "vs_baseline": rec.get("vs_baseline"),
            "variant": rec.get("variant"),
            "attachment": rec.get("attachment"),
            "date": rec.get("date"),
            "source": rec.get("source"),
            "stale": True,
            "provenance": "MEASURED.json keep-best record — NOT this "
                          "round's measurement",
        }
    except Exception:
        return None


def _error_line(msg, permanent=None):
    payload = {
        "metric": METRIC, "value": None, "unit": UNIT,
        "vs_baseline": None, "error": msg,
    }
    if _RUN_ID:
        payload["run_id"] = _RUN_ID
    if permanent:
        # The parent's fault classifier concluded the attachment is
        # DEAD (N identical consecutive failures), not flapping —
        # downstream consumers should reschedule, not retry.
        payload["permanent"] = True
    last = _last_measured_block()
    if last is not None:
        payload["last_measured"] = last
    return json.dumps(payload)


def _classify_diags(diags, threshold=3):
    """Transient-vs-permanent verdict over the parent's child-failure
    diagnostics (resilience/elastic.py's classifier; lazy import so the
    happy path never pays it, best-effort so classification can never
    break the final-line contract)."""
    try:
        from fm_spark_tpu.resilience.elastic import classify_failures

        return classify_failures(diags, threshold)
    except Exception:
        return "transient"


def _dirty_input_leg(art_dir, model, log):
    """Hardened-ingest leg (ISSUE 5, ``--dirty-input``): stream a
    synthetic 3-shard Criteo-shaped dataset with deterministically
    corrupted mid-shard lines through the quarantine policy and measure
    the host-side ingest rate. Host-only (no device involvement) and
    cheap, so it runs before the sweep and its stats land in the result
    JSON even when the attachment later dies: ``bad_records`` is the
    dead-lettered count and ``quarantine_exact`` asserts it equals the
    injected corruption — the bench-level witness that dirty input
    degrades to quarantine accounting instead of a crash or silent
    noise."""
    import shutil
    import tempfile

    import numpy as np

    from fm_spark_tpu.data import criteo
    from fm_spark_tpu.data.stream import RecordGuard, ShardReader

    tmp = tempfile.mkdtemp(prefix="fm_dirty_")
    try:
        rng = np.random.default_rng(0)
        paths = []
        n_per, n_shards, injected = 2000, 3, 0
        for s in range(n_shards):
            p = os.path.join(tmp, f"shard{s}.tsv")
            criteo.synthesize_tsv(p, n_per, seed=s)
            with open(p, "rb") as f:
                lines = f.read().splitlines(keepends=True)
            # Flip bytes mid-shard: ~1% of lines, deterministic.
            for k in rng.choice(np.arange(10, n_per - 10),
                                size=n_per // 100, replace=False):
                lines[int(k)] = b"\x00corrupt\t" + lines[int(k)][:9] + b"\n"
                injected += 1
            with open(p, "wb") as f:
                f.write(b"".join(lines))
            paths.append(p)
        bucket = 1 << 14
        total = n_shards * n_per

        def _run(native_ingest, qdir):
            """One full pass under quarantine; returns (guard, dt)."""
            from fm_spark_tpu.data.native_stream import make_stream_batches

            shutil.rmtree(qdir, ignore_errors=True)
            guard = RecordGuard("quarantine", quarantine_dir=qdir,
                                max_bad_frac=0.5)
            batches = make_stream_batches(
                ShardReader(paths), "criteo", 512, criteo.NUM_FIELDS,
                guard=guard, num_features=criteo.NUM_FIELDS * bucket,
                bucket=bucket,
                native_ingest=native_ingest,
            )
            t0 = time.perf_counter()
            while guard.n_ok + guard.n_bad < total:
                batches.next_batch()
            return guard, time.perf_counter() - t0

        # Priced BOTH ways (ISSUE 6): the per-line Python parser and the
        # native chunk parser run the same dirty pass with identical
        # quarantine semantics — the result JSON carries both rates so
        # the native win (and any accounting drift) stays attributable.
        guard, dt = _run(False, os.path.join(art_dir, f"quarantine_{model}"))
        stats = {
            "rows": total,
            "bad_records": guard.n_bad,
            "injected_bad": injected,
            "quarantine_exact": guard.n_bad == injected,
            "rows_per_sec": round(total / dt, 1),
            "policy": "quarantine",
        }
        log(f"[inner] [dirty-input] {total} rows in {dt:.2f}s "
            f"({stats['rows_per_sec']:,.0f} rows/sec, python parse); "
            f"{guard.n_bad}/{injected} corrupt lines quarantined")
        from fm_spark_tpu.data.native_stream import native_stream_supported

        if native_stream_supported("criteo", criteo.NUM_FIELDS, bucket):
            nguard, ndt = _run(
                "auto", os.path.join(art_dir, f"quarantine_{model}_native"))
            stats["rows_per_sec_native"] = round(total / ndt, 1)
            stats["native_quarantine_exact"] = nguard.n_bad == injected
            stats["native_counters_match"] = (
                nguard.counters() == guard.counters())
            log(f"[inner] [dirty-input] {total} rows in {ndt:.2f}s "
                f"({stats['rows_per_sec_native']:,.0f} rows/sec, native "
                f"chunk parse); {nguard.n_bad}/{injected} quarantined, "
                f"counters match: {stats['native_counters_match']}")
        return stats
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def inner_main(args):
    t_start = time.perf_counter()
    _log("[inner] importing jax + initializing backend...")

    # Resilience wiring (ISSUE 2): the health-event journal + the
    # supervisor/fault machinery arm BEFORE the backend touch, so
    # init-path failures are journaled and deterministically injectable
    # (the package import pulls fm_spark_tpu, and thus jax — which this
    # child is about to import anyway; backend INIT still happens only
    # at the jax.devices() below).
    from fm_spark_tpu.resilience import (
        BackoffPolicy,
        CircuitOpen,
        RetriesExhausted,
        Supervisor,
        faults,
        is_device_loss,
    )
    from fm_spark_tpu import obs
    from fm_spark_tpu.utils.logging import EventLog

    art_dir = _artifacts_dir(args)
    # Per-run telemetry directory (ISSUE 7): every stream this run
    # emits — spans, metrics snapshots, the flight-recorder window, and
    # the health journal — lives under <artifacts>/obs/<run_id>/. The
    # parent mints the run id and passes it down so retried attempts
    # append to the SAME run (journal included), and the id is echoed
    # in every result line.
    global _RUN_ID
    run_id = _RUN_ID = args.run_id or _gen_run_id()
    obs_dir = _obs_run_dir(art_dir, run_id)
    obs.configure(obs_dir, run_id=run_id, install_signals=True)
    # Live introspection (ISSUE 14): the capture engine arms over this
    # run dir — a sentinel `regressed` verdict on any leg below fires a
    # bounded capture bundle while the slow program is still resident —
    # and --metrics-port serves the live registry while the sweep runs.
    from fm_spark_tpu.obs import introspect

    introspect.configure(obs_dir, run_id=run_id)
    if args.metrics_port is not None:
        from fm_spark_tpu.obs import export as obs_export

        _msrv = obs_export.start_metrics_server(args.metrics_port)
        print(json.dumps({"metrics_port": _msrv.port,
                          "metrics_url": _msrv.url}), flush=True)
    journal = EventLog(os.path.join(obs_dir,
                                    f"health_{args.model}.jsonl"),
                       mirror_to_flight=True)
    journal.emit("backend_init_start", model=args.model)

    # Init watchdog: a backend init still running after --init-timeout is
    # treated as hung; exiting early lets the parent retry within its
    # total deadline instead of burning the full attempt timeout.
    init_done = threading.Event()

    def _init_watchdog():
        if not init_done.wait(args.init_timeout):
            journal.emit("backend_init_timeout",
                         timeout_s=args.init_timeout)
            print(_error_line(
                f"backend init exceeded {args.init_timeout:.0f}s "
                "(init watchdog)"), flush=True)
            _log(f"[inner] init watchdog fired at {args.init_timeout:.0f}s"
                 " -- exiting for parent retry")
            os._exit(3)

    threading.Thread(target=_init_watchdog, daemon=True).start()
    # The injected init faults (hang / exit:3) fire HERE — after the
    # watchdog arms, before the real backend touch — reproducing the
    # observed attachment failure modes on any backend (faults.py).
    faults.inject("backend_init")
    import jax

    # Warm start: the persistent compile cache turns the second
    # process's XLA compilation into a disk read — on BEFORE the first
    # compile (utils/compile_cache says where it lives).
    from fm_spark_tpu.utils import compile_cache

    cache_dir = compile_cache.enable()
    _log(f"[inner] persistent compile cache at {cache_dir}")

    import jax.numpy as jnp
    from jax import lax

    from fm_spark_tpu.utils import device as device_lib

    devs = jax.devices()  # forces backend init
    init_done.set()
    # First stdout line: the device every rate below came from.
    device = device_lib.describe()
    print(json.dumps({"device": device, "compile_cache": cache_dir}),
          flush=True)
    journal.emit("backend_init_up",
                 seconds=round(time.perf_counter() - t_start, 1),
                 devices=len(devs), kind=devs[0].device_kind)
    _log(f"[inner] backend up in {time.perf_counter() - t_start:.1f}s: "
         f"{len(devs)} x {devs[0].device_kind}")

    # Perf provenance (ISSUE 9): every completed leg is appended to the
    # cross-run ledger (artifacts/obs/ledger.jsonl) with a measurement
    # fingerprint — lever-config hash, chip kind + count, jax/libtpu
    # versions, degraded/fused_fallback stamps, and the supervisor-
    # journal attachment-health verdict — and judged by the noise-aware
    # sentinel against its (leg, fingerprint) cohort history BEFORE the
    # record lands. The verdict rides the leg record, the result JSON,
    # and (via the parent's keep-best gate) the MEASURED.json decision.
    from fm_spark_tpu.obs.ledger import runtime_versions

    ledger = obs.PerfLedger(obs.default_ledger_path(art_dir))
    sentinel = obs.Sentinel(ledger)
    _versions = runtime_versions()

    from fm_spark_tpu import models
    from fm_spark_tpu.sparse import (
        make_field_deepfm_sparse_body,
        make_field_ffm_sparse_sgd_body,
        make_field_sparse_sgd_body,
    )
    from fm_spark_tpu.train import TrainConfig

    import numpy as np

    _set_model(args.model)
    if args.model == "ffm":
        # Config 4's shape (configs.avazu_ffm_r16): 23 fields, 16384
        # per-field buckets, rank 16.
        num_fields, bucket = 23, 1 << 14
        rank = args.rank or DEFAULT_RANK["ffm"]
    elif args.model == "deepfm":
        # Config 5's shape (configs.criteo1tb_deepfm): 39 fields,
        # 262144 buckets, rank 16, 3x400 MLP head on dense Adam.
        num_fields, bucket = 39, 1 << 18
        rank = args.rank or DEFAULT_RANK["deepfm"]
    elif args.model == "fm_kaggle":
        # Config 2's shape (configs.criteo_kaggle_fm_r32): 39 fields,
        # 32768 per-field buckets, rank 32 — per-field tables are SMALL
        # (2.1MB bf16), so the avazu small-table lesson applies and the
        # grid stages the cd-bf16-over-fp32 candidate first.
        num_fields, bucket = 39, 1 << 15
        rank = args.rank or DEFAULT_RANK["fm_kaggle"]
    else:
        num_fields, bucket = 39, 262_144
        rank = args.rank or DEFAULT_RANK["fm"]
    batch = args.batch
    steps_warmup = 3
    steps_timed = args.steps

    def make_spec(param_dtype, compute_dtype=None):
        if args.model == "ffm":
            return models.FieldFFMSpec(
                num_features=num_fields * bucket, rank=rank,
                num_fields=num_fields, bucket=bucket, init_std=0.01,
                param_dtype=param_dtype,
                compute_dtype=compute_dtype or args.compute_dtype,
            )
        if args.model == "deepfm":
            return models.FieldDeepFMSpec(
                num_features=num_fields * bucket, rank=rank,
                num_fields=num_fields, bucket=bucket, init_std=0.01,
                mlp_dims=(400, 400, 400),
                param_dtype=param_dtype,
                compute_dtype=compute_dtype or args.compute_dtype,
            )
        return models.FieldFMSpec(
            num_features=num_fields * bucket, rank=rank,
            num_fields=num_fields, bucket=bucket, init_std=0.01,
            param_dtype=param_dtype,
            compute_dtype=compute_dtype or args.compute_dtype,
        )

    rng = np.random.default_rng(0)
    # Criteo-like Zipf skew within each field's bucket.
    ids_np = (rng.zipf(1.3, size=(batch, num_fields)) % bucket).astype(np.int32)
    ids = jnp.asarray(ids_np)
    vals = jnp.ones((batch, num_fields), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 2, batch), jnp.float32)
    weights = jnp.ones((batch,), jnp.float32)

    # Variant sweep: with explicit knobs, measure exactly what was asked;
    # with pure defaults, ALSO measure the host-dedup candidate (PERF.md
    # round-3 lever) and report the fastest — the headline is "the
    # framework's best configuration", decided by measurement, not by a
    # default frozen before the chip could confirm it.
    lever_explicit = (args.sparse_update != "scatter_add"
                      or args.use_pallas
                      or args.host_dedup or args.param_dtype != "float32"
                      or args.compute_dtype != "float32"
                      or args.compact_cap
                      or args.compact_device or args.gfull_fused
                      or args.segtotal_pallas
                      or args.fused_embed != "off"
                      or args.embed_tier != "off")
    shape_explicit = (args.rank is not None or args.batch != 1 << 17
                      or args.steps != 20)
    # --fast-first keeps the tiered variant sweep even at a non-default
    # SHAPE (batch/steps/rank only change what one leg measures — the
    # stamp below keeps the provenance honest); explicit LEVER knobs
    # still mean "measure exactly this one program".
    explicit = lever_explicit or (shape_explicit and not args.fast_first)
    variants = [(
        f"{args.param_dtype}/{args.sparse_update}"
        + ("/pallas" if args.use_pallas else "")
        + (f"/compact{args.compact_cap}" if args.compact_cap
           else "/hostdedup" if args.host_dedup else "")
        + ("/devaux" if args.compact_device else "")
        + ("/cd-bf16" if args.compute_dtype == "bfloat16" else "")
        + ("/gfull" if args.gfull_fused else "")
        + ("/segtotal" if args.segtotal_pallas else "")
        + (f"/fused-{args.fused_embed}" if args.fused_embed != "off"
           else "")
        + (f"/tier-{args.embed_tier}" if args.embed_tier != "off"
           else ""),
        (args.param_dtype, None),
        TrainConfig(learning_rate=0.05, lr_schedule="constant",
                    optimizer="sgd", sparse_update=args.sparse_update,
                    use_pallas=args.use_pallas, host_dedup=args.host_dedup,
                    compact_cap=args.compact_cap,
                    compact_device=args.compact_device,
                    gfull_fused=args.gfull_fused,
                    segtotal_pallas=args.segtotal_pallas,
                    fused_embed=args.fused_embed,
                    embed_tier=args.embed_tier, hot_rows=args.hot_rows,
                    embed_bucket_rows=args.embed_bucket_rows),
    )]
    if not explicit:
        head, tail = default_variants(args.model, batch)
        variants[0:0] = head
        variants.extend(tail)
        if args.fast_first:
            # Tier 1 = the RECORDED winner (MEASURED.json), measured
            # before any A/B leg: with a warm compile cache its result
            # JSON lands in seconds, so even a window that dies right
            # after still beats a null artifact. The head is already
            # ranked best-first, so this only reorders when the record
            # disagrees with the static ranking.
            rec = _recorded_winner(METRIC)
            idx = next((i for i, (l, _, _) in enumerate(variants)
                        if l == rec), None)
            if idx:
                variants.insert(0, variants.pop(idx))
            _log(f"[inner] fast-first: leg 1 = "
                 f"{variants[0][0]!r}"
                 + (f" (recorded winner)" if idx is not None else
                    " (ranked head; no recorded winner in sweep)"))

    # Batch and rank are part of a rate's provenance (a doubled batch
    # amortizes fixed per-step work; a different rank is a different
    # program entirely), so non-default values are stamped into every
    # label and such rates can never keep-best into MEASURED.json
    # (comparable_variant below).
    stamp = ""
    if args.batch != 1 << 17:
        stamp += f"/b{args.batch}"
    if args.rank is not None and args.rank != DEFAULT_RANK[args.model]:
        stamp += f"/r{args.rank}"
    if stamp:
        variants = [(f"{label}{stamp}", dtypes, config)
                    for label, dtypes, config in variants]

    import functools

    aux_cache = {}

    def build_variant(dtypes, config):
        spec = make_spec(*dtypes)
        init_opt = None
        if args.model == "ffm":
            body = make_field_ffm_sparse_sgd_body(spec, config)
        elif args.model == "deepfm":
            body, init_opt = make_field_deepfm_sparse_body(spec, config)
        else:
            body = make_field_sparse_sgd_body(spec, config)
        aux = None
        if config.host_dedup:
            # Aux for the (fixed) bench batch is computed once here; in
            # production it rides the prefetch thread (DedupAuxBatches) —
            # bench_input.py --host-dedup measures that host-side rate.
            akey = config.compact_cap  # 0 = full-B dedup aux
            if akey not in aux_cache:
                from fm_spark_tpu.ops.scatter import compact_aux, dedup_aux

                aux_cache[akey] = jax.device_put(
                    compact_aux(ids_np, akey) if akey else dedup_aux(ids_np)
                )
            aux = aux_cache[akey]
        return spec, init_opt, body, aux

    # Per-leg supervision (ISSUE 2): a transient device loss mid-leg is
    # retried with bounded backoff instead of forfeiting the leg; the
    # circuit breaker abandons the REMAINING legs when the attachment
    # keeps dying (salvaging completed measurements beats burning the
    # deadline re-crashing), and every transition lands in the health
    # journal next to the sweep artifacts.
    sup = Supervisor(
        policy=BackoffPolicy(initial=2.0, multiplier=2.0, max_delay=30.0,
                             max_attempts=3),
        journal=journal, breaker_threshold=3,
    )
    # Elastic degraded mode (ISSUE 4): when a leg's retries exhaust on a
    # PERMANENT fault (identical consecutive device losses — dead
    # capacity, not a flap), shed chips instead of abandoning the sweep:
    # the controller halves the device set, the breaker re-arms, the leg
    # re-runs, and every subsequent rate is normalized per SURVIVING
    # chip with the payload stamped degraded — a measured result on a
    # shrunk mesh instead of an error-only artifact.
    elastic = None
    if args.elastic:
        from fm_spark_tpu.resilience import ElasticController

        elastic = ElasticController(devices=devs,
                                    max_shrinks=args.max_shrinks,
                                    journal=journal)
    n_chips = len(devs)

    dirty_stats = None
    if args.dirty_input:
        # Best-effort: a broken dirty leg must not forfeit the device
        # sweep (the mirror of the per-variant guards below).
        try:
            dirty_stats = _dirty_input_leg(art_dir, args.model, _log)
            journal.emit("dirty_input_leg", **dirty_stats)
        except Exception as e:  # noqa: BLE001 — diagnosable, not fatal
            _log(f"[inner] [dirty-input] FAILED ({type(e).__name__}): "
                 f"{(str(e).splitlines() or [''])[0][:200]}")

    t_first_result = None  # wall-clock to the FIRST emitted result
    results = []
    # Per-label sentinel verdict blocks (resumed legs reload theirs
    # from the sweep artifact) — what emit_best stamps into the
    # payload's sentinel/all_verdicts fields.
    leg_verdicts = {}
    # Labels whose fused_embed='auto' resolved to the XLA path (ISSUE
    # 8): the rate is a valid XLA measurement, but its provenance says
    # "fused requested, not served" — stamped into the leg record and
    # the payload so the parent's keep-best gate can refuse it.
    fused_fallback_legs = set()
    resumed = {}
    if args.resume_sweep:
        resumed = _completed_legs(
            art_dir, args.model, {l for l, _, _ in variants},
            device_kind=devs[0].device_kind, since=args.resume_since,
        )

    def emit_best():
        """Print the cumulative-best result line (the parent's salvage
        scan takes the LAST one) and return the payload."""
        nonlocal t_first_result
        if t_first_result is None:
            t_first_result = round(time.perf_counter() - t_start, 1)
        best_rate, best_label, _, _ = max(results)
        payload = {
            "metric": METRIC,
            "value": round(best_rate, 1),
            "unit": UNIT,
            "vs_baseline": (round(best_rate / TARGET_PER_CHIP, 4)
                            if TARGET_PER_CHIP else None),
            "variant": best_label,
            "platform": device["platform"],
            "device": device["kind"],
            "n_devices": device["count"],
            "all_variants": {l: round(r, 1) for r, l, _, _ in results},
            "legs_completed": len(results),
            "t_first_result_s": t_first_result,
            "run_id": run_id,
            # Step-time percentiles (per-leg mean step times), ingest
            # rate/accounting, fault timeline — the substrate ROADMAP
            # items 1/3/5 read their numbers from (ISSUE 7).
            "telemetry": obs.telemetry_block(),
        }
        # Sentinel stamps (ISSUE 9): the promoted leg's full verdict
        # block — the parent's keep-best gate refuses anything but
        # improved/flat — plus the per-leg verdict map.
        if best_label in leg_verdicts:
            payload["sentinel"] = leg_verdicts[best_label]
        payload["all_verdicts"] = {
            label: (block or {}).get("verdict")
            for label, block in leg_verdicts.items()
        }
        if resumed:
            payload["resumed_legs"] = len(resumed)
        if dirty_stats is not None:
            # The dirty-input leg's quarantine accounting rides the
            # result JSON (ISSUE 5): bad_records = dead-lettered count.
            payload["dirty_input"] = dirty_stats
            payload["bad_records"] = dirty_stats["bad_records"]
        if elastic is not None and elastic.degraded:
            # A shrunk-mesh rate must never masquerade as a full-mesh
            # one: stamp the degraded provenance (chips = the surviving
            # count the per-chip rate is normalized to).
            payload.update(elastic.summary())
        if best_label in fused_fallback_legs:
            # A fused-requested leg that ran the XLA path must never
            # become the recorded keep-best under its fused label
            # (ISSUE 8); the parent's _emit_final gate refuses this
            # stamp exactly like a degraded one.
            payload["fused_fallback"] = True
        if args.chaos:
            # A chaos-drill rate measured a run under injected faults
            # (ISSUE 10) — its own cohort, never the recorded
            # capability; the parent's _emit_final gate refuses it.
            payload["chaos"] = True
        print(json.dumps(payload), flush=True)
        return payload

    if resumed:
        # --resume-sweep: completed legs from the persisted sweep
        # artifact seed the results, and the best-so-far line is emitted
        # BEFORE any remaining leg runs — a restart after a mid-window
        # kill re-enters through the warm compile cache and is
        # salvageable from its first second, without re-measuring what
        # already landed.
        for label, rec in resumed.items():
            dt_banked = float(rec.get("dt_s", 0.0))
            results.append((float(rec["value"]), label,
                            dt_banked, float(rec.get("loss", 0.0))))
            if rec.get("fused_fallback"):
                fused_fallback_legs.add(label)
            if rec.get("sentinel"):
                # The banked leg was already judged (and ledgered) by
                # the attempt that measured it — re-observing would
                # double-count it in its own cohort history.
                leg_verdicts[label] = dict(rec["sentinel"],
                                           resumed=True)
            # Banked legs still belong in the telemetry percentiles:
            # obs.configure reset the registry for this attempt, so
            # without replaying the banked per-leg mean the final
            # telemetry block would cover only re-measured legs.
            if dt_banked > 0:
                obs.histogram("step_time_ms").observe(
                    dt_banked / steps_timed * 1e3)
        remaining = sum(1 for l, _, _ in variants if l not in resumed)
        _log(f"[inner] --resume-sweep: {len(resumed)} completed leg(s) "
             f"loaded from the sweep artifact; {remaining} remaining")
        journal.emit("resume_sweep", resumed_legs=len(resumed),
                     remaining_legs=remaining)
        emit_best()

    for label, dtypes, config in variants:
        if label in resumed:
            _log(f"[inner] [{label}] resumed from sweep artifact "
                 f"({resumed[label]['value']:,.1f} {UNIT}) -- skipping")
            continue
        # Everything variant-specific — INCLUDING the host aux build,
        # whose CompactCapOverflow is exactly the failure a staged
        # tight-cap variant can hit at an unmeasured batch — sits inside
        # one guard so a broken variant is skipped, not sweep-fatal.
        try:
            spec, init_opt, body, aux = build_variant(dtypes, config)
        except Exception as e:  # noqa: BLE001 — same rationale as the
            # warmup/timing guard below
            _log(f"[inner] [{label}] construction FAILED "
                 f"({type(e).__name__}): "
                 f"{(str(e).splitlines() or [''])[0][:200]}"
                 " -- skipping variant")
            continue
        if config.fused_embed == "auto":
            # The 'auto' lever's fallback is queryable, never silent
            # (ISSUE 8): resolve the plan ONCE here and stamp the leg
            # when the XLA path is what actually runs.
            from fm_spark_tpu.sparse import fused_embed_plan

            fam, fb_reason = fused_embed_plan(spec, config)
            if fam is None:
                fused_fallback_legs.add(label)
                _log(f"[inner] [{label}] fused-embed XLA fallback "
                     f"({fb_reason}) -- leg will never keep-best")
        # n_steps is a DYNAMIC argument so the warmup call compiles the
        # exact program the timed call runs (a static count would
        # recompile inside the timed region). DeepFM threads its dense
        # optax state through the carry (same shape as the multistep
        # roll); the other models carry (params, loss) only.
        if init_opt is not None:
            # (params, opt, loss) carry; params + opt donated.
            @functools.partial(jax.jit, donate_argnums=(0, 1))
            def run_df(params, opt, ids, vals, labels, weights, aux,
                       n_steps, body=body):
                def fbody(i, carry):
                    p, o, _ = carry
                    return body(p, o, i, ids, vals, labels, weights, aux)

                return lax.fori_loop(0, n_steps, fbody,
                                     (params, opt, jnp.float32(0)))

            jit_fn = run_df

            def run(carry, *a):
                return run_df(carry[0], carry[1], *a)
        else:
            # (params, loss) carry; params donated.
            @functools.partial(jax.jit, donate_argnums=(0,))
            def run_pl(params, ids, vals, labels, weights, aux, n_steps,
                       body=body):
                def fbody(i, carry):
                    p, _ = carry
                    return body(p, i, ids, vals, labels, weights, aux)

                return lax.fori_loop(0, n_steps, fbody,
                                     (params, jnp.float32(0)))

            jit_fn = run_pl

            def run(carry, *a):
                return run_pl(carry[0], *a)

        if args.fast_first and not results and compile_cache.is_enabled():
            # AOT warm-start: lower + compile leg 1's program against
            # ABSTRACT shapes before the multi-GB tables are even
            # initialized — on a warm cache this is a deserialize (the
            # whole point: the healthy window starts MEASURING in
            # seconds); on a cold one it populates the cache for every
            # later process. The later run() call re-traces but its XLA
            # compile hits the same cache entry. Skipped when the cache
            # is off (the work would be thrown away) and best-effort:
            # an AOT failure must not cost the leg.
            try:
                from fm_spark_tpu.sparse import abstract_field_batch

                t_aot = time.perf_counter()
                sds = jax.ShapeDtypeStruct
                params_abs = jax.eval_shape(spec.init, jax.random.key(0))
                batch_abs = abstract_field_batch(spec, batch)
                aux_abs = (None if aux is None else jax.tree_util.tree_map(
                    lambda a: sds(a.shape, a.dtype), aux))
                n_abs = sds((), jnp.int32)
                if init_opt is not None:
                    opt_abs = jax.eval_shape(init_opt, params_abs)
                    jit_fn.lower(params_abs, opt_abs, *batch_abs,
                                 aux_abs, n_abs).compile()
                else:
                    jit_fn.lower(params_abs, *batch_abs,
                                 aux_abs, n_abs).compile()
                cs = compile_cache.cache_stats()
                _log(f"[inner] [{label}] AOT precompile in "
                     f"{time.perf_counter() - t_aot:.1f}s (cache: "
                     f"{cs['hits']} hits / {cs['misses']} misses, "
                     f"{cs['entries']} entries)")
            except Exception as e:  # noqa: BLE001 — best-effort
                _log(f"[inner] [{label}] AOT precompile failed "
                     f"({type(e).__name__}): "
                     f"{(str(e).splitlines() or [''])[0][:200]}")

        def measure(label=label, spec=spec, init_opt=init_opt, run=run,
                    aux=aux):
            """One supervised measurement attempt. The ``sweep_leg``
            fault point fires first (the deterministic mid-sweep device
            loss), then FRESH tables — params are donated into the step,
            so every retry must rebuild them; the local scope also
            guarantees the tables are dropped before the next variant's
            init (two resident sets would double peak HBM)."""
            faults.inject("sweep_leg")
            params = spec.init(jax.random.key(0))
            carry = (
                (params, init_opt(params), jnp.float32(0))
                if init_opt is not None else (params, jnp.float32(0))
            )
            _log(f"[inner] [{label}] compiling + warmup (first TPU "
                 "compile is slow, ~20-60s)...")
            t0 = time.perf_counter()
            carry = run(carry, ids, vals, labels, weights, aux,
                        jnp.int32(steps_warmup))
            float(carry[-1])  # d2h fence
            _log(f"[inner] [{label}] warmup done in "
                 f"{time.perf_counter() - t0:.1f}s; timing {steps_timed} "
                 f"steps x batch {batch}...")
            t0 = time.perf_counter()
            carry = run(carry, ids, vals, labels, weights, aux,
                        jnp.int32(steps_timed))
            final_loss = float(carry[-1])  # d2h fence
            return time.perf_counter() - t0, final_loss

        # Supervision scope: the per-leg retry recovers TRANSIENT
        # losses (a raise that leaves the process healthy — the
        # injectable kind, and brief flaps surfaced as step errors). A
        # WEDGED backend is beyond in-process repair — the retry reuses
        # this leg's jitted executable and device-resident aux, and a
        # dead backend may hang rather than raise — so that mode stays
        # the parent watchdog's job: attempt timeout →
        # kill → respawn → auto --resume-sweep of the banked legs.
        outcome = None
        t_leg_wall, t_leg0 = time.time(), time.perf_counter()
        # Failure delta over THIS leg: the fingerprint's attachment-
        # health verdict is per-measurement weather, not run-lifetime
        # state (one early flap must not stamp every later leg flaky).
        leg_fail0 = sup.total_failures
        while outcome is None:
            try:
                dt, final_loss = sup.run(measure, op=f"leg:{label}",
                                         retryable=is_device_loss)
                outcome = "ok"
            except (CircuitOpen, RetriesExhausted) as e:
                if (elastic is not None and sup.permanent()
                        and elastic.can_shrink()):
                    # Permanent fault + capacity to shed: degrade
                    # instead of abandoning. The shrink is journaled,
                    # the breaker re-arms, and the SAME leg re-runs.
                    # What the shrink changes here is the ACCOUNTING,
                    # not the placement: the leg is a single-process
                    # measurement whose per-chip rate divides by the
                    # fleet the result claims to represent, so the
                    # denominator drops to the surviving count and the
                    # payload is stamped degraded (and never keep-bests
                    # into MEASURED.json). A fresh retry window is the
                    # other half of the value — bounded by max_shrinks,
                    # so a default device that is truly dead still
                    # abandons after the ladder is spent.
                    prev_chips = n_chips
                    n_chips = len(elastic.shrink(f"leg:{label}"))
                    # Keep every banked rate on ONE denominator: legs
                    # measured before the shrink re-normalize to the
                    # surviving count, so max() ranks variants on
                    # comparable per-chip figures instead of letting a
                    # post-shrink leg win on a 2x smaller divisor.
                    results[:] = _renormalize_results(results, prev_chips,
                                                      n_chips)
                    sup.reset(f"leg:{label}")
                    _log(f"[inner] [{label}] permanent device fault -- "
                         f"degraded mode: retrying on {n_chips} chip(s) "
                         f"(shrink {elastic.shrinks}/{elastic.max_shrinks})")
                    continue
                if isinstance(e, CircuitOpen):
                    _log(f"[inner] circuit open ({e}) -- abandoning the "
                         "remaining legs; completed measurements still "
                         "count")
                    outcome = "abandon"
                else:
                    # A device loss that exhausted its retries (mixed
                    # failure modes, or no elastic capacity left); its
                    # history is in the health journal.
                    _log(f"[inner] [{label}] FAILED "
                         f"({type(e).__name__}): "
                         f"{(str(e).splitlines() or [''])[0][:200]}"
                         " -- skipping variant")
                    outcome = "skip"
            except Exception as e:  # noqa: BLE001 — one broken variant
                # (e.g. a Mosaic lowering reject, round 5's segtotal
                # block-spec ValueError) must not kill the remaining
                # A/Bs; the parent's retry would re-crash on the same
                # variant and the sweep would never price the rest.
                # Hangs are the watchdog's job.
                _log(f"[inner] [{label}] FAILED ({type(e).__name__}): "
                     f"{(str(e).splitlines() or [''])[0][:200]}"
                     " -- skipping variant")
                outcome = "skip"
        # Retroactive per-leg span (compile+warmup+timed window+any
        # retries): the report's phase breakdown attributes the sweep's
        # wall-clock leg by leg without fencing inside the measurement.
        obs.emit_span("bench/leg", t_leg_wall,
                      time.perf_counter() - t_leg0,
                      label=label, outcome=outcome)
        if outcome == "abandon":
            break
        if outcome == "skip":
            continue
        if not np.isfinite(final_loss):
            # compact_device signals cap overflow by POISONING the loss
            # (-inf; sparse.py _fold_overflow) instead of raising like
            # the host aux build — a poisoned run's rate is a
            # measurement of a corrupted program and must not enter
            # results (it could win max() and reach MEASURED.json).
            _log(f"[inner] [{label}] non-finite final loss "
                 f"({final_loss}) — overflow/divergence poison; "
                 "skipping variant")
            continue
        rate = steps_timed * batch / dt / n_chips
        results.append((rate, label, dt, final_loss))
        # One step-time sample per leg (the timed window's mean step —
        # the fori_loop rolls the steps into one program, so per-step
        # fencing would change the measurement): percentiles across
        # legs land in the telemetry block.
        obs.histogram("step_time_ms").observe(dt / steps_timed * 1e3)
        # Device-memory watermark right after the leg, while its tables
        # are still resident: HBM peak rides the leg record next to the
        # rate (the registry gauges feed the telemetry block too).
        mem = obs.device_memory_snapshot(devs) or {}
        # Fingerprint + sentinel verdict (ISSUE 9): judge this rate
        # against the cohort history, then append it — best-effort by
        # the telemetry contract (a broken ledger must not cost the
        # leg), but a verdict failure is logged, never silent.
        degraded_now = elastic is not None and elastic.degraded
        leg_health = ("degraded" if degraded_now else
                      "flaky" if (sup.total_failures - leg_fail0) > 0
                      else sup.health_verdict())
        fingerprint = obs.measurement_fingerprint(
            variant=label, model=args.model, batch=batch,
            steps=steps_timed, rank=rank,
            device_kind=devs[0].device_kind, n_chips=n_chips,
            jax_version=_versions["jax_version"],
            libtpu_version=_versions["libtpu_version"],
            degraded=degraded_now,
            fused_fallback=label in fused_fallback_legs,
            chaos=args.chaos,
            attachment_health=leg_health,
        )
        reused_ledger_record = False
        try:
            # Crash window on a RETRIED attempt only (the lookup costs
            # a ledger scan, so the common fresh path skips it): the
            # aborted attempt appended this leg's ledger record but
            # died before _persist_incremental banked it, so the
            # resume scan re-measured the leg.
            prior = [r for r in ledger.records(kind="bench_leg",
                                               leg=METRIC,
                                               run_id=run_id)
                     if r.get("variant") == label
                     ] if args.resume_sweep else []
            if prior and prior[-1].get("sentinel"):
                reused_ledger_record = True
                # Judge the RE-MEASURED rate against the recorded
                # history (which already contains the aborted
                # attempt's row) WITHOUT appending a duplicate
                # (run_id, leg, variant) record — the verdict stays
                # truthful about this value, the history stays
                # duplicate-free.
                leg_verdicts[label] = dict(
                    sentinel.judge(METRIC, round(rate, 1), fingerprint),
                    reused_ledger_record=True)
            else:
                leg_verdicts[label] = sentinel.observe({
                    "kind": "bench_leg", "leg": METRIC,
                    "run_id": run_id,
                    "variant": label, "value": round(rate, 1),
                    "unit": UNIT, "dt_s": round(dt, 3),
                    "loss": round(final_loss, 6),
                    # PJRT's peak_bytes_in_use is the PROCESS-
                    # cumulative high-water mark at leg end (no reset
                    # API exists): legs after the sweep's largest
                    # inherit its peak.
                    "hbm_peak_bytes": mem.get("peak_bytes_in_use"),
                    "fingerprint": fingerprint,
                })
            _log(f"[inner] [{label}] sentinel: "
                 f"{leg_verdicts[label]['verdict']} "
                 f"({leg_verdicts[label]['reason']})")
        except Exception as e:  # noqa: BLE001 — ledger is best-effort
            _log(f"[inner] [{label}] ledger/sentinel failed "
                 f"({type(e).__name__}): "
                 f"{(str(e).splitlines() or [''])[0][:200]}")
        # Per-leg cost attribution (ISSUE 14): pair the measured step
        # time with the leg's bytes-moved model (the same traffic-term
        # families bench_kernels.py prices per kernel) into a
        # `cost_attribution` ledger record — the autotuner's evidence
        # base (ROADMAP item 4) grows on every sweep, not only at
        # pricing time. value = model-implied GB/s. A resumed leg whose
        # aborted attempt already ledgered is SKIPPED, same dedup as
        # the bench_leg record above (the two appends travel together,
        # so the crash window leaves both or neither) — one record per
        # (run_id, variant).
        if not reused_ledger_record:
            try:
                pb = 2 if dtypes[0] == "bfloat16" else 4
                cb = 2 if dtypes[1] == "bfloat16" else 4
                cost = introspect.step_cost_model(
                    args.model, batch, rank, cap=config.compact_cap,
                    param_bytes=pb, compute_bytes=cb)
                step_s = dt / steps_timed
                ledger.append({
                    "kind": "cost_attribution",
                    "leg": f"cost/{METRIC}",
                    "run_id": run_id, "variant": label,
                    "value": round(cost["bytes_total"] / step_s / 1e9,
                                   3),
                    "unit": "GB/s(model)",
                    "step_ms": round(step_s * 1e3, 3),
                    "bytes_per_step": cost["bytes_total"],
                    "families": cost["families"],
                    "assumptions": cost["assumptions"],
                    "fingerprint": fingerprint,
                })
            except Exception as e:  # noqa: BLE001 — best-effort rule
                _log(f"[inner] [{label}] cost-attribution append "
                     f"failed ({type(e).__name__}): "
                     f"{(str(e).splitlines() or [''])[0][:200]}")
        _log(f"[inner] [{label}] {rate:,.0f} samples/sec/chip "
             f"(dt={dt:.3f}s loss={final_loss:.4f})")
        # Emit the best-so-far line after EVERY variant: if a later
        # variant hangs/crashes, the parent's salvage
        # scan still finds a valid completed measurement (it takes the
        # LAST matching line). In --fast-first terms this IS the tier
        # boundary: the first line (leg 1 = the recorded winner) is a
        # full non-provisional result, emitted before any remaining
        # sweep leg starts.
        payload = emit_best()
        # Keep-best incrementally persisted: an interrupted run never
        # reports null when any leg completed. ``ts`` stamps the record
        # so --resume-since can tell THIS run's legs from a prior
        # round's.
        leg_record = {
            "variant": label, "value": round(rate, 1), "unit": UNIT,
            "dt_s": round(dt, 3), "loss": round(final_loss, 6),
            "device": devs[0].device_kind,
            "ts": round(time.time(), 3),
            "t_since_start_s": round(time.perf_counter() - t_start, 1),
            # Provenance fields (ISSUE 9): run_id + fingerprint are
            # REQUIRED on every leg record (tools/resilience_lint.py
            # pins these keys), so a sweep artifact line can always be
            # traced to its run and comparability cohort.
            "run_id": run_id,
            "fingerprint": fingerprint,
            "hbm_peak_bytes": mem.get("peak_bytes_in_use"),
        }
        if label in leg_verdicts:
            leg_record["sentinel"] = leg_verdicts[label]
            leg_record["verdict"] = leg_verdicts[label]["verdict"]
        if elastic is not None and elastic.degraded:
            leg_record["chips"] = n_chips
            leg_record["degraded"] = True
        if label in fused_fallback_legs:
            leg_record["fused_fallback"] = True
        if args.chaos:
            leg_record["chaos"] = True
        _persist_incremental(art_dir, args.model, payload, leg_record)
        # Metrics snapshot after every leg: a later kill still leaves
        # the run's numeric record in <obs_dir>/metrics.jsonl.
        obs.export_snapshot()

    if not results:
        _log("[inner] every variant failed; no measurement")
        obs.shutdown()
        return 1
    rate, label, dt, final_loss = max(results)
    _log(f"[inner] device={devs[0].device_kind} "
         f"chips={n_chips} best={label} batch={batch} "
         f"steps={steps_timed} dt={dt:.3f}s loss={final_loss:.4f}"
         + (f" DEGRADED (shrinks={elastic.shrinks})"
            if elastic is not None and elastic.degraded else ""))
    obs.shutdown()
    return 0


# --------------------------------------------------------------------------
# Parent: spawn the child with a hard timeout, retry with backoff under a
# TOTAL wall-clock deadline, emit a provisional error JSON after every
# failed attempt, and salvage the best-so-far line even on SIGTERM.
# --------------------------------------------------------------------------

# Shared with the signal handler: the last valid cumulative-best result
# line streamed from any child, the failure log so far, and the live
# child process (so the handler can kill it before exiting — an orphaned
# child would keep holding the exclusive TPU attachment). RLock: the
# handler runs on the main thread, which may already hold the lock when
# the signal lands.
_SALVAGE = {"line": None, "failures": [], "emitted": False, "proc": None,
            "permanent": False}
_SALVAGE_LOCK = threading.RLock()

# Parent-side ledger target (set by main): the error path appends a
# NULL record — a dead round is a first-class attachment_transient
# data point in the history, not a gap (the BENCH_r03–r05 lesson).
_LEDGER_PATH = None
_MODEL_NAME = "fm"


def _load_obs_file(name):
    """Load fm_spark_tpu/obs/<name>.py standalone (ledger/sentinel are
    deliberately stdlib-only): the light parent gets provenance and the
    keep-best gate without importing the jax-pulling package."""
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fm_spark_tpu", "obs", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"_bench_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    # Register before exec: dataclass processing looks the module up
    # in sys.modules.
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def _ledger_error_record():
    """Append the dead-round null record (best-effort by the final-line
    contract)."""
    if _LEDGER_PATH is None:
        return
    try:
        lg = _load_obs_file("ledger")
        st = _load_obs_file("sentinel")
        ledger = lg.PerfLedger(_LEDGER_PATH)
        st.Sentinel(ledger).observe({
            "kind": "bench_leg", "leg": METRIC,
            "run_id": _RUN_ID or "unknown",
            "variant": None, "value": None, "unit": UNIT,
            "error": "; ".join(_SALVAGE["failures"])[:500]
            or "no attempt completed",
            "fingerprint": lg.measurement_fingerprint(
                variant="(error)", model=_MODEL_NAME,
                attachment_health="down"),
        })
    except Exception as e:
        _log(f"[parent] error-record ledger append failed: {e!r}")


def comparable_variant(variant) -> bool:
    """True iff a sweep result's variant label carries no non-default
    shape stamp — ``/b<digits>`` (non-default ``--batch``) or
    ``/r<digits>`` (non-default ``--rank``), added by inner_main. Only
    such results are comparable with the recorded MEASURED.json rates:
    every recorded rate is at its model's default batch and rank, a
    doubled batch amortizes fixed per-step work into an incomparable
    samples/sec, and a different rank is a different program."""
    return not re.search(r"/[br]\d", str(variant or ""))


def _emit_final():
    """Print the authoritative last line exactly once (result or error),
    and on a real measurement rewrite MEASURED.json so every downstream
    projection (dryrun_multichip, PERF analyses) picks up the new rate
    with its provenance — the single-source-of-truth contract of
    fm_spark_tpu/measured.py (VERDICT r4 Weak #1)."""
    with _SALVAGE_LOCK:
        if _SALVAGE["emitted"]:
            return
        _SALVAGE["emitted"] = True
        if _SALVAGE["line"] is not None:
            print(_SALVAGE["line"], flush=True)
            try:
                parsed = json.loads(_SALVAGE["line"])
                # Only a real TPU measurement may become the recorded
                # rate — a CPU smoke run must not clobber provenance.
                if "tpu" not in str(parsed.get("device", "")).lower():
                    raise RuntimeError(
                        f"not a TPU measurement: {parsed.get('device')!r}")
                # A non-default-shape A/B (the /b262144 or /r32 labels)
                # stays in its sweep artifact; promoting it is a
                # deliberate re-baseline, not a keep-best side effect.
                if not comparable_variant(parsed.get("variant")):
                    raise RuntimeError(
                        f"non-default-shape variant "
                        f"{parsed.get('variant')!r}; not comparable with "
                        "the recorded default-shape rate")
                # A degraded (shrunk-mesh) rate is a salvage artifact,
                # not the attachment's measured capability — it must
                # never become the recorded keep-best.
                if parsed.get("degraded"):
                    raise RuntimeError(
                        f"degraded measurement on {parsed.get('chips')} "
                        "chip(s) after an elastic shrink; keeping the "
                        "recorded full-mesh rate")
                # A fused-embed leg that fell back to XLA measured the
                # wrong program for its label — never the keep-best
                # (ISSUE 8; same contract as the degraded stamp).
                if parsed.get("fused_fallback"):
                    raise RuntimeError(
                        "fused-embed run fell back to the XLA path; "
                        "not a fused-kernel measurement — keeping the "
                        "recorded rate")
                # A chaos-drill rate ran under an injected fault
                # schedule (ISSUE 10): a different program in
                # everything but name — never the keep-best.
                if parsed.get("chaos"):
                    raise RuntimeError(
                        "chaos-drill measurement (run under an active "
                        "fault schedule); drill legs have their own "
                        "ledger cohort — keeping the recorded rate")
                # Sentinel gate (ISSUE 9): only an improved/flat
                # verdict against the ledger's cohort history may
                # promote — a statistically-regressed rate, or one
                # measured under adverse attachment weather, never
                # overwrites the recorded capability no matter how the
                # numeric comparison lands.
                sb = parsed.get("sentinel")
                if not _load_obs_file("sentinel").keepbest_allowed(sb):
                    raise RuntimeError(
                        f"sentinel verdict {(sb or {}).get('verdict')!r}"
                        f" ({(sb or {}).get('reason')}); only improved/"
                        "flat measurements may promote — keeping the "
                        "recorded rate")
                # Keep-best: MEASURED.json records the best measured
                # on-chip capability. A later slower window or a
                # SIGTERM-salvaged partial sweep must not clobber a
                # better earlier measurement.
                from fm_spark_tpu.measured import (
                    load_measured,
                    update_entry,
                )
                entry = METRIC_ENTRY[parsed["metric"]]
                try:
                    prev = load_measured()[entry][
                        "rate_samples_per_sec_per_chip"]
                except (OSError, ValueError, KeyError):
                    prev = 0.0
                if parsed["value"] <= prev:
                    raise RuntimeError(
                        f"measured {parsed['value']:.0f} <= recorded "
                        f"best {prev:.0f}; keeping the recorded rate")
                update_entry(
                    entry,
                    rate=parsed["value"],
                    vs_baseline=parsed.get("vs_baseline"),
                    variant=parsed.get("variant", "?"),
                    source=f"bench.py --model sweep (round 5+), metric "
                           f"{parsed['metric']}",
                    attachment=parsed.get("device", "unknown device"),
                    date=time.strftime("%Y-%m-%d", time.gmtime()),
                )
                _log(f"[parent] MEASURED.json {entry} updated from "
                     "this sweep")
            except Exception as e:  # never break the final-line contract
                _log(f"[parent] MEASURED.json update failed: {e!r}")
        else:
            _ledger_error_record()
            print(_error_line("; ".join(_SALVAGE["failures"])
                              or "no attempt completed",
                              permanent=_SALVAGE["permanent"]),
                  flush=True)


def _parse_result_line(line):
    line = line.strip()
    if not line.startswith("{"):
        return None
    try:
        parsed = json.loads(line)
    except json.JSONDecodeError:
        return None
    if parsed.get("metric") == METRIC and parsed.get("value") is not None:
        return line
    return None


def _run_attempt(argv, timeout_s):
    """One child run. Returns (json_line_or_None, diagnostic_str).

    The child's stdout is STREAMED (not buffered in communicate()): each
    cumulative-best line is recorded into _SALVAGE the moment it appears,
    so an outer SIGTERM landing mid-sweep still finds the newest
    completed measurement.
    """
    cmd = [sys.executable, os.path.abspath(__file__), "--inner"] + argv
    # stderr inherited -> child heartbeats stream live.
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True)
    with _SALVAGE_LOCK:
        _SALVAGE["proc"] = proc

    found_holder = {"line": None}

    def reader():
        for line in proc.stdout:
            got = _parse_result_line(line)
            if got is not None:
                # LAST matching line wins: the child prints a
                # cumulative-best line after each variant.
                found_holder["line"] = got
                with _SALVAGE_LOCK:
                    _SALVAGE["line"] = got
        proc.stdout.close()

    rd = threading.Thread(target=reader, daemon=True)
    rd.start()

    hb_stop = threading.Event()

    def heartbeat():
        t0 = time.perf_counter()
        while not hb_stop.wait(30):
            # One-decimal durations everywhere a duration is
            # interpolated: BENCH_r05's tail printed the raw float
            # ("timeout 125.98949042700042s").
            _log(f"[parent] attempt alive, {time.perf_counter() - t0:.1f}s "
                 f"elapsed (timeout {timeout_s:.1f}s)")

    hb = threading.Thread(target=heartbeat, daemon=True)
    hb.start()
    timed_out = False
    try:
        proc.wait(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        timed_out = True
        proc.kill()
        proc.wait()
    finally:
        hb_stop.set()
        rd.join(timeout=10)
        with _SALVAGE_LOCK:
            _SALVAGE["proc"] = None

    found = found_holder["line"]
    if found is not None:
        return found, ""
    if timed_out:
        return None, f"child hung: no result within {timeout_s:.0f}s (killed)"
    return None, f"child exited rc={proc.returncode} without a result line"


def main():
    ap = argparse.ArgumentParser(
        description="FM training throughput bench (variant knobs for "
        "perf sweeps; defaults = the headline configuration)"
    )
    ap.add_argument("--inner", action="store_true",
                    help="internal: run the measurement in-process")
    ap.add_argument("--param-dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--compute-dtype", default="float32",
                    dest="compute_dtype",
                    choices=["float32", "bfloat16"],
                    help="forward/backward buffer dtype (the [B, w] "
                         "passes; storage stays --param-dtype)")
    ap.add_argument("--sparse-update", default="scatter_add",
                    choices=["scatter_add", "dedup", "dedup_sr"])
    ap.add_argument("--use-pallas", action="store_true", dest="use_pallas",
                    help="route row gather/update through the Pallas "
                         "pipelined-DMA kernels (PERF.md 'Pallas' lever)")
    ap.add_argument("--host-dedup", action="store_true", dest="host_dedup",
                    help="host-precomputed dedup aux: device writes each "
                         "unique id once (PERF.md round-3 lever; pair "
                         "with --sparse-update dedup or dedup_sr)")
    ap.add_argument("--compact-cap", type=int, default=0, dest="compact_cap",
                    help="COMPACT host-dedup: static per-field unique-id "
                         "capacity; device touches the big tables with "
                         "cap lanes instead of B (requires --host-dedup "
                         "or --compact-device, and a dedup "
                         "--sparse-update)")
    ap.add_argument("--compact-device", action="store_true",
                    dest="compact_device",
                    help="build the compact aux on device inside the "
                         "step (the scale-out form of --compact-cap; "
                         "exclusive with --host-dedup)")
    ap.add_argument("--gfull-fused", action="store_true",
                    dest="gfull_fused",
                    help="fused g_full construction (no per-field "
                         "concat([g_v, g_l]); PERF.md round-4 lever)")
    ap.add_argument("--segtotal-pallas", action="store_true",
                    dest="segtotal_pallas",
                    help="Pallas sorted-run segment totals in the "
                         "compact update (no blocked-prefix "
                         "materialization; round-5 lever)")
    ap.add_argument("--fused-embed", default="off",
                    choices=["off", "auto", "require"],
                    dest="fused_embed",
                    help="fused Pallas embedding path (ISSUE 8): "
                         "'require' measures exactly the fused kernel "
                         "family (fails if unservable); 'auto' falls "
                         "back to XLA — the leg is then stamped "
                         "fused_fallback and never keep-bests into "
                         "MEASURED.json")
    ap.add_argument("--embed-tier", default="off",
                    choices=["off", "auto", "require"],
                    dest="embed_tier",
                    help="tiered embedding store lever (ISSUE 16) for "
                         "the measured config: the in-HBM sweep legs "
                         "reject 'require' loudly (the tiered path is "
                         "priced by its OWN ladder, bench_embed.py, "
                         "into the embed_bench ledger kind — never "
                         "compared against in-HBM legs)")
    ap.add_argument("--hot-rows", type=int, default=0, dest="hot_rows",
                    help="HBM hot-tier rows for --embed-tier (see "
                         "bench_embed.py for the tiered ladder itself)")
    ap.add_argument("--embed-bucket-rows", type=int, default=512,
                    dest="embed_bucket_rows",
                    help="rows per hot-tier bucket for --embed-tier")
    ap.add_argument("--fast-first", action="store_true",
                    dest="fast_first",
                    help="tiered sweep (warm-start): measure the "
                         "recorded winner variant FIRST (AOT-"
                         "precompiled when the compile cache is on) "
                         "and emit its non-provisional result JSON "
                         "before the remaining legs start; every leg "
                         "streams to --artifacts-dir as it lands")
    ap.add_argument("--elastic", action="store_true",
                    help="elastic degraded mode: a sweep leg whose "
                         "retries exhaust on a PERMANENT fault (N "
                         "identical consecutive device losses) sheds "
                         "chips (8>4>2>1) and re-runs instead of "
                         "abandoning the sweep; the result JSON is "
                         "stamped degraded with per-surviving-chip "
                         "normalization, and never keep-bests into "
                         "MEASURED.json")
    ap.add_argument("--max-shrinks", type=int, default=3,
                    dest="max_shrinks",
                    help="with --elastic: how many times the device "
                         "set may halve before the fault propagates")
    ap.add_argument("--chaos", action="store_true",
                    help="chaos-drill stamping (ISSUE 10): this run is "
                         "executing under an active fault schedule "
                         "(FM_SPARK_FAULTS), so every leg's measurement "
                         "fingerprint carries chaos=true — drill legs "
                         "form their own ledger cohort and can never "
                         "join a real perf cohort or pass the keep-best "
                         "gate into MEASURED.json")
    ap.add_argument("--dirty-input", action="store_true",
                    dest="dirty_input",
                    help="run the hardened-ingest leg before the sweep "
                         "(ISSUE 5): stream a synthetic 3-shard dataset "
                         "with deterministically corrupted lines through "
                         "the quarantine policy and stamp its "
                         "bad_records / rows_per_sec accounting into "
                         "the result JSON (host-only, ~seconds)")
    ap.add_argument("--resume-sweep", action="store_true",
                    dest="resume_sweep",
                    help="skip sweep legs already completed in "
                         "--artifacts-dir's sweep_<model>.jsonl and "
                         "measure only the remaining ones (the restart "
                         "path after a mid-window kill; composes with "
                         "--fast-first and the warm compile cache — "
                         "the best-so-far line is emitted before any "
                         "remaining leg runs)")
    ap.add_argument("--resume-since", type=float, default=0.0,
                    dest="resume_since", metavar="EPOCH",
                    help="with --resume-sweep: only resume legs whose "
                         "sweep record is stamped at/after this unix "
                         "time (the parent passes its own start time "
                         "when auto-resuming a retried attempt; 0 = "
                         "any prior record)")
    ap.add_argument("--artifacts-dir", default=None, dest="artifacts_dir",
                    help="where sweep_<model>.jsonl / "
                         "keepbest_<model>.json land (default: "
                         "artifacts/ next to this script)")
    ap.add_argument("--model", default="fm", choices=sorted(METRICS),
                    help="which fused step to measure: fm = the tracked "
                         "Criteo headline; ffm = config 4's avazu shape "
                         "(refreshes MEASURED.json's ffm_avazu entry)")
    ap.add_argument("--rank", type=int, default=None,
                    help="factor rank (default: 64 for fm, 16 for ffm)")
    ap.add_argument("--batch", type=int, default=1 << 17)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--attempts", type=int, default=6,
                    help="max child attempts before emitting the error JSON "
                         "(the total deadline usually binds first)")
    ap.add_argument("--attempt-timeout", type=float, default=900.0,
                    help="hard wall-clock limit per attempt (seconds); "
                         "sized for the 7-variant default sweep (round 2 "
                         "ran 5 variants inside 600s) — a hung INIT "
                         "still exits at --init-timeout, and the "
                         "cumulative-best lines salvage a sweep the "
                         "limit cuts short")
    ap.add_argument("--total-deadline", type=float, default=1500.0,
                    dest="total_deadline",
                    help="hard wall-clock limit for the WHOLE run incl. "
                         "retries; kept under the driver's ~30min outer "
                         "kill window so the final JSON line always lands")
    ap.add_argument("--init-timeout", type=float, default=240.0,
                    dest="init_timeout",
                    help="child-side backend init watchdog: an init that "
                         "has not finished by then never finishes here; "
                         "the child exits early for a cheap retry")
    ap.add_argument("--metrics-port", type=int, default=None,
                    dest="metrics_port", metavar="PORT",
                    help="serve the live metrics registry from the "
                         "measuring child over stdlib HTTP on "
                         "127.0.0.1:PORT (0 = OS-assigned, echoed as a "
                         "JSON line): /metrics Prometheus text + "
                         "/healthz JSON — watch a sweep without "
                         "touching the process (ISSUE 14)")
    ap.add_argument("--run-id", default=None, dest="run_id",
                    help="telemetry run id (ISSUE 7): every stream this "
                         "run emits lands under <artifacts>/obs/"
                         "<run_id>/ and the id is echoed in the result "
                         "JSON. Default: minted fresh — the parent "
                         "passes its mint to every child attempt so "
                         "retries append to the SAME run")
    args = ap.parse_args()

    if (args.host_dedup or args.compact_device) and (
        args.sparse_update not in ("dedup", "dedup_sr")
    ):
        ap.error("--host-dedup/--compact-device require --sparse-update "
                 "dedup or dedup_sr")
    if (args.host_dedup or args.compact_device) and args.use_pallas:
        ap.error("--host-dedup/--compact-device and --use-pallas are "
                 "exclusive")
    if args.compact_cap and not (args.host_dedup or args.compact_device):
        ap.error("--compact-cap requires --host-dedup or --compact-device")
    if args.compact_device and args.host_dedup:
        ap.error("--compact-device and --host-dedup are exclusive")
    if args.compact_device and not args.compact_cap:
        ap.error("--compact-device requires --compact-cap")

    if args.inner:
        sys.exit(inner_main(args))

    # Re-build the child argv from the variant knobs only.
    _set_model(args.model)
    # Mint the run id HERE so every retried child appends to the same
    # per-run telemetry directory and the parent's own error JSON
    # carries the id of the evidence it left behind.
    global _RUN_ID, _LEDGER_PATH, _MODEL_NAME
    _RUN_ID = args.run_id or _gen_run_id()
    _MODEL_NAME = args.model
    _LEDGER_PATH = os.path.join(_artifacts_dir(args), "obs",
                                "ledger.jsonl")
    argv = [
        "--model", args.model,
        "--param-dtype", args.param_dtype,
        "--compute-dtype", args.compute_dtype,
        "--sparse-update", args.sparse_update,
        "--batch", str(args.batch),
        "--steps", str(args.steps),
        "--init-timeout", str(args.init_timeout),
        "--run-id", _RUN_ID,
    ]
    if args.rank is not None:
        argv += ["--rank", str(args.rank)]
    if args.use_pallas:
        argv.append("--use-pallas")
    if args.host_dedup:
        argv.append("--host-dedup")
    if args.compact_cap:
        argv += ["--compact-cap", str(args.compact_cap)]
    if args.compact_device:
        argv.append("--compact-device")
    if args.gfull_fused:
        argv.append("--gfull-fused")
    if args.segtotal_pallas:
        argv.append("--segtotal-pallas")
    if args.fused_embed != "off":
        argv += ["--fused-embed", args.fused_embed]
    if args.embed_tier != "off":
        argv += ["--embed-tier", args.embed_tier,
                 "--hot-rows", str(args.hot_rows),
                 "--embed-bucket-rows", str(args.embed_bucket_rows)]
    if args.fast_first:
        argv.append("--fast-first")
    if args.dirty_input:
        argv.append("--dirty-input")
    if args.chaos:
        argv.append("--chaos")
    if args.elastic:
        argv += ["--elastic", "--max-shrinks", str(args.max_shrinks)]
    if args.artifacts_dir:
        argv += ["--artifacts-dir", args.artifacts_dir]
    if args.metrics_port is not None:
        argv += ["--metrics-port", str(args.metrics_port)]
    # An outer kill (timeout(1) sends SIGTERM) must still leave a
    # parseable final line: best-so-far result if any child printed one,
    # otherwise the error JSON with the failure log.
    import signal

    def _on_signal(signum, frame):
        with _SALVAGE_LOCK:
            _SALVAGE["failures"].append(
                f"parent received signal {signum} before completion")
            proc = _SALVAGE["proc"]
            salvaged = _SALVAGE["line"] is not None
        if proc is not None:
            try:
                proc.kill()
            except OSError:
                pass
        _emit_final()
        # A salvaged sweep IS a successful measurement (fast-first
        # contract: any completed leg beats a null artifact) — exit 0
        # so callers chained on success still advance.
        os._exit(0 if salvaged else 1)

    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, _on_signal)

    deadline = time.perf_counter() + args.total_deadline
    t_epoch = time.time()  # auto-resume cutoff: only THIS run's legs
    raw_diags = []  # un-prefixed child failure diags for classification
    for attempt in range(1, args.attempts + 1):
        remaining = deadline - time.perf_counter()
        if remaining < 90:
            with _SALVAGE_LOCK:
                _SALVAGE["failures"].append(
                    f"total deadline {args.total_deadline:.0f}s reached "
                    f"after {attempt - 1} attempts")
            break
        # Reserve 15s so the final emit always beats the deadline.
        timeout_s = min(args.attempt_timeout, remaining - 15)
        child_argv = list(argv)
        if args.resume_sweep:
            child_argv.append("--resume-sweep")
            if args.resume_since:
                child_argv += ["--resume-since", str(args.resume_since)]
        elif attempt > 1:
            # A retried attempt auto-resumes: legs the previous child
            # completed before it died are loaded from the incremental
            # sweep artifact instead of re-measured — the remaining
            # deadline goes to legs that still NEED a window. Scoped to
            # records stamped after this parent started, so a prior
            # round's artifact can never masquerade as today's data.
            child_argv += ["--resume-sweep",
                           "--resume-since", f"{t_epoch:.3f}"]
        _log(f"[parent] attempt {attempt}/{args.attempts} "
             f"(timeout {timeout_s:.0f}s, {remaining:.0f}s of total "
             "budget left)")
        line, diag = _run_attempt(child_argv, timeout_s)
        if line is not None:
            with _SALVAGE_LOCK:
                _SALVAGE["line"] = line
            _emit_final()
            return 0
        raw_diags.append(diag)
        with _SALVAGE_LOCK:
            _SALVAGE["failures"].append(f"attempt {attempt}: {diag}")
        _log(f"[parent] {diag}")
        # Transient-vs-permanent classification (ISSUE 4 satellite — the
        # BENCH_r05 failure mode: six supervised attempts burned against
        # a permanently dead attachment): N identical consecutive child
        # failures mean the attachment is DEAD, so re-spawning and
        # re-sleeping the remaining attempts only burns the deadline.
        if _classify_diags(raw_diags, threshold=3) == "permanent":
            with _SALVAGE_LOCK:
                _SALVAGE["permanent"] = True
                _SALVAGE["failures"].append(
                    f"classified permanent after {len(raw_diags)} "
                    "identical consecutive failures -- abandoning the "
                    f"{args.attempts - attempt} remaining attempt(s)")
            _log(f"[parent] permanent fault: {len(raw_diags)} identical "
                 "consecutive failures -- stopping retries")
            break
        # Provisional artifact NOW: if the outer window kills us later,
        # the last stdout line is already parseable.
        with _SALVAGE_LOCK:
            print(_error_line(
                "provisional after failed attempt "
                f"{attempt}: " + "; ".join(_SALVAGE["failures"])),
                flush=True)
        if attempt < args.attempts:
            if _classify_diags(raw_diags, threshold=2) == "permanent":
                # Two identical failures already: suspected permanent.
                # The next attempt is the cheap confirmation probe —
                # spend the budget on it, not on a backoff sleep.
                _log("[parent] identical consecutive failures -- "
                     "skipping backoff (suspected permanent fault)")
                continue
            from fm_spark_tpu.utils.sleeps import scaled as _sleep_scaled

            # Designed sleep (FM_SPARK_TEST_SLEEP_SCALE shrinks it in
            # the fault suite); the deadline guard is NOT scaled.
            backoff = min(_sleep_scaled(10 * attempt),
                          max(0, deadline - time.perf_counter() - 90))
            if backoff > 0:
                _log(f"[parent] backing off {backoff:.1f}s before retry")
                time.sleep(backoff)

    _emit_final()
    return 1


if __name__ == "__main__":
    sys.exit(main())
