"""The CLI lever registry (VERDICT r4 #7): one row per TrainConfig lever.

Parser setup (:func:`add_lever_args`), train-config threading
(:func:`lever_overrides`) and the strategy-independent guards
(``validate_any``) iterate ONE table: adding lever N+1 to the CLI is one
``_Lever`` row here (+ its TrainConfig field and step support). Which
step serves a lever is each factory's declaration (``sparse.declares``).
"""

from __future__ import annotations

import dataclasses

from fm_spark_tpu.sparse import overflow_without_cap


@dataclasses.dataclass(frozen=True)
class _Lever:
    flag: str            # CLI flag, e.g. "--score-sharded"
    field: str           # TrainConfig field name (= argparse dest)
    kind: str            # 'flag' | 'int' | 'choice'
    help: str
    choices: tuple = ()
    # Optional guard (tconfig) -> error message | None, run by
    # cli.cmd_train for EVERY strategy once the TrainConfig is built.
    validate_any: object = None


def check_levers_any(tconfig):
    """Run every registry row's strategy-independent guard; returns the
    first error message or None."""
    for lv in _LEVERS:
        if lv.validate_any is not None:
            msg = lv.validate_any(tconfig)
            if msg:
                return msg
    return None


def _v_hot_rows_need_tier(tc):
    if tc.hot_rows > 0 and tc.embed_tier == "off":
        # Capacity without the lever would be a silent no-op: the
        # in-HBM trainers never consult hot_rows.
        return "--hot-rows has no effect without --embed-tier auto|require"
    if tc.embed_tier != "off" and tc.hot_rows > 0 and \
            tc.hot_rows % tc.embed_bucket_rows:
        return (
            f"--hot-rows {tc.hot_rows} must be a multiple of "
            f"--embed-bucket-rows {tc.embed_bucket_rows} (the hot tier "
            "is managed in whole buckets)"
        )


_LEVERS = (
    _Lever("--host-dedup", "host_dedup", "flag",
           "precompute per-batch dedup sort/segment maps on the host "
           "prefetch thread; device writes each unique id once (needs "
           "--sparse-update dedup or dedup_sr; single-chip FieldFM)"),
    _Lever("--compact-cap", "compact_cap", "int",
           "COMPACT host-dedup: static per-field unique-id capacity — "
           "the device touches the big tables with this many lanes "
           "instead of the batch size (the measured headline winner, "
           "PERF.md). Must bound every field's per-batch unique-id "
           "count (the aux builder raises otherwise). Needs "
           "--host-dedup or --compact-device"),
    _Lever("--compact-device", "compact_device", "flag",
           "build the compact aux ON DEVICE inside the step (no host "
           "aux shipping) — the scale-out form of --compact-cap: "
           "composes with --row-shards 2-D meshes and multi-process "
           "runs. Needs --compact-cap and a dedup --sparse-update; "
           "exclusive with --host-dedup"),
    _Lever("--compact-overflow", "compact_overflow", "choice",
           "policy when a field's per-batch unique ids exceed "
           "--compact-cap: error (default; host aux raises before the "
           "step, device aux poisons the loss), drop (device: overflow "
           "ids behave as absent features), split (host: split the "
           "batch until every field fits — exact, more steps)",
           choices=("error", "drop", "split"),
           validate_any=overflow_without_cap),
    _Lever("--collective-dtype", "collective_dtype", "choice",
           "wire dtype for the sharded steps' activation collectives "
           "(score psums, DeepFM h, FFM sel all_to_all) — bfloat16 "
           "halves the dominant ICI bytes (parallel/projection.py); "
           "multi-device field_sparse only",
           choices=("float32", "bfloat16")),
    _Lever("--score-sharded", "score_sharded", "flag",
           "shard the [B,k] score/dscores math over examples on the "
           "sharded FM step (exact; one tiny [B] dscores all_gather) — "
           "removes the only non-shardable batch-proportional term "
           "(parallel/projection.py)"),
    _Lever("--deep-sharded", "deep_sharded", "flag",
           "example-shard the DeepFM deep head on the sharded step "
           "(h all_gather -> one all_to_all, MLP on B/n examples per "
           "chip, [B] deep-score gather) — ~n x fewer h wire bytes "
           "and the deep FLOPs divide by n (parallel/projection.py)"),
    _Lever("--gfull-fused", "gfull_fused", "flag",
           "build each field's backward g_full buffer directly as "
           "ds·x·(s1 − m·xv_full) instead of concat([g_v, g_l]) — "
           "removes one materialized copy pass per field (measured "
           "~+8%% on-chip and composes with --segtotal-pallas to the "
           "1.422M headline, PERF.md round-5 table; ULP-pinned in "
           "tests/test_gfull.py). FieldFM/DeepFM fused bodies; other "
           "step factories reject it"),
    _Lever("--sel-blocked", "sel_blocked", "flag",
           "FFM: compute the field-aware interaction and its backward "
           "in per-owner-field blocks — the [B, F, F, k] sel/dsel/dv "
           "tensors (config 4's dominant HBM traffic, PERF.md) are "
           "never materialized; largest live buffer drops to [B, F, "
           "k]. Single-chip FieldFFM body; staged for on-chip pricing "
           "in the bench --model ffm sweep"),
    _Lever("--segtotal-pallas", "segtotal_pallas", "flag",
           "compute the compact update's segment sums with the Pallas "
           "sorted-run kernel (streaming read, VMEM-resident [cap, w] "
           "accumulator — no [B, w] prefix materialization; "
           "ops/pallas_segsum.py). Needs --compact-cap; off-TPU runs "
           "interpret mode; the on-chip A/B prices it"),
    _Lever("--fused-embed", "fused_embed", "choice",
           "fused Pallas embedding path (ops/pallas_fused.py): 'auto' "
           "uses the kernel family serving this (model, config, "
           "backend) — the FieldFM compact backward (g_full rebuilt "
           "on-chip + segment totals in one kernel; the per-field "
           "gradient set never touches HBM) or the sel-blocked "
           "FieldFFM kernels — and falls back to the XLA path with a "
           "stderr notice when none does; 'require' hard-fails "
           "instead of falling back (bench legs that must price the "
           "kernel)",
           choices=("off", "auto", "require")),
    _Lever("--embed-tier", "embed_tier", "choice",
           "tiered embedding store (fm_spark_tpu/embed): hot-bucket "
           "HBM cache of --hot-rows rows over host cold storage, "
           "async batch-keyed bucket prefetch, LRU-by-batch eviction "
           "with dirty write-back — bit-identical to the in-HBM flat "
           "FM path. 'auto' tiers when the tiered trainer serves this "
           "(flat FM, single strategy, sgd/ftrl/adagrad) and falls "
           "back with a stderr notice (embed.tier_plan's reason); "
           "'require' hard-fails instead of falling back",
           choices=("off", "auto", "require")),
    _Lever("--hot-rows", "hot_rows", "int",
           "HBM hot-tier capacity in rows for --embed-tier (multiple "
           "of --embed-bucket-rows; must cover one batch's touched-"
           "bucket working set, and be < num-features — otherwise "
           "there is nothing to tier)",
           validate_any=_v_hot_rows_need_tier),
    _Lever("--embed-bucket-rows", "embed_bucket_rows", "int",
           "rows per hot-tier bucket (the residency/eviction/prefetch "
           "unit; default 512). Smaller buckets = finer eviction, more "
           "transfers; must divide --hot-rows and num-features"),
)


def _add_lever_args(parser):
    """Registry-driven argparse rows (one per _Lever)."""
    for lv in _LEVERS:
        if lv.kind == "flag":
            parser.add_argument(lv.flag, action="store_true",
                                dest=lv.field, help=lv.help)
        elif lv.kind == "int":
            parser.add_argument(lv.flag, type=int, default=None,
                                dest=lv.field, help=lv.help)
        elif lv.kind == "choice":
            parser.add_argument(lv.flag, default=None,
                                choices=list(lv.choices),
                                dest=lv.field, help=lv.help)
        else:
            raise ValueError(f"unknown lever kind {lv.kind!r}")


def _lever_overrides(args) -> dict:
    """The registry's train_config(**overrides) slice: store_true flags
    map False -> None (no override) so config defaults survive."""
    out = {}
    for lv in _LEVERS:
        v = getattr(args, lv.field)
        if lv.kind == "flag":
            v = True if v else None
        out[lv.field] = v
    return out
