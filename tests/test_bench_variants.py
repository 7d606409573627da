"""Pins for bench.py's default sweep grid (bench.default_variants).

The sweep's labels are the measurement's provenance — MEASURED.json and
every PERF.md table row is keyed by them — so a label that disagrees
with its TrainConfig silently corrupts the record (round 5 nearly
shipped exactly this: an insert-order bug put the composed variant
behind probes it was staged to precede). These tests pin label<->config
consistency and the salvage ordering without touching a device.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402


def _grid(model, batch=1 << 17):
    head, tail = bench.default_variants(model, batch)
    return head + tail


def test_fm_label_config_consistency():
    for label, (pd, cd), cfg in _grid("fm"):
        assert ("gfull" in label) == cfg.gfull_fused, label
        assert ("segtotal" in label) == cfg.segtotal_pallas, label
        assert ("fusedbwd" in label) == (cfg.fused_embed != "off"), label
        assert ("devaux" in label) == cfg.compact_device, label
        assert (f"compact{cfg.compact_cap}" in label) == (
            cfg.compact_cap > 0), label
        assert label.startswith(pd), label
        assert ("cd-bf16" in label) == (cd == "bfloat16"), label
        # compact aux comes from exactly one builder
        assert cfg.host_dedup != cfg.compact_device, label


def test_fm_salvage_order_composed_first():
    head, _ = bench.default_variants("fm", 1 << 17)
    cfgs = [c for _, _, c in head]
    # [0] measured winner (floor cap 12288, 1,422,411 on 2026-07-31);
    # [1] the fused Pallas backward challenger at the same floor cap
    # (ISSUE 8 — staged right after the incumbent, the round-5 selblk
    # pattern; 'require' so a no-Pallas attachment skips, never
    # silently pricing XLA under the fused label);
    # [2] the batch/10-bound cap leg (the formula-derived fallback);
    # [3] the historical-cap drift leg; [4][5] single-lever legs; [6]
    # the r3 winner closing the grid.
    assert cfgs[0].gfull_fused and cfgs[0].segtotal_pallas
    assert cfgs[0].compact_cap == 12288
    assert cfgs[1].fused_embed == "require"
    assert cfgs[1].compact_cap == 12288
    assert not cfgs[1].gfull_fused and not cfgs[1].segtotal_pallas
    assert cfgs[2].gfull_fused and cfgs[2].segtotal_pallas
    assert cfgs[2].compact_cap == 13312
    assert cfgs[3].gfull_fused and cfgs[3].segtotal_pallas
    assert cfgs[3].compact_cap == 16384
    assert cfgs[4].gfull_fused and not cfgs[4].segtotal_pallas
    assert cfgs[5].segtotal_pallas and not cfgs[5].gfull_fused
    assert not cfgs[6].gfull_fused and not cfgs[6].segtotal_pallas


def test_fm_tight_cap_bounds_measured_unique():
    # The tight cap must bound the bench batch's measured max per-field
    # unique count (Zipf 1.3, seed 0) or the staged A/B would die on
    # compact_overflow='error'; and it must be a multiple of segtotal's
    # 512 tile. Values measured 2026-07-31.
    for batch, max_unique in ((131072, 11990), (262144, 20109)):
        head, _ = bench.default_variants("fm", batch)
        tight = sorted({c.compact_cap for _, _, c in head})[0]
        assert tight % 512 == 0
        assert max_unique <= tight <= batch


def test_fm_cap_respects_small_batch():
    # No compact variant may cap above the batch (the aux builder would
    # allocate dead lanes); the tight-cap A/B additionally floors at 512
    # (segtotal's tile).
    for label, _, cfg in _grid("fm", batch=1024):
        if cfg.compact_cap:
            assert cfg.compact_cap in (512, 1024), label
            assert f"compact{cfg.compact_cap}" in label, label


def test_deepfm_grid():
    grid = _grid("deepfm")
    assert [c.optimizer for _, _, c in grid] == ["adam", "adam"]
    assert [c.gfull_fused for _, _, c in grid] == [False, True]
    for label, _, cfg in grid:
        assert ("gfull" in label) == cfg.gfull_fused, label
        assert ("segtotal" in label) == cfg.segtotal_pallas, label


def test_ffm_grid_no_compact():
    for label, _, cfg in _grid("ffm"):
        assert cfg.compact_cap == 0, "compact measured a loser on avazu"
        assert "compact" not in label
        assert ("selblk" in label) == cfg.sel_blocked, label
        assert ("selblk-pallas" in label) == (
            cfg.fused_embed != "off"), label


def test_comparable_variant_gate():
    # The MEASURED.json keep-best gate: non-default-shape labels (the
    # /b262144 batch A/B, any explicit --rank run) must never be
    # comparable with the recorded default-shape rates; every real
    # default-shape label must be.
    for bad in (
        "bfloat16/dedup_sr/compact26624/cd-bf16/gfull/segtotal/b262144",
        "float32/scatter_add/b2048",
        "bfloat16/dedup_sr/compact16384/cd-bf16/r32",
        "float32/scatter_add/b2048/r8",
    ):
        assert not bench.comparable_variant(bad), bad
    for ok in (
        "bfloat16/dedup_sr/compact16384/cd-bf16/gfull/segtotal",
        "float32/scatter_add/cd-bf16",
        "bfloat16/dedup_sr/compact16384/devaux/cd-bf16",
        "float32/dedup/compact16384",
        None,
    ):
        assert bench.comparable_variant(ok), ok


def test_fm_kaggle_grid():
    # Config 2's grid: cd-bf16-over-fp32 staged first (small-table
    # regime, the measured avazu-winner form), the criteo-winner form
    # second, bf16/dedup_sr as the tail sentinel; compact cap bounds
    # the measured 10,711 max per-field unique at B=131072.
    head, tail = bench.default_variants("fm_kaggle", 1 << 17)
    label0, (pd0, cd0), cfg0 = head[0]
    assert label0 == "float32/scatter_add/cd-bf16"
    assert (pd0, cd0) == ("float32", "bfloat16")
    label1, _, cfg1 = head[1]
    assert cfg1.compact_cap == 16384 and cfg1.host_dedup
    assert f"compact{cfg1.compact_cap}" in label1
    assert [c.sparse_update for _, _, c in tail] == ["dedup_sr"]


def test_ffm_salvage_order_measured_winner_first():
    head, tail = bench.default_variants("ffm", 1 << 17)
    # 816,553 on 2026-07-31 (MEASURED.json ffm_avazu): fp32 storage +
    # bf16 compute + scatter_add. Label<->config consistency matters
    # here doubly — cd-bf16 with FP32 storage is exact-storage, so the
    # label's "/cd-bf16" is the only record that compute ran in bf16.
    label, (pd, cd), cfg = head[0]
    assert label == "float32/scatter_add/cd-bf16"
    assert (pd, cd) == ("float32", "bfloat16")
    assert cfg.sparse_update == "scatter_add"
    assert not cfg.host_dedup and not cfg.compact_device


@pytest.mark.slow
def test_default_grids_build_and_step():
    """Every default-sweep variant of every model must CONSTRUCT and run
    one step — label pins alone would let a variant that fails at build
    time (the class the sweep's per-variant guard logs and skips) go
    unnoticed until the driver's round-end bench. Tiny shapes; segtotal
    runs its interpret path off-TPU."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fm_spark_tpu import models
    from fm_spark_tpu.ops.scatter import compact_aux, dedup_aux
    from fm_spark_tpu.sparse import (
        make_field_deepfm_sparse_step,
        make_field_ffm_sparse_sgd_step,
        make_field_sparse_sgd_step,
    )

    B, F, BUCKET, RANK = 512, 4, 256, 8
    rng = np.random.default_rng(0)
    ids_np = (rng.zipf(1.3, size=(B, F)) % BUCKET).astype(np.int32)
    ids = jnp.asarray(ids_np)
    vals = jnp.ones((B, F), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 2, B), jnp.float32)
    weights = jnp.ones((B,), jnp.float32)

    for model in ("fm", "ffm", "deepfm", "fm_kaggle"):
        head, tail = bench.default_variants(model, B)
        assert head or tail, model
        for label, (pd, cd), cfg in head + tail:
            # Mirror bench.make_spec's dtype fallback: a None compute
            # dtype means "the --compute-dtype default" (float32), NOT
            # dtype(None) — numpy canonicalizes the latter to float64.
            common = dict(
                num_features=F * BUCKET, rank=RANK, num_fields=F,
                bucket=BUCKET, init_std=0.01, param_dtype=pd,
                compute_dtype=cd or "float32",
            )
            aux = None
            if cfg.host_dedup:
                aux = (compact_aux(ids_np, cfg.compact_cap)
                       if cfg.compact_cap else dedup_aux(ids_np))
            if model == "ffm":
                spec = models.FieldFFMSpec(**common)
                step = make_field_ffm_sparse_sgd_step(spec, cfg)
            elif model == "deepfm":
                spec = models.FieldDeepFMSpec(**common, mlp_dims=(8, 8))
                step = make_field_deepfm_sparse_step(spec, cfg)
            else:
                spec = models.FieldFMSpec(**common)
                step = make_field_sparse_sgd_step(spec, cfg)
            params = spec.init(jax.random.key(0))
            if model == "deepfm":
                opt = step.init_opt_state(params)
                params, opt, loss = step(params, opt, jnp.int32(0), ids,
                                         vals, labels, weights, aux)
            else:
                params, loss = step(params, jnp.int32(0), ids, vals,
                                    labels, weights, aux)
            assert np.isfinite(float(loss)), f"{model}:{label}"


def test_dirty_input_leg_quarantines_exactly_the_injected_lines(tmp_path):
    """The --dirty-input leg (ISSUE 5): synthetic 3-shard dataset with
    deterministically corrupted lines streams through the quarantine
    policy; the stamped stats account for EVERY row and the dead-letter
    count equals the injected corruption."""
    logs = []
    stats = bench._dirty_input_leg(str(tmp_path), "fm", logs.append)
    assert stats["policy"] == "quarantine"
    assert stats["rows"] == 6000
    assert stats["injected_bad"] == 60
    assert stats["bad_records"] == 60
    assert stats["quarantine_exact"] is True
    assert stats["rows_per_sec"] > 0
    # Priced both ways (ISSUE 6): when the native chunk parser is
    # available the leg re-runs under it and asserts the quarantine
    # accounting is identical, not just similar.
    from fm_spark_tpu.data.native_stream import native_stream_supported

    if native_stream_supported("criteo", 39, 1 << 14):
        assert stats["rows_per_sec_native"] > 0
        assert stats["native_quarantine_exact"] is True
        assert stats["native_counters_match"] is True
    # The dead-letter journal landed beside the artifacts.
    from fm_spark_tpu.utils.logging import read_events

    events = read_events(
        os.path.join(str(tmp_path), "quarantine_fm", "deadletter.jsonl"))
    assert sum(1 for e in events if e["event"] == "bad_record") == 60
    assert logs and "quarantined" in logs[-1]


def test_fused_fallback_payload_never_keep_bests(monkeypatch, capsys):
    """The parent's MEASURED.json gate (ISSUE 8): a payload stamped
    fused_fallback — a fused-requested leg that ran the XLA path — must
    never update the recorded rate, exactly like a degraded one."""
    import json as _json

    from fm_spark_tpu import measured as measured_lib

    def _boom(*a, **kw):
        raise AssertionError("fused_fallback payload reached keep-best")

    monkeypatch.setattr(measured_lib, "update_entry", _boom)
    payload = {
        "metric": "criteo_fm_rank64_10Mfeat_samples_per_sec_per_chip",
        "value": 9e9, "unit": "samples/sec/chip",
        "variant": "bfloat16/dedup_sr/compact12288/cd-bf16/fusedbwd",
        "device": "TPU v5 lite", "fused_fallback": True,
    }
    monkeypatch.setitem(bench._SALVAGE, "line", _json.dumps(payload))
    monkeypatch.setitem(bench._SALVAGE, "emitted", False)
    bench._emit_final()  # must print the line but refuse the record
    out = capsys.readouterr().out
    assert _json.loads(out.strip().splitlines()[-1]) == payload

    # Control: the same payload WITHOUT the stamp reaches update_entry.
    called = {}
    monkeypatch.setattr(
        measured_lib, "update_entry",
        lambda entry, **kw: called.setdefault("entry", entry))
    clean = {k: v for k, v in payload.items() if k != "fused_fallback"}
    monkeypatch.setitem(bench._SALVAGE, "line", _json.dumps(clean))
    monkeypatch.setitem(bench._SALVAGE, "emitted", False)
    bench._emit_final()
    assert called, "clean payload should have reached keep-best"
