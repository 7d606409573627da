"""Noise-aware regression sentinel (ISSUE 9): deterministic synthetic
series pinning each verdict, and the keep-best gate — a ``regressed`` /
``attachment_transient`` verdict must NEVER overwrite MEASURED.json."""

import importlib.util
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from fm_spark_tpu.obs.ledger import (  # noqa: E402
    PerfLedger,
    measurement_fingerprint,
)
from fm_spark_tpu.obs.sentinel import (  # noqa: E402
    ALL_VERDICTS,
    Sentinel,
    SentinelPolicy,
    classify,
    keepbest_allowed,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: A stable cohort: per-chip rates with ~1% wiggle (the measured
#: healthy-attachment leg-to-leg spread).
STABLE = [1_000_000.0, 1_010_000.0, 995_000.0, 1_005_000.0, 998_000.0]


# ----------------------------------------------------------- classify


def test_step_improvement_classifies_improved():
    block = classify(STABLE, 1_400_000.0)
    assert block["verdict"] == "improved"
    assert block["z"] > 3.0
    assert block["n_history"] == len(STABLE)


def test_in_band_noise_classifies_flat():
    for v in (995_000.0, 1_000_000.0, 1_012_000.0):
        assert classify(STABLE, v)["verdict"] == "flat"


def test_healthy_drop_classifies_regressed():
    block = classify(STABLE, 880_000.0)
    assert block["verdict"] == "regressed"
    assert block["z"] < -3.0
    assert "healthy" in block["reason"]


def test_slow_drift_eventually_classifies_regressed():
    """A -1.5%/round drift: each step sits inside the band, but the
    trailing window follows it down slowly enough that the cumulative
    drop eventually breaks out — the failure mode a fixed threshold
    on the LAST value would never catch."""
    history = list(STABLE)
    value = 1_000_000.0
    verdicts = []
    for _ in range(30):
        value *= 0.985
        block = classify(history, value)
        verdicts.append(block["verdict"])
        history.append(value)
    assert verdicts[0] == "flat"  # one drift step is inside the band
    assert "regressed" in verdicts
    assert "improved" not in verdicts


def test_single_outlier_under_weather_is_attachment_transient():
    """The r03–r05 shape: a throttled window measures way low, but the
    supervisor journal says the attachment was flaky — weather, not a
    regression. The SAME value on a healthy attachment IS regressed."""
    low = 550_000.0
    assert classify(STABLE, low,
                    attachment_health="flaky")["verdict"] \
        == "attachment_transient"
    assert classify(STABLE, low,
                    attachment_health="down")["verdict"] \
        == "attachment_transient"
    assert classify(STABLE, low,
                    attachment_health="healthy")["verdict"] == "regressed"


def test_null_measurement_is_transient_under_weather():
    block = classify(STABLE, None, attachment_health="down")
    assert block["verdict"] == "attachment_transient"
    # A null with NO adverse evidence cannot be blamed on weather.
    assert classify(STABLE, None)["verdict"] == "insufficient_history"


def test_thin_history_is_insufficient():
    assert classify([], 1.0)["verdict"] == "insufficient_history"
    assert classify([1.0, 2.0], 1.0)["verdict"] == "insufficient_history"
    # Nulls in the history carry no statistical weight.
    assert classify([None, None, 1.0], 1.0)["verdict"] \
        == "insufficient_history"


def test_improvement_does_not_fire_on_inflated_noise():
    """One throttled value in the window must not widen the band enough
    to hide a real move — MAD (not stddev) is the noise scale."""
    history = STABLE + [600_000.0]  # one throttled outlier banked
    assert classify(history, 1_400_000.0)["verdict"] == "improved"
    assert classify(history, 850_000.0)["verdict"] == "regressed"


def test_rel_floor_absorbs_identical_history():
    """A cohort that repeats to the digit has MAD 0 — the relative
    floor keeps sub-threshold wiggle flat instead of flagging it."""
    flat_hist = [1_000_000.0] * 5
    assert classify(flat_hist, 1_030_000.0)["verdict"] == "flat"
    assert classify(flat_hist, 1_100_000.0)["verdict"] == "improved"


def test_policy_window_bounds_the_trailing_band():
    """Old history beyond the window must not drag the band: after 8+
    values at the new level, the old level is out of the statistic."""
    history = [500_000.0] * 10 + [1_000_000.0] * 8
    assert classify(history, 1_002_000.0,
                    policy=SentinelPolicy(window=8))["verdict"] == "flat"


def test_verdict_vocabulary_is_closed():
    assert set(ALL_VERDICTS) == {
        "improved", "flat", "regressed", "attachment_transient",
        "insufficient_history"}


# ------------------------------------------------- ledger-bound judge


def _seed(led, values, leg="legA", variant="v", health="healthy"):
    for i, v in enumerate(values):
        led.append({
            "kind": "bench_leg", "leg": leg, "run_id": f"r{i}",
            "value": v,
            "fingerprint": measurement_fingerprint(
                variant=variant, model="fm",
                attachment_health=health),
        })


def test_sentinel_prefers_exact_cohort(tmp_path):
    led = PerfLedger(str(tmp_path / "l.jsonl"))
    _seed(led, STABLE, variant="a")
    _seed(led, [200.0, 210.0, 190.0], variant="b")
    fp_b = measurement_fingerprint(variant="b", model="fm")
    block = Sentinel(led).judge("legA", 205.0, fp_b)
    # Variant b judges against ITS cohort (~200), not the 1M leg-wide
    # mix it would drown in.
    assert block["cohort"] == "exact"
    assert block["verdict"] == "flat"


def test_sentinel_widens_to_leg_when_cohort_thin(tmp_path):
    led = PerfLedger(str(tmp_path / "l.jsonl"))
    _seed(led, STABLE, variant="a")
    fp_new = measurement_fingerprint(variant="brand-new-lever",
                                     model="fm")
    block = Sentinel(led).judge("legA", 1_400_000.0, fp_new)
    # A fresh lever variant has no exact history — judged against the
    # metric's measured band instead of getting a free pass.
    assert block["cohort"] == "leg"
    assert block["verdict"] == "improved"


def test_widening_never_crosses_device_kinds(tmp_path):
    """A first TPU number must not score against CPU history: the
    leg-wide fallback cohort is pinned to the same device_kind +
    n_chips, so a cross-device judgment honestly reports
    insufficient_history instead of a fake 'improved'."""
    led = PerfLedger(str(tmp_path / "l.jsonl"))
    for i, v in enumerate([100.0, 105.0, 95.0, 102.0]):
        led.append({
            "kind": "kernel_pricing", "leg": "gather", "run_id": f"r{i}",
            "value": v,
            "fingerprint": measurement_fingerprint(
                variant="gather", model="kernels", device_kind="cpu",
                n_chips=1)})
    fp_tpu = measurement_fingerprint(variant="gather", model="kernels",
                                     device_kind="TPU v5 lite", n_chips=1)
    # 50 GB/s would be z >> 3 against the CPU band — but it is not
    # comparable evidence, and a regressed TPU rate must not slip
    # through the keep-best gate dressed as 'improved'.
    block = Sentinel(led).judge("gather", 50_000.0, fp_tpu)
    assert block["verdict"] == "insufficient_history"
    # Same-device history still widens across lever configs.
    fp_cpu = measurement_fingerprint(variant="gather-v2",
                                     model="kernels", device_kind="cpu",
                                     n_chips=1)
    block = Sentinel(led).judge("gather", 101.0, fp_cpu)
    assert block["cohort"] == "leg"
    assert block["verdict"] == "flat"


def test_observe_judges_before_appending(tmp_path):
    led = PerfLedger(str(tmp_path / "l.jsonl"))
    _seed(led, STABLE, variant="a")
    fp = measurement_fingerprint(variant="a", model="fm")
    block = Sentinel(led).observe({
        "kind": "bench_leg", "leg": "legA", "run_id": "rx",
        "value": 1_001_000.0, "fingerprint": fp})
    assert block["verdict"] == "flat"
    recs = led.records()
    assert len(recs) == len(STABLE) + 1
    assert recs[-1]["sentinel"]["verdict"] == "flat"
    # The judged value was NOT part of its own history.
    assert block["n_history"] == len(STABLE)


# ------------------------------------------------------ keep-best gate


@pytest.mark.parametrize("verdict,allowed", [
    ("improved", True),
    ("flat", True),
    ("insufficient_history", True),  # defers to the legacy > rule
    ("regressed", False),
    ("attachment_transient", False),
    ("garbage", False),
])
def test_keepbest_allowed_matrix(verdict, allowed):
    assert keepbest_allowed({"verdict": verdict}) is allowed


def test_keepbest_allows_pre_sentinel_artifacts():
    assert keepbest_allowed(None) is True
    assert keepbest_allowed({}) is True


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "bench_gate_mod", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("verdict", ["regressed", "attachment_transient"])
def test_emit_final_gate_never_overwrites_measured(tmp_path, monkeypatch,
                                                   verdict, capsys):
    """The acceptance pin: a TPU-stamped, numerically-better salvage
    line whose sentinel verdict is regressed/attachment_transient must
    leave MEASURED.json byte-identical."""
    import fm_spark_tpu.measured as measured

    src = os.path.join(REPO, "MEASURED.json")
    dst = tmp_path / "MEASURED.json"
    dst.write_bytes(open(src, "rb").read())
    monkeypatch.setattr(measured, "MEASURED_PATH", str(dst))

    bench = _load_bench()
    line = json.dumps({
        "metric": bench.METRIC, "value": 9_999_999.0,
        "unit": bench.UNIT, "vs_baseline": 8.0,
        "variant": "bfloat16/dedup_sr/compact12288/cd-bf16/gfull"
                   "/segtotal",
        "device": "TPU v5 lite",
        "sentinel": {"verdict": verdict, "reason": "test", "z": -9.0,
                     "n_history": 6},
    })
    before = dst.read_bytes()
    with bench._SALVAGE_LOCK:
        bench._SALVAGE.update(line=line, emitted=False)
    bench._emit_final()
    assert dst.read_bytes() == before, (
        f"{verdict} verdict overwrote MEASURED.json")
    # The refused line was still printed (the final-line contract).
    assert json.loads(capsys.readouterr().out)["value"] == 9_999_999.0


def test_emit_final_promotes_improved_verdict(tmp_path, monkeypatch,
                                              capsys):
    """The same line with an ``improved`` verdict DOES promote — the
    gate blocks verdicts, not the keep-best path itself."""
    import fm_spark_tpu.measured as measured

    src = os.path.join(REPO, "MEASURED.json")
    dst = tmp_path / "MEASURED.json"
    dst.write_bytes(open(src, "rb").read())
    monkeypatch.setattr(measured, "MEASURED_PATH", str(dst))

    bench = _load_bench()
    line = json.dumps({
        "metric": bench.METRIC, "value": 9_999_999.0,
        "unit": bench.UNIT, "vs_baseline": 8.0,
        "variant": "bfloat16/dedup_sr/compact12288/cd-bf16/gfull"
                   "/segtotal",
        "device": "TPU v5 lite",
        "sentinel": {"verdict": "improved", "reason": "test", "z": 9.0,
                     "n_history": 6},
    })
    with bench._SALVAGE_LOCK:
        bench._SALVAGE.update(line=line, emitted=False)
    bench._emit_final()
    capsys.readouterr()
    doc = json.loads(dst.read_text())
    assert doc["headline"]["rate_samples_per_sec_per_chip"] \
        == 9_999_999.0


def test_sentinel_widened_cohort_excludes_chaos_rows(tmp_path):
    """ISSUE 10 satellite: chaos-drill rows must never lend their band
    to a real cohort just because the exact history is thin — nor can
    a real band judge a chaos leg."""
    led = PerfLedger(str(tmp_path / "l.jsonl"))
    # The only leg-wide history is chaos-drill rows at a crippled rate.
    for i, v in enumerate([100.0, 105.0, 95.0, 102.0, 99.0]):
        led.append({
            "kind": "bench_leg", "leg": "legA", "run_id": f"c{i}",
            "value": v,
            "fingerprint": measurement_fingerprint(
                variant="a", model="fm", chaos=True),
        })
    fp_real = measurement_fingerprint(variant="brand-new", model="fm")
    block = Sentinel(led).judge("legA", 1_000_000.0, fp_real)
    # Widening found nothing comparable: insufficient history, NOT an
    # "improved" verdict against the chaos band.
    assert block["verdict"] == "insufficient_history"
    # And a chaos measurement judges against the chaos band only.
    fp_chaos = measurement_fingerprint(variant="a", model="fm",
                                       chaos=True)
    chaos_block = Sentinel(led).judge("legA", 101.0, fp_chaos)
    assert chaos_block["cohort"] == "exact"
    assert chaos_block["verdict"] == "flat"


def test_emit_final_gate_refuses_chaos_stamped_payload(tmp_path,
                                                       monkeypatch,
                                                       capsys):
    """ISSUE 10 satellite: a chaos-drill leg — even TPU-stamped,
    numerically better, sentinel-improved — must never pass the
    keep-best gate into MEASURED.json."""
    import fm_spark_tpu.measured as measured

    src = os.path.join(REPO, "MEASURED.json")
    dst = tmp_path / "MEASURED.json"
    dst.write_bytes(open(src, "rb").read())
    monkeypatch.setattr(measured, "MEASURED_PATH", str(dst))

    bench = _load_bench()
    line = json.dumps({
        "metric": bench.METRIC, "value": 9_999_999.0,
        "unit": bench.UNIT, "vs_baseline": 8.0,
        "variant": "bfloat16/dedup_sr/compact12288/cd-bf16/gfull"
                   "/segtotal",
        "device": "TPU v5 lite",
        "chaos": True,
        "sentinel": {"verdict": "improved", "reason": "test", "z": 9.0,
                     "n_history": 6},
    })
    before = dst.read_bytes()
    with bench._SALVAGE_LOCK:
        bench._SALVAGE.update(line=line, emitted=False)
    bench._emit_final()
    assert dst.read_bytes() == before, (
        "a chaos-stamped payload overwrote MEASURED.json")
    assert json.loads(capsys.readouterr().out)["value"] == 9_999_999.0


def test_quality_eval_drifting_auc_series_verdicts(tmp_path):
    """ISSUE 13 satellite: the sentinel over a quality_eval cohort —
    a healthy AUC plateau reads flat, the label-flip collapse reads
    regressed (it is a QUALITY regression, not weather), and the
    cohort never mixes with bench legs."""
    led = PerfLedger(str(tmp_path / "l.jsonl"))
    fp = measurement_fingerprint(variant="quality/demo/ftrl",
                                 model="fm")
    plateau = [0.712, 0.708, 0.715, 0.711, 0.709, 0.713]
    for i, auc in enumerate(plateau):
        led.append({"kind": "quality_eval", "leg": "quality/demo",
                    "run_id": f"d{i}", "value": auc,
                    "fingerprint": fp})
    s = Sentinel(led)
    assert s.judge("quality/demo", 0.710, fp)["verdict"] == "flat"
    drift = s.judge("quality/demo", 0.33, fp)
    assert drift["verdict"] == "regressed"
    assert drift["z"] < -3
    # Same drop under adverse attachment weather would be transient —
    # but quality evals run on-host; healthy weather keeps it real.
    fp_flaky = measurement_fingerprint(variant="quality/demo/ftrl",
                                       model="fm",
                                       attachment_health="flaky")
    assert s.judge("quality/demo", 0.33, fp_flaky)["verdict"] \
        == "attachment_transient"
    # Cohort isolation: a bench leg's history is invisible here.
    assert s.judge("bench_legZ", 0.7, fp)["verdict"] \
        == "insufficient_history"
