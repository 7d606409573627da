"""CI smoke for the warm-start tiered bench (ISSUE 1): ``bench.py
--fast-first`` on the CPU backend.

One subprocess covers the whole contract, kill included:

1. the sweep's FIRST leg lands its non-provisional result as an
   incrementally-persisted keep-best artifact (``legs_completed == 1``
   — written BEFORE any remaining sweep leg completes);
2. a SIGTERM mid-sweep leaves that artifact intact and parseable — an
   interrupted run never reports null when any leg completed;
3. the parent, having salvaged a result line, exits 0 (so callers
   chained on success still advance).

Model ``fm_kaggle`` is the smallest registered shape (39 × 32768 × 33
tables ≈ 170 MB fp32), and its default sweep has no Pallas legs — the
whole run is a few table inits + small CPU compiles.
"""

import json
import os
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def test_fast_first_incremental_artifact_survives_sigterm(tmp_path):
    art = tmp_path / "art"
    kb_path = art / "keepbest_fm_kaggle.json"
    proc = subprocess.Popen(
        [sys.executable, BENCH, "--fast-first",
         "--model", "fm_kaggle", "--batch", "128", "--steps", "2",
         "--artifacts-dir", str(art),
         "--attempts", "1", "--attempt-timeout", "560",
         "--total-deadline", "580", "--init-timeout", "180"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu",
                       "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cc")},
    )
    try:
        # Wait for the FIRST leg's keep-best artifact (the fast-first
        # tier boundary); the remaining legs are still ahead.
        deadline = time.time() + 560
        kb = None
        while time.time() < deadline and proc.poll() is None:
            if kb_path.exists():
                try:
                    kb = json.loads(kb_path.read_text())
                except json.JSONDecodeError:
                    kb = None  # mid-replace; atomic rename lands whole
                if kb is not None:
                    break
            time.sleep(0.5)
        assert kb is not None, "no keep-best artifact before deadline"
        assert kb["value"] is not None and kb["value"] > 0
        assert kb["metric"].startswith("kaggle_fm_rank32")
        assert kb["legs_completed"] == 1, (
            "first persisted result must precede the remaining legs"
        )
        assert kb["t_first_result_s"] > 0
        assert "/b128" in kb["variant"]  # shape provenance stamp

        # Give the parent's stdout reader a beat to record the child's
        # result line, then kill mid-sweep.
        time.sleep(2.0)
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate(timeout=60)

    # Salvaged run: exit 0 with a parseable final result line.
    assert proc.returncode == 0, f"rc={proc.returncode}\n{out[-2000:]}"
    lines = [ln for ln in out.strip().splitlines()
             if ln.startswith("{")]
    assert lines, f"no JSON line on stdout:\n{out[-2000:]}"
    final = json.loads(lines[-1])
    assert final.get("value") is not None
    assert final.get("error") is None
    # The artifact survived the kill and still parses.
    assert json.loads(kb_path.read_text())["value"] is not None
    # Every completed leg was streamed to the sweep log.
    sweep = (art / "sweep_fm_kaggle.jsonl").read_text().strip()
    assert len(sweep.splitlines()) >= 1
    for ln in sweep.splitlines():
        assert json.loads(ln)["value"] > 0
