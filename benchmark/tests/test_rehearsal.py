"""Every cell end to end at tiny sizes on the CPU (four virtual devices
for the four-chip cell). A rehearsal is never a result: no metric, never
``correct``; off the chip a real run exits non-zero before any work."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import harness

RUN = os.path.join(harness.HERE, "run.py")
MANIFEST = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))


def run(*args, cache_dir):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(cache_dir)}
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, RUN, *args], env=env, text=True,
                          capture_output=True, timeout=900, cwd=harness.ROOT)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_rehearsal(cell, trace, tmp_path):
    done = run("--workload", cell, "--seed", "3", "--seconds", "3",
               "--trace", str(trace), "--rehearse", cache_dir=tmp_path)
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is False
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert line["rehearsed"]["checks_passed"] is True, done.stderr[-3000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    want = {m["name"] for m in MANIFEST["per_layer" if trace else "end_to_end"]
            if cell in m.get("workloads", [cell])}
    got = set(line["rehearsed"]["values"])
    # The CPU has no device plane: what needs the trace is left out.
    assert got <= want and (trace or got == want)


def test_off_the_chip_a_real_run_refuses(tmp_path):
    done = run("--workload", "fm_r64.train", "--seed", "1", "--seconds", "1",
               "--trace", "0", cache_dir=tmp_path)
    assert done.returncode != 0 and done.stdout.strip() == ""
    assert "needs a TPU" in done.stderr
