"""The contracts that let the program be trusted on the chip, pinned on
the CPU: chip_smoke.py refuses to run without a TPU, one function
decides interpret-vs-compile, VMEM budgets count lane padding, the
native library is keyed by its source's hash, every entry point names
its device, one process per chip (bench.py's parent stays off JAX; the
local fleet launchers refuse on a TPU).
"""

import contextlib
import importlib.util
import io
import json
import os
import shutil
import subprocess
import sys
import types

import jax
import pytest

from fm_spark_tpu import native, ops
from fm_spark_tpu.ops import PallasUnavailable, vmem

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CPU_ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}


# ---------------------------------------------------------- chip_smoke.py


@pytest.mark.parametrize("platforms", ["cpu", "tpu"])
def test_chip_smoke_refuses_without_a_tpu_before_doing_work(
        tmp_path, platforms):
    """``JAX_PLATFORMS=cpu``, or a machine told to use a TPU it does not
    have (backend init raises), is exit != 0 in phase 0: no phase line,
    no verdict, nothing written."""
    script = tmp_path / "repo" / "chip_smoke.py"
    script.parent.mkdir()
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), script)
    out = subprocess.run(
        [sys.executable, str(script)], capture_output=True, text=True,
        timeout=120,
        env={**os.environ, "JAX_PLATFORMS": platforms, "PYTHONPATH": REPO})
    assert out.returncode != 0
    assert out.stdout == ""
    assert "no TPU" in out.stderr
    assert os.listdir(script.parent) == ["chip_smoke.py"]


def test_chip_smoke_alone_in_a_directory_fails_without_a_result(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in CPU_ENV.items() if k != "PYTHONPATH"}
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], capture_output=True, text=True,
        timeout=120, cwd=tmp_path, env=env)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "fm_spark_tpu" in out.stderr


# ------------------------------------------------- the interpret decision


@pytest.mark.parametrize("platform,want", [("cpu", True), ("tpu", False)])
def test_one_function_decides_interpret(monkeypatch, platform, want):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    assert ops.pallas_interpret() is want


def test_unknown_platform_raises_instead_of_interpreting(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(PallasUnavailable, match="'gpu'"):
        ops.pallas_interpret()


def test_no_other_code_compares_the_backend_to_a_platform_name():
    """The decision used to be five string compares; it stays one."""
    hits = []
    for root, _dirs, files in os.walk(os.path.join(REPO, "fm_spark_tpu")):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            with open(path) as f:
                if "default_backend()" in f.read():
                    hits.append(os.path.relpath(path, REPO))
    assert hits == ["fm_spark_tpu/ops/__init__.py"]


# ------------------------------------------------------------ VMEM budgets


def test_vmem_counts_what_mosaic_allocates():
    # The segment-totals accumulator at config 3: [16384+520, 65] fp32
    # is 8.65 MB lane-padded to 128, not the 4.4 MB of its elements.
    assert vmem.buffer_bytes((16904, 65)) == 16904 * 128 * 4
    # 16-bit rows pack 16 to a sublane tile; buffers multiply.
    assert vmem.buffer_bytes((9, 65), 2, buffers=2) == 2 * 16 * 128 * 2
    # Leading dims multiply the padded trailing tile.
    assert vmem.buffer_bytes((128, 23, 368)) == 128 * 24 * 384 * 4


def test_vmem_limit_refuses_at_build_time_over_the_chips_capacity():
    from fm_spark_tpu.ops import pallas_fused

    assert vmem.limit_for(1 << 20, "small") == vmem.DEFAULT_LIMIT
    need = 40 << 20
    assert vmem.limit_for(need, "resident") == need + need // 4
    with pytest.raises(PallasUnavailable, match="VMEM"):
        vmem.limit_for(vmem.usable_bytes(), "too big")
    # Config 3's fused backward fits (with a raised limit); a cap of a
    # million rows does not, and says so through the probe.
    assert pallas_fused.fm_bwd_supported(16384, 65, 2) is None
    assert "VMEM" in pallas_fused.fm_bwd_supported(1 << 20, 65, 4)


# ------------------------------------------------------- native library


def _native_copy(tmp_path, name):
    """The native package's loader over a private copy of its source."""
    shutil.copy(os.path.join(REPO, "fm_spark_tpu", "native", "__init__.py"),
                tmp_path / "__init__.py")
    spec = importlib.util.spec_from_file_location(
        name, tmp_path / "__init__.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.skipif(not native.available(),
                    reason=f"no native build: {native.build_error()}")
def test_native_library_is_keyed_by_source_hash_and_ignores_stale_so(
        tmp_path):
    built = native.lib_path()
    shutil.copy(os.path.join(REPO, "fm_spark_tpu", "native", "fasthash.cpp"),
                tmp_path)
    shutil.copy(built, tmp_path)
    # A binary an older tree left behind, under the old fixed name.
    (tmp_path / "libfmfast.so").write_bytes(b"not a shared object")
    mod = _native_copy(tmp_path, "_native_copy_a")
    assert os.path.basename(mod.lib_path()) == os.path.basename(built)
    assert mod.available(), mod.build_error()
    assert mod._lib._name == mod.lib_path()
    # Change the source: the name moves, so the old binary is never
    # loaded for the new source.
    with open(tmp_path / "fasthash.cpp", "a") as f:
        f.write("\n// changed\n")
    assert mod.lib_path() != str(tmp_path / os.path.basename(built))
    assert not os.path.exists(mod.lib_path())


@pytest.mark.skipif(shutil.which("g++") is None, reason="no g++ on PATH")
def test_native_missing_symbol_is_an_error_not_a_numpy_fallback(tmp_path):
    shutil.copy(os.path.join(REPO, "fm_spark_tpu", "native", "fasthash.cpp"),
                tmp_path)
    mod = _native_copy(tmp_path, "_native_copy_b")
    # A library under the RIGHT name that lacks most of the surface.
    stub = tmp_path / "stub.cpp"
    stub.write_text('extern "C" unsigned fm_murmur3_32() { return 0; }\n')
    subprocess.run(["g++", "-shared", "-fPIC", str(stub), "-o",
                    mod.lib_path()], check=True, timeout=120)
    with pytest.raises(AttributeError, match="fm_hash_bytes_batch"):
        mod.available()


# ----------------------------------------------- every entry point names
# ----------------------------------------------- the device it ran on


def _cli_json(argv):
    from fm_spark_tpu import cli

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        assert cli.main(argv) == 0
    return [json.loads(ln) for ln in buf.getvalue().splitlines()
            if ln.startswith("{")]


def test_cli_train_names_device_first_and_reports_placement(monkeypatch):
    import dataclasses

    from fm_spark_tpu import configs as configs_lib

    # Config 3's code path at a CPU-test size (as tests/test_cli.py).
    monkeypatch.setitem(
        configs_lib.CONFIGS, "criteo_small", dataclasses.replace(
            configs_lib.CONFIGS["criteo1tb_fm_r64"], name="criteo_small",
            bucket=64))
    docs = _cli_json(["train", "--config", "criteo_small",
                      "--synthetic", "512", "--steps", "3",
                      "--batch-size", "64", "--test-fraction", "0",
                      "--log-every", "1"])
    assert docs[0]["device"] == {"platform": "cpu", "kind": "cpu",
                                 "count": jax.device_count()}
    assert docs[0]["compile_cache"] == os.environ[
        "JAX_COMPILATION_CACHE_DIR"]
    (placed,) = [d["placement"] for d in docs if "placement" in d]
    assert placed["platforms"] == ["cpu"]
    # 39 fields pad to 40 over the 8 forced host devices: 5 each.
    assert placed["fields_per_device"] == {
        str(i): 5 for i in range(jax.device_count())}
    assert any("memory_after_fit" in d for d in docs)
    rates = [d for d in docs if "samples_per_sec_per_chip" in d]
    assert rates and docs.index(rates[0]) > 0


def test_cli_serve_names_device_first(tmp_path):
    from fm_spark_tpu import models

    spec = models.FieldFMSpec(num_features=4 * 32, rank=2, num_fields=4,
                              bucket=32, init_std=0.1)
    models.save_model(str(tmp_path / "m"), spec,
                      spec.init(jax.random.key(0)))
    docs = _cli_json(["serve", "--model", str(tmp_path / "m"),
                      "--synthetic", "64", "--batch-size", "8",
                      "--buckets", "1,8", "--max-requests", "4"])
    assert docs[0]["device"]["platform"] == "cpu"
    assert docs[-1]["serve_summary"]["served_requests"] == 4


# ------------------------------------------------- one process per chip


_PARENT = """
import json, sys
sys.argv = ["bench.py", "--attempts", "1"]
import bench
# A TPU result far under the recorded best: the parent walks its whole
# keep-best gate (which imports the package, and with it jax) and then
# leaves MEASURED.json alone.
line = json.dumps({"metric": bench.METRIC, "value": 1.0,
                   "device": "TPU v5 lite", "platform": "tpu"})
bench._run_attempt = lambda argv, timeout_s: (line, "")
rc = bench.main()
from jax._src import xla_bridge
print(json.dumps({"rc": rc, "jax_imported": "jax" in sys.modules,
                  "backend_initialized":
                      xla_bridge.backends_are_initialized()}))
"""


def test_bench_parent_initialises_no_backend(tmp_path):
    """The parent supervises a child that needs the chip; had it touched
    a JAX backend it would hold that chip. Whole parent path, with the
    child's run replaced by its result line."""
    out = subprocess.run(
        [sys.executable, "-c", _PARENT], capture_output=True, text=True,
        timeout=120, cwd=REPO,
        env={k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"})
    assert out.returncode == 0, out.stderr[-2000:]
    verdict = json.loads(out.stdout.strip().splitlines()[-1])
    assert verdict == {"rc": 0, "jax_imported": True,
                       "backend_initialized": False}


def _fake_tpu(monkeypatch):
    dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite",
                                id=0)
    monkeypatch.setattr(jax, "devices", lambda *a: [dev])


def test_refuse_on_tpu_is_quiet_on_the_cpu():
    from fm_spark_tpu.serve.fleet import refuse_on_tpu

    assert refuse_on_tpu("a test") is None


def test_cli_serve_fleet_refuses_on_a_tpu(monkeypatch, tmp_path):
    from fm_spark_tpu import cli

    _fake_tpu(monkeypatch)
    with pytest.raises(SystemExit) as e:
        cli.main(["serve", "--fleet", "2", "--model", str(tmp_path),
                  "--obs-dir", "none"])
    msg = str(e.value)
    assert "cli serve --fleet 2" in msg and "TPU v5 lite" in msg
    assert "one process at a time" in msg
    assert not os.listdir(tmp_path)     # nothing was started


def test_bench_serve_fleet_refuses_on_a_tpu(monkeypatch, tmp_path):
    spec = importlib.util.spec_from_file_location(
        "_bench_serve_refusal", os.path.join(REPO, "bench_serve.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    _fake_tpu(monkeypatch)
    with pytest.raises(SystemExit, match="bench_serve.py --fleet 2"):
        mod.main(["--fleet", "2", "--smoke",
                  "--art-dir", str(tmp_path / "art")])
    assert not os.path.exists(tmp_path / "art")
