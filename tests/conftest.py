"""Test fixture: simulate an 8-device TPU mesh on CPU.

The Spark idiom `local[*]` — whole cluster as threads in one JVM, same code
path as a real cluster — maps to XLA's forced host-device count (SURVEY.md
§4): 8 fake CPU devices exercise the identical shard_map/psum code path as a
real v5e-8. Must run before jax initializes, hence env vars at import time.
"""

import os

# Tests need the 8-fake-device mesh and deterministic CPU numerics, on
# any machine. Plugins (jaxtyping) may import jax before this conftest,
# so the env var alone is not enough — jax.config.update works at any
# point before backend init.
os.environ["JAX_PLATFORMS"] = "cpu"
# The telemetry plane is ON by default in `cli train` (ISSUE 7) —
# right for production, wrong for a test suite where hundreds of
# in-process cli.main() calls would each open a run directory in the
# repo, reset the process-wide metrics registry mid-suite, and chain a
# signal handler into the pytest process. Tests that exercise the
# plane pass --obs-dir explicitly (tests/test_cli.py, test_obs*.py).
os.environ.setdefault("FM_SPARK_OBS_DIR", "none")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# The persistent compile cache is ON in every entry point the tests
# drive (cli.main, FMTrainer, fleet replicas, bench children). Place it
# for this session — through the variable, as any launcher would — so
# the suite never reads or writes the checkout's own cache; children
# inherit it, and tests that pin cold/warm behaviour hand their children
# a directory of their own.
if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    import atexit
    import shutil
    import tempfile

    _cc = tempfile.mkdtemp(prefix="fm_spark_tests_cc_")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _cc
    atexit.register(shutil.rmtree, _cc, ignore_errors=True)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
if jax.config.jax_compilation_cache_dir != os.environ[
        "JAX_COMPILATION_CACHE_DIR"]:
    # A plugin imported jax before the variable above was set.
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
jax.config.update("jax_debug_nans", False)  # enabled per-test where useful
assert len(jax.devices()) >= 8, (
    "conftest failed to get 8 fake CPU devices — was the XLA backend "
    "initialized before conftest import?"
)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def eight_devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 fake CPU devices, got {len(devs)}"
    return devs[:8]
