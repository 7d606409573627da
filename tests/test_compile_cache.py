"""Warm-start contract: the persistent compile cache, where it lives,
and the AOT lower/compile entries.

The load-bearing test is the CROSS-PROCESS one: a cold process
populates the cache dir, and a second process compiling the same
winner-variant step performs ZERO fresh XLA compilations (every compile
request is a cache hit). Subprocesses are required: in-process, jit's
own dispatch cache would short-circuit before the persistent cache is
ever consulted. Children are pointed at a directory the way any launcher
would point them: through ``JAX_COMPILATION_CACHE_DIR``.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fm_spark_tpu import models
from fm_spark_tpu.train import TrainConfig
from fm_spark_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _small_fm_spec(**kw):
    return models.FieldFMSpec(
        num_features=3 * 32, rank=2, num_fields=3, bucket=32,
        init_std=0.01, **kw,
    )


# The winner-variant lever stack (minus segtotal_pallas, whose CPU
# interpret mode would dominate the test's runtime without changing
# what is being pinned): bf16 storage + dedup_sr + host compact + gfull.
# The child records every jax.config.update it sees: with the variable
# set, the program must set no cache directory in code.
_CHILD = """
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
_updates = []
_real_update = jax.config.update
def _spy(name, value):
    _updates.append(name)
    return _real_update(name, value)
jax.config.update = _spy
from fm_spark_tpu.utils import compile_cache
from fm_spark_tpu import models
from fm_spark_tpu.train import TrainConfig
from fm_spark_tpu.sparse import precompile_field_sparse_step

compile_cache.enable()
spec = models.FieldFMSpec(num_features=3 * 32, rank=2, num_fields=3,
                          bucket=32, init_std=0.01,
                          param_dtype="bfloat16",
                          compute_dtype="bfloat16")
config = TrainConfig(learning_rate=0.05, lr_schedule="constant",
                     optimizer="sgd", sparse_update="dedup_sr",
                     host_dedup=True, compact_cap=32, gfull_fused=True)
precompile_field_sparse_step(spec, config, 64)
print(json.dumps({**compile_cache.cache_stats(),
                  "dir_set_in_code":
                      "jax_compilation_cache_dir" in _updates}))
"""


def _run_child(cache_dir) -> dict:
    out = subprocess.run(
        [sys.executable, "-c", _CHILD],
        capture_output=True, text=True, timeout=420, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "JAX_COMPILATION_CACHE_DIR": str(cache_dir)},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_cold_populates_then_warm_compiles_nothing(tmp_path):
    """Cold run: cache misses, entries written — in the directory the
    VARIABLE names, with no directory set in code. Warm run (new
    process, same step): zero fresh XLA compilations — the warm-start
    acceptance criterion, asserted via cache stats."""
    cold = _run_child(tmp_path / "cc")
    assert cold["enabled"]
    assert cold["dir"] == str(tmp_path / "cc")
    assert not cold["dir_set_in_code"]
    assert cold["misses"] > 0
    assert cold["entries"] > 0
    assert cold["bytes"] > 0

    warm = _run_child(tmp_path / "cc")
    assert warm["misses"] == 0, (
        f"warm process recompiled: {warm}"
    )
    assert warm["hits"] >= 1
    # Nothing new was serialized — the executables were all reused.
    assert warm["entries"] == cold["entries"]


@pytest.fixture
def cache_config_guard():
    """Restore jax's cache config + the module's state after a test
    that re-places the cache in-process."""
    prev = {
        "jax_compilation_cache_dir":
            jax.config.jax_compilation_cache_dir,
        "jax_persistent_cache_min_entry_size_bytes":
            jax.config.jax_persistent_cache_min_entry_size_bytes,
        "jax_persistent_cache_min_compile_time_secs":
            jax.config.jax_persistent_cache_min_compile_time_secs,
    }
    prev_dir = compile_cache._state["dir"]
    yield
    for k, v in prev.items():
        jax.config.update(k, v)
    compile_cache._state["dir"] = prev_dir
    compile_cache.reset_stats()


def test_enable_and_stats_in_process(tmp_path, cache_config_guard,
                                     monkeypatch):
    # What jax itself does at import when the variable names a dir.
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path / "cc"))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cc"))
    d = compile_cache.enable()
    assert d == str(tmp_path / "cc") and os.path.isdir(d)
    assert compile_cache.is_enabled()
    compile_cache.reset_stats()

    @jax.jit
    def f(x):
        return jnp.sin(x) * 3.25 + jnp.flip(x)

    f(jnp.arange(23.0)).block_until_ready()
    s = compile_cache.cache_stats()
    assert s["requests"] >= 1
    assert s["entries"] >= 1
    assert s["misses"] + s["hits"] == s["requests"]


def test_unset_variable_means_the_fixed_checkout_path(
        tmp_path, cache_config_guard, monkeypatch):
    """No variable: the cache is ``<checkout>/.jax_compile_cache`` —
    DEFAULT_DIR, a fixed path, so a second run finds the first's
    entries. (Redirected for the call itself, so the suite never writes
    into the checkout.)"""
    assert compile_cache.DEFAULT_DIR == os.path.join(
        REPO, ".jax_compile_cache")
    monkeypatch.delenv(compile_cache.ENV)
    monkeypatch.setattr(compile_cache, "DEFAULT_DIR",
                        str(tmp_path / ".jax_compile_cache"))
    assert compile_cache.enable() == compile_cache.DEFAULT_DIR
    assert jax.config.jax_compilation_cache_dir == compile_cache.DEFAULT_DIR
    assert os.path.isfile(
        os.path.join(compile_cache.DEFAULT_DIR, "cache_meta.json"))
    # Idempotent: a second call neither moves nor re-arms anything.
    assert compile_cache.enable() == compile_cache.DEFAULT_DIR


def test_variable_set_after_import_is_an_error_not_a_silent_no_cache(
        tmp_path, cache_config_guard, monkeypatch):
    """jax reads the variable at import only. Setting it later would
    leave the process with no cache at all; the contract forbids
    repairing that in code, so it must be loud."""
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path / "late"))
    jax.config.update("jax_compilation_cache_dir", None)
    compile_cache._state["dir"] = None
    with pytest.raises(RuntimeError, match="after jax was imported"):
        compile_cache.enable()


def test_entry_points_keep_the_cache_on_without_a_flag(
        cache_config_guard):
    """FMTrainer (and through it every training entry point) arms the
    cache with no flag and no opt-in variable."""
    from fm_spark_tpu.train import FMTrainer

    compile_cache._state["dir"] = None
    FMTrainer(models.FMSpec(num_features=32, rank=2),
              TrainConfig(optimizer="sgd"))
    assert compile_cache.is_enabled()
    assert compile_cache.cache_stats()["dir"] == os.environ[
        compile_cache.ENV]


def test_aot_compiled_step_matches_jit_step(rng):
    """The AOT entry's Compiled is the SAME program the training loop's
    jit dispatch builds: running both from identical state yields the
    identical loss (and the Compiled is callable with concrete args)."""
    from fm_spark_tpu.sparse import (
        make_field_sparse_sgd_step,
        precompile_field_sparse_step,
    )

    spec = _small_fm_spec()
    config = TrainConfig(learning_rate=0.05, lr_schedule="constant",
                         optimizer="sgd")
    B = 32
    ids = jnp.asarray(rng.integers(0, 32, (B, 3)).astype(np.int32))
    vals = jnp.ones((B, 3), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 2, B).astype(np.float32))
    weights = jnp.ones((B,), jnp.float32)

    compiled = precompile_field_sparse_step(spec, config, B)
    p1 = spec.init(jax.random.key(7))
    _, loss_aot = compiled(p1, jnp.int32(0), ids, vals, labels,
                           weights, None)

    step = make_field_sparse_sgd_step(spec, config)
    p2 = spec.init(jax.random.key(7))
    _, loss_jit = step(p2, jnp.int32(0), ids, vals, labels, weights)
    assert float(loss_aot) == pytest.approx(float(loss_jit), rel=1e-6)


def test_aot_rejects_bad_args():
    from fm_spark_tpu.sparse import lower_field_sparse_step

    spec = _small_fm_spec()
    config = TrainConfig(learning_rate=0.05, lr_schedule="constant",
                         optimizer="sgd")
    with pytest.raises(ValueError, match="steps per call"):
        lower_field_sparse_step(spec, config, 32, steps_per_call=0)


def test_sharded_aot_entries(eight_devices):
    """The field-sharded and dense-mesh AOT entries lower (and the FM
    sharded one compiles) against abstract sharded shapes — no table or
    batch ever placed on the mesh."""
    from fm_spark_tpu.parallel import (
        lower_field_sharded_step,
        lower_parallel_train_step,
        make_field_mesh,
        make_mesh,
        precompile_field_sharded_step,
    )

    mesh = make_field_mesh(8)
    spec = _small_fm_spec(param_dtype="bfloat16",
                          compute_dtype="bfloat16")
    config = TrainConfig(learning_rate=0.05, lr_schedule="constant",
                         optimizer="sgd", sparse_update="dedup_sr",
                         compact_device=True, compact_cap=32,
                         compact_overflow="drop")
    compiled = precompile_field_sharded_step(spec, config, mesh, 64)
    assert compiled is not None

    # FFM + the multistep roll: lower-only (the API/shape contract;
    # full compiles of every family would dominate the suite's budget).
    ffm = models.FieldFFMSpec(
        num_features=3 * 32, rank=2, num_fields=3, bucket=32,
        init_std=0.01, param_dtype="float32", compute_dtype="bfloat16",
    )
    sgd = TrainConfig(learning_rate=0.05, lr_schedule="constant",
                      optimizer="sgd")
    assert lower_field_sharded_step(ffm, sgd, mesh, 64) is not None
    assert lower_field_sharded_step(
        spec, config, mesh, 64, steps_per_call=2
    ) is not None

    # Host-built aux cannot be precompiled (it rides each batch).
    with pytest.raises(ValueError, match="host-built"):
        lower_field_sharded_step(
            spec,
            TrainConfig(learning_rate=0.05, lr_schedule="constant",
                        optimizer="sgd", sparse_update="dedup_sr",
                        host_dedup=True, compact_cap=32),
            mesh, 64,
        )

    # Dense dp/row mesh step (parallel/step.py's entry).
    fm = models.FMSpec(num_features=512, rank=4, init_std=0.01)
    dmesh = make_mesh(2, 4)
    assert lower_parallel_train_step(
        fm, TrainConfig(learning_rate=0.1, optimizer="adam"), dmesh,
        "row", batch_size=64, nnz=8,
    ) is not None
