"""Persistent XLA compilation cache: on by default, placed from outside.

The step programs are deterministic functions of (spec, TrainConfig,
batch shape), so a SECOND process should never pay XLA again: jax's
persistent compilation cache serializes every compiled executable to
disk keyed by the lowered HLO + compile options + platform version, and
a warm process deserializes instead of recompiling. On the TPU the first
compile of each fused step is the larger part of a cold run, so every
entry point (``cli train`` / ``serve`` / ``predict``, ``FMTrainer``,
``bench.py``'s child, fleet replicas, ``chip_smoke.py``) calls
:func:`enable` without being asked.

Where the cache lives:

- ``JAX_COMPILATION_CACHE_DIR`` set: jax reads that variable itself at
  import, and this module sets NO directory in code — whoever launched
  the process (a test, a fleet parent, the machine's image) placed it.
- unset: ``<checkout>/.jax_compile_cache`` (:data:`DEFAULT_DIR`). A
  fixed path, never a temporary, pid- or time-named one: a cache that
  moves between runs never hits.

:func:`enable` also drops jax's min-size / min-compile-time thresholds
to zero so EVERY executable is cached (the defaults skip sub-second
compiles, and the warm-start contract is "a warm process performs ZERO
fresh XLA compilations"), and :func:`cache_stats` exposes hit/miss
counts (jax's monitoring events) plus the on-disk footprint so tests,
``PredictEngine.warmup`` and the smoke assert that contract instead of
trusting wall-clock. jax's own ``JAX_ENABLE_COMPILATION_CACHE=false``
still turns the cache off; nothing here overrides it.

The cache composes with the AOT entries (:func:`fm_spark_tpu.sparse.
precompile_field_sparse_step` and friends): an AOT ``.compile()``
populates the same cache the later jit dispatch reads.
"""

from __future__ import annotations

import os
import threading

__all__ = [
    "DEFAULT_DIR",
    "ENV",
    "cache_stats",
    "enable",
    "is_enabled",
    "reset_stats",
]

#: jax's own variable; read by jax at import, never written here.
ENV = "JAX_COMPILATION_CACHE_DIR"

# Repo root = two levels above the package (utils/ -> fm_spark_tpu/ ->
# repo): the default cache travels with the checkout.
_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_DIR = os.path.join(_REPO_ROOT, ".jax_compile_cache")

# jax monitoring event names (jax/_src/compiler.py): one *request* per
# compile that consults the cache, one *hit* per executable served from
# it. misses = requests − hits, i.e. fresh XLA compilations.
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"

_lock = threading.Lock()
_state = {"dir": None, "hits": 0, "requests": 0, "listener": False}


def _on_event(event: str, **_kw) -> None:
    if event == _HIT_EVENT:
        with _lock:
            _state["hits"] += 1
    elif event == _REQUEST_EVENT:
        with _lock:
            _state["requests"] += 1


def enable() -> str:
    """Turn the persistent compilation cache on and return its
    directory (see the module docstring for where that is). Idempotent;
    safe before OR after backend init — only compiles issued after the
    first call are covered."""
    import jax
    from jax._src import compilation_cache, monitoring

    placed = bool(os.environ.get(ENV, "").strip())
    path = jax.config.jax_compilation_cache_dir if placed else DEFAULT_DIR
    if not path:
        raise RuntimeError(
            f"{ENV} was set after jax was imported, so jax never read "
            "it and would keep no cache; set it in the environment the "
            "process starts with")
    path = os.path.abspath(path)
    with _lock:
        if _state["dir"] == path:
            return path
    if not placed:
        jax.config.update("jax_compilation_cache_dir", path)
    # Cache EVERYTHING: the default thresholds skip small/fast compiles,
    # but warm-start correctness (zero fresh compilations) needs every
    # executable the step dispatch will ask for — including the tiny
    # device_put/convert helpers that precede the fused step.
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    os.makedirs(path, exist_ok=True)
    # Provenance breadcrumb through the durable seam (ISSUE 20, the
    # ``cache`` path class): which process last enabled the cache, and
    # with which jax — the first thing to check when a "warm" start
    # recompiles. Best-effort: a cache on a failing disk still works
    # as a cache.
    from fm_spark_tpu.utils import durable

    durable.atomic_write_json(
        os.path.join(path, "cache_meta.json"),
        {"dir": path, "pid": os.getpid(), "jax_version": jax.__version__},
        path_class="cache", best_effort=True)
    # jax latches "is the cache used?" at the FIRST compile of the
    # process; a process that compiled anything before enable() would
    # silently never write an entry. Resetting the latch makes enable()
    # effective at any point; the file cache lazily re-initializes from
    # the configured directory on the next compile.
    compilation_cache.reset_cache()
    with _lock:
        _state["dir"] = path
        listen = not _state["listener"]
        _state["listener"] = True
    if listen:
        monitoring.register_event_listener(_on_event)
    return path


def is_enabled() -> bool:
    return _state["dir"] is not None


def reset_stats() -> None:
    """Zero the in-process hit/miss counters (on-disk entries are
    untouched). Tests use this to isolate the compile they measure."""
    with _lock:
        _state["hits"] = 0
        _state["requests"] = 0


def cache_stats() -> dict:
    """Counters + on-disk footprint::

        {"enabled": bool, "dir": str|None,
         "requests": int, "hits": int, "misses": int,
         "entries": int, "bytes": int}

    ``misses`` = compile requests served by a fresh XLA compilation this
    process; the warm-start contract is ``misses == 0`` on a populated
    cache. ``entries`` counts serialized executables (the ``*-cache``
    files of jax's LRU file cache; its ``-atime`` bookkeeping and the
    breadcrumb are not executables).
    """
    with _lock:
        d = _state["dir"]
        hits, requests = _state["hits"], _state["requests"]
    entries = 0
    nbytes = 0
    if d and os.path.isdir(d):
        for root, _dirs, files in os.walk(d):
            for f in files:
                if not f.endswith("-cache"):
                    continue
                entries += 1
                try:
                    nbytes += os.path.getsize(os.path.join(root, f))
                except OSError:
                    pass
    return {
        "enabled": d is not None,
        "dir": d,
        "requests": requests,
        "hits": hits,
        "misses": max(0, requests - hits),
        "entries": entries,
        "bytes": nbytes,
    }
