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

:func:`enable` also registers, once, a listener for jax's compile
DURATIONS, which turns each into a hot interval of the program's one
ring (``obs.record_interval``): ``compile/trace``, ``compile/lower``,
``compile/backend`` and ``compile/cache_read``, with ``fun_name`` where
jax passes it. In jax 0.9.0 ``backend_compile_duration`` wraps
``compiler.compile_or_get_cached``, so a ``compile/backend`` is the XLA
compilation OR the read from this cache: its ``cache_hit`` says which
(and ``saved_s`` what the hit saved), and the ``compile/cache_read``
lies inside it. One clock: the callback reads ``time.perf_counter()``
when jax reports the duration and counts back; jax's own ``time.time()``
stamps are not used. The parent is the recording thread's innermost open
interval, so a compilation inside a loop hangs under the
``train/dispatch`` (``step``) or ``serve/batch`` that caused it. jax
reports a trace for every ``jax.numpy`` function traced INSIDE another
function's trace or lowering, hundreds a step: a trace or lowering
becomes an interval only where none is open around it on its thread (the
outer one's duration holds it), told by the start jax announces for
each; every ``compile/backend`` is kept. The listeners run only when
something compiles.

The cache composes with the AOT entries (:func:`fm_spark_tpu.sparse.
precompile_field_sparse_step` and friends): an AOT ``.compile()``
populates the same cache the later jit dispatch reads.
"""

from __future__ import annotations

import os
import threading
import time

from fm_spark_tpu import obs

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

# jax's duration events (jax/_src/dispatch.py, compiler.py) and the hot
# interval each becomes. A jax without one of them loses that interval.
_COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile/trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile/lower",
    "/jax/core/compile/backend_compile_duration": "compile/backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "compile/cache_read",
}
_SAVED_EVENT = "/jax/compilation_cache/compile_time_saved_sec"

_lock = threading.Lock()
_state = {"dir": None, "hits": 0, "requests": 0, "listener": False}
# Per thread (jax reports a compile's events on the compiling thread):
# between a cache request and its backend_compile_duration, did the
# cache answer (``hit``) and what did that save (``saved_s``); and how
# many of jax's compile durations have started and not ended (``open``).
_pending = threading.local()


def _on_event(event: str, **_kw) -> None:
    if event == _HIT_EVENT:
        with _lock:
            _state["hits"] += 1
        _pending.hit = True
    elif event == _REQUEST_EVENT:
        with _lock:
            _state["requests"] += 1
        _pending.hit, _pending.saved_s = False, None


def _on_start(event: str, _value=None, **_kw) -> None:
    """jax announces a duration's start as a scalar under the same name:
    count it, so that its end knows whether it lay inside another."""
    if event in _COMPILE_SPANS:
        _pending.open = getattr(_pending, "open", 0) + 1


def _on_duration(event: str, duration, **kw) -> None:
    """One of jax's compile durations into the ring (module docstring).
    Called from inside a compile: whatever a later jax hands it, it
    records what it can and never raises."""
    try:
        name = _COMPILE_SPANS.get(event)
        if name is None:
            if event == _SAVED_EVENT:
                _pending.saved_s = float(duration)
            return
        t1 = time.perf_counter()
        if name != "compile/cache_read":    # jax announces no start of it
            _pending.open = around = max(getattr(_pending, "open", 1) - 1, 0)
            if around and name != "compile/backend":
                return
        attrs = {}
        if "fun_name" in kw:
            attrs["fun_name"] = str(kw["fun_name"])
        if name == "compile/backend":
            attrs["cache_hit"] = getattr(_pending, "hit", False)
            saved = getattr(_pending, "saved_s", None)
            if attrs["cache_hit"] and saved is not None:
                attrs["saved_s"] = saved
            _pending.hit, _pending.saved_s = False, None
        obs.record_interval(name, t1 - float(duration), t1, **attrs)
    except Exception:  # noqa: BLE001 -- a tracing fault must not fail the compile it narrates
        pass


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
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_scalar_listener(_on_start)
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
