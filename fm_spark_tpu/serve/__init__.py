"""Low-latency online serving runtime (ISSUE 12).

The request path for the millions-of-users north star, composed from
pieces other PRs battle-tested:

- :mod:`~fm_spark_tpu.serve.engine` — the AOT micro-batched
  :class:`PredictEngine`: per-bucket executables compiled once at
  warmup through the PR-1 persistent compile cache (zero fresh XLA
  compiles on the request path), a request coalescer under an explicit
  latency budget, and an atomically swappable model generation;
- :mod:`~fm_spark_tpu.serve.reload` — the :class:`ReloadFollower`:
  hot model reload by polling the checkpoint chain's ``last_good``
  publish point through the read-only
  :class:`~fm_spark_tpu.checkpoint.ChainFollower`, with degraded mode
  (keep serving the old generation) and a bounded-staleness gauge;
- :mod:`~fm_spark_tpu.serve.frontdoor` — the production front door
  (ISSUE 17): stdlib HTTP transport + deadline-aware admission
  control (priority classes, bounded per-class queues, shed BEFORE
  the coalescer, Retry-After backpressure);
- :mod:`~fm_spark_tpu.serve.fleet` — the multi-process replica fleet:
  N engines behind one door, each hot-following the chain via its own
  read-only ``ChainFollower``, health-checked/drained/re-admitted by
  the parent, with the PR-3 elastic controller as the scale-down
  primitive;
- :mod:`~fm_spark_tpu.serve.loadgen` — the seeded traffic-replay load
  generator (diurnal ramps, flash crowds, slow clients, retry storms)
  the chaos engine composes with fault plans;
- ``bench_serve.py`` (repo root) — the latency/throughput ladder that
  stamps p50/p99 + QPS/chip into the PR-9 ledger as ``serve_bench``
  records, sentinel-gated exactly like training legs (fleet rungs are
  their own cohorts).
"""

from fm_spark_tpu.serve.engine import (
    DEFAULT_BUCKETS,
    Generation,
    PredictEngine,
    ServeFuture,
)
from fm_spark_tpu.serve.frontdoor import (
    AdmissionController,
    BackendError,
    FrontDoor,
    LocalBackend,
    parse_classes,
)
__all__ = [
    "DEFAULT_BUCKETS",
    "AdmissionController",
    "BackendError",
    "FrontDoor",
    "Generation",
    "LocalBackend",
    "PredictEngine",
    "ReloadFollower",
    "ServeFuture",
    "parse_classes",
]


def __getattr__(name):
    # ReloadFollower comes on first use (PEP 562): serve.reload imports
    # fm_spark_tpu.checkpoint and with it orbax, 13-25 s of a scorer's
    # start-up (PERF.md §5) that a process following no checkpoint
    # chain never needs.
    if name == "ReloadFollower":
        from fm_spark_tpu.serve.reload import ReloadFollower

        globals()[name] = ReloadFollower
        return ReloadFollower
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(__all__))
