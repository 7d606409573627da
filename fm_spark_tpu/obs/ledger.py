"""Perf provenance ledger: append-only JSONL of every measurement.

The telemetry plane's MEMORY (ISSUE 9). PR 7 gave one run a directory;
this module gives every run a row in a durable, machine-readable
history, so "is this number better than last week's" stops being a
PERF.md prose argument ("attachment transient, not a regression") and
becomes a query. Three record kinds share one stream:

- ``bench_leg`` — one sweep leg's measured rate (bench.py appends one
  per completed leg, nulls included: a dead-attachment round records
  ``value: null`` with ``attachment_health: "down"`` instead of
  leaving a gap — the BENCH_r03–r05 lesson);
- ``kernel_pricing`` — one bench_kernels.py row (measured ms + the
  bytes-model GB/s that is the higher-is-better ``value``);
- ``attachment_probe`` — one device-probe outcome, so "attachment
  weather" has a first-class record stream;
- ``serve_bench`` — one bench_serve.py ladder rung (ISSUE 12): QPS/chip
  as the higher-is-better ``value`` with p50/p99 request latency
  alongside. Serving legs carry their own leg names, so their cohorts
  never mix with training legs — the sentinel gates serving
  regressions exactly like training ones, separately;
- ``quality_eval`` — one time-ordered eval day of the continuous-
  learning loop (ISSUE 13; online.py): eval AUC as the
  higher-is-better ``value``, with the day index, global step, and
  full metric dict alongside. Quality legs live in their own
  ``quality/<config>/<optimizer>`` namespace, so model-quality cohorts
  never share a trailing band with any throughput cohort — an AUC
  series judged by the same sentinel machinery, separately;
- ``embed_bench`` — one bench_embed.py ladder rung (ISSUE 16): the
  tiered embedding store's gathered-rows/s as the higher-is-better
  ``value``, with hit rate, eviction count, blocking-stall ms, HBM
  watermark, and host RSS alongside. Tiered legs carry their own
  ``embed_rows_<decade>`` leg names — their cohorts NEVER mix with
  in-HBM training legs, because a tiered rows/s and an in-HBM rows/s
  price different memory hierarchies (PERF.md round 20).

Every record carries a **measurement fingerprint**
(:func:`measurement_fingerprint`): the lever-config hash, chip type +
count, jax/libtpu versions, the degraded / fused_fallback stamps, and
the attachment-health verdict from the supervisor journal. Records
whose fingerprints share a :func:`fingerprint` ``key`` were measured
under comparable conditions — that is the cohort unit the regression
sentinel (:mod:`fm_spark_tpu.obs.sentinel`) classifies over. The
attachment-health verdict is deliberately NOT part of the key: weather
is *evidence* for the sentinel, not a reason to fork the cohort.

Contracts:

- **append-only** — :meth:`PerfLedger.append` only ever appends one
  JSON line; nothing rewrites history (a measurement, once recorded,
  is provenance).
- **jax-free** — importable from the light bench parent process; the
  jax/libtpu version fields are passed in by callers that have a
  backend up.
- **torn-tail tolerant** — :meth:`PerfLedger.records` skips
  unparseable lines (a SIGKILL mid-append must not poison the
  history), same policy as every other obs stream.
- **schema'd** — :meth:`PerfLedger.append` REFUSES records missing
  ``run_id``/``fingerprint``/``kind``/``leg`` (the runtime half of the
  tools/resilience_lint.py leg-record rule): an unattributable number
  is exactly the hand-adjudication this ledger retires.
"""

from __future__ import annotations

import hashlib
import json
import os
import time

from fm_spark_tpu.utils import durable

__all__ = [
    "LEDGER_FILE",
    "PerfLedger",
    "default_ledger_path",
    "fingerprint_key",
    "measurement_fingerprint",
]

#: The ledger lives BESIDE the per-run directories (one history file
#: across runs), not inside them: ``artifacts/obs/ledger.jsonl``.
LEDGER_FILE = "ledger.jsonl"

_REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))

#: Fields every record must carry (the lint-enforced minimum).
REQUIRED_FIELDS = ("kind", "leg", "run_id", "fingerprint")

#: Fingerprint fields that define a comparability cohort. Everything
#: else in the fingerprint (attachment_health above all) is evidence
#: attached to one measurement, not a cohort splitter.
_KEY_FIELDS = ("config_hash", "device_kind", "n_chips", "jax_version",
               "libtpu_version", "degraded", "fused_fallback")


def default_ledger_path(art_dir: str | None = None) -> str:
    """``<artifacts>/obs/ledger.jsonl`` (default: the repo's
    ``artifacts/``) — sibling of the per-run obs directories."""
    art_dir = art_dir or os.path.join(_REPO_ROOT, "artifacts")
    return os.path.join(art_dir, "obs", LEDGER_FILE)


def _stable_hash(obj) -> str:
    return hashlib.sha256(
        json.dumps(obj, sort_keys=True, default=str).encode()
    ).hexdigest()[:12]


def fingerprint_key(fp: dict) -> str:
    """The cohort key: a stable hash over the comparability-defining
    fingerprint fields (see :data:`_KEY_FIELDS`).

    ``chaos`` splits the cohort ONLY when set (ISSUE 10): a leg
    measured under an active fault schedule ran a different program in
    everything but name, so chaos-drill legs form their own cohort and
    can never join — or poison the trailing band of — a real perf
    cohort. Folded in asymmetrically (absent/falsy contributes nothing
    to the hash) so every pre-chaos historical key stays byte-stable.
    """
    src = {k: fp.get(k) for k in _KEY_FIELDS}
    if fp.get("chaos"):
        src["chaos"] = True
    return _stable_hash(src)


def measurement_fingerprint(*, variant: str, model: str | None = None,
                            batch: int | None = None,
                            steps: int | None = None,
                            rank: int | None = None,
                            extra: dict | None = None,
                            device_kind: str | None = None,
                            n_chips: int | None = None,
                            jax_version: str | None = None,
                            libtpu_version: str | None = None,
                            degraded: bool = False,
                            fused_fallback: bool = False,
                            chaos: bool = False,
                            attachment_health: str = "healthy") -> dict:
    """Build one measurement fingerprint.

    ``config_hash`` digests the program identity (variant label +
    model/batch/steps/rank — the same fields the bench's provenance
    stamps protect — plus any caller-supplied ``extra`` shape/dtype
    fields: bench_kernels prices the SAME kernel at different
    width/cap/dtype, and those must be distinct cohorts); the
    environment fields ride alongside, and ``key`` is the cohort key.
    ``attachment_health`` is the supervisor-journal verdict for THIS
    measurement (``healthy | flaky | degraded | down``). ``chaos``
    marks a fault-drill measurement (ISSUE 10) — its own cohort, never
    keep-best eligible.
    """
    ident = {"variant": variant, "model": model, "batch": batch,
             "steps": steps, "rank": rank}
    if extra:
        ident["extra"] = extra
    fp = {
        "config_hash": _stable_hash(ident),
        "variant": variant,
        "device_kind": device_kind,
        "n_chips": n_chips,
        "jax_version": jax_version,
        "libtpu_version": libtpu_version,
        "degraded": bool(degraded),
        "fused_fallback": bool(fused_fallback),
        "chaos": bool(chaos),
        "attachment_health": attachment_health,
    }
    fp["key"] = fingerprint_key(fp)
    return fp


def runtime_versions() -> dict:
    """Best-effort ``{"jax_version", "libtpu_version"}`` from an
    already-imported jax (never imports it — the ledger stays usable
    from the light parent process)."""
    import sys

    out = {"jax_version": None, "libtpu_version": None}
    jax = sys.modules.get("jax")
    if jax is None:
        return out
    out["jax_version"] = getattr(jax, "__version__", None)
    try:
        backend = jax.extend.backend.get_backend()
        out["libtpu_version"] = getattr(backend, "platform_version",
                                        None)
    except Exception:
        pass
    return out


class PerfLedger:
    """Append-only JSONL measurement history (see module docstring)."""

    def __init__(self, path: str | None = None):
        self.path = path or default_ledger_path()

    # ------------------------------------------------------------ write

    def append(self, record: dict) -> dict:
        """Append one record (returns it, ``ts``-stamped). Raises
        ``ValueError`` on a record missing the required provenance
        fields — an unattributable number must fail loudly at the
        call site, not surface as a hole in the history."""
        missing = [k for k in REQUIRED_FIELDS if not record.get(k)]
        if missing:
            raise ValueError(
                f"ledger record missing required field(s) {missing}; "
                f"every measurement needs {REQUIRED_FIELDS}"
            )
        fp = record["fingerprint"]
        if not isinstance(fp, dict) or not fp.get("key"):
            raise ValueError(
                "ledger record fingerprint must be a "
                "measurement_fingerprint() dict (with its cohort 'key')"
            )
        record = dict(record)
        record.setdefault("ts", round(time.time(), 3))
        try:
            os.makedirs(os.path.dirname(os.path.abspath(self.path)),
                        exist_ok=True)
        except OSError:
            pass
        # Observability tier (ISSUE 20): the append is best-effort
        # through the durable seam — a failing disk degrades the
        # history (counted: io.write_failed_total, obs/io_degraded),
        # never the measurement run it narrates.
        durable.append_line_path(self.path, json.dumps(record),
                                 path_class="obs", best_effort=True)
        return record

    # ------------------------------------------------------------- read

    def records(self, kind: str | None = None, leg: str | None = None,
                run_id: str | None = None,
                fingerprint_key: str | None = None) -> list[dict]:
        """All records in APPEND ORDER (the sentinel's history axis),
        optionally filtered. Missing file = empty history; torn or
        malformed lines are skipped."""
        out = []
        try:
            with open(self.path) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        rec = json.loads(line)
                    except json.JSONDecodeError:
                        continue
                    if not isinstance(rec, dict):
                        continue
                    if kind is not None and rec.get("kind") != kind:
                        continue
                    if leg is not None and rec.get("leg") != leg:
                        continue
                    if run_id is not None and rec.get("run_id") != run_id:
                        continue
                    if fingerprint_key is not None and (
                            (rec.get("fingerprint") or {}).get("key")
                            != fingerprint_key):
                        continue
                    out.append(rec)
        except OSError:
            pass
        return out

    def cohort(self, leg: str, fingerprint_key: str) -> list[dict]:
        """The exact comparability cohort: same leg, same fingerprint
        key, append-ordered."""
        return self.records(leg=leg, fingerprint_key=fingerprint_key)
