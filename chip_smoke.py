#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once at the full width of config 3
(``criteo1tb_fm_r64``: rank 64, 39 x 262,144 buckets, batch 131,072),
through the entry points a user calls — ``fm_spark_tpu.cli.main([...])``
in-process, the same code ``python -m fm_spark_tpu.cli`` runs, and
``PredictEngine`` — with random weights made from a seed:

  device         a TPU, or exit != 0 before any work (JAX_PLATFORMS=cpu too)
  train_default  cli train, registry defaults (fp32, XLA gather/scatter)
  train_winner   cli train, the registry's recorded-winner flags (bf16,
                 compact, gfull, the Pallas segment totals)
  kernels        every registered Pallas kernel COMPILED at the widths of
                 the config that would use it, against its jax.numpy
                 reference; a refusal passes only if the kernel's own
                 probe predicted it
  score          cli serve on the saved model, then PredictEngine scores
                 against the FM formula in float32 NumPy
  cache          compile-cache requests / hits / misses (run the smoke
                 twice in one command: the second run shows zero misses)

One process, no children: a chip belongs to one process at a time.
Everything it writes goes under ``.chip_smoke_out/`` beside this file.
Stdout carries one JSON line per phase and, last, the verdict
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
the entry points' own output goes to stderr. Exit 0 only if every phase
passed.
"""

import contextlib
import importlib.metadata
import io
import json
import math
import os
import resource
import shutil
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, ".chip_smoke_out")
MODEL = os.path.join(OUT, "model")
CONFIG = "criteo1tb_fm_r64"
STEPS = 8
# Config 3 (configs/__init__.py) and config 4 (avazu_ffm_r16) widths.
B3, W3, CAP3, BUCKET3 = 131_072, 65, 16_384, 262_144
B4, F4, K4 = 8_192, 23, 16
# Written tolerances, as max|got - want| / max|want|: fp32 paths hold
# fp32 (bf16 where fp32 is declared fails by two orders of magnitude);
# bf16 storage/compute carries 8 bits of mantissa through a few ops.
TOL = {"float32": 1e-5, "bfloat16": 2e-2}
# Serving: |score - reference| <= 1e-4 relative (fp32 parameters).
SCORE_TOL = 1e-4


class SmokeFailure(Exception):
    """A check did not hold."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class _Tee(io.TextIOBase):
    """Collects what an entry point prints and passes it on to stderr."""

    def __init__(self):
        self.text = []

    def write(self, s):
        self.text.append(s)
        sys.stderr.write(s)
        return len(s)

    def flush(self):
        sys.stderr.flush()


def run_cli(argv) -> list[dict]:
    """``fm_spark_tpu.cli.main(argv)`` in-process; returns the JSON
    objects it printed, in order."""
    from fm_spark_tpu import cli

    tee = _Tee()
    print(f"chip_smoke: cli {' '.join(argv)}", file=sys.stderr, flush=True)
    with contextlib.redirect_stdout(tee):
        rc = cli.main(argv)
    check(rc == 0, f"cli {argv[0]} returned {rc}")
    docs = []
    for line in "".join(tee.text).splitlines():
        if line.startswith("{"):
            docs.append(json.loads(line))
    return docs


def first(docs, key):
    found = [d[key] for d in docs if key in d]
    check(found, f"the run printed no {key!r} line")
    return found[0]


# ------------------------------------------------------------------ phases


def phase_device(ctx) -> dict:
    import jax
    import jaxlib

    from fm_spark_tpu import native
    from fm_spark_tpu.models.io import MAX_FILE_BYTES
    from fm_spark_tpu.utils import compile_cache
    from fm_spark_tpu.utils import device as device_lib

    try:
        dev = device_lib.describe()
    except RuntimeError as e:   # no backend JAX is allowed to use came up
        sys.exit(f"chip_smoke: no TPU — {e}; nothing was run")
    if dev["platform"] != "tpu":
        sys.exit(f"chip_smoke: no TPU — jax reports {dev}; nothing was run")
    ctx["device"] = dev
    check(native.available(),
          f"native library unavailable: {native.build_error()}")
    # The driver's chip machine limits file size (one 2.7 GB params.npz
    # was refused there); the saved model is cut to MAX_FILE_BYTES files.
    fsize = resource.getrlimit(resource.RLIMIT_FSIZE)[0]
    check(fsize == resource.RLIM_INFINITY or fsize >= MAX_FILE_BYTES,
          f"file size limit {fsize} B is under the {MAX_FILE_BYTES} B "
          "of one saved-model file")
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)
    return {
        **dev,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": importlib.metadata.version("libtpu"),
        "compile_cache": compile_cache.enable(),
        "native": native.lib_path(),
        "file_size_limit": None if fsize == resource.RLIM_INFINITY else fsize,
    }


def _train(ctx, extra, model_out=None) -> dict:
    argv = ["train", "--config", CONFIG, "--synthetic", "524288",
            "--steps", str(STEPS), "--test-fraction", "0",
            "--log-every", "1", "--obs-dir", os.path.join(OUT, "obs"),
            *extra]
    if model_out:
        argv += ["--model-out", model_out]
    docs = run_cli(argv)
    dev = first(docs, "device")
    check(dev == ctx["device"],
          f"cli train names device {dev}, the smoke found {ctx['device']}")
    losses = [d["loss"] for d in docs if "step" in d and "loss" in d]
    check(len(losses) == STEPS, f"{len(losses)} loss lines for {STEPS} steps")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall over {STEPS} steps: {losses}")
    placed = first(docs, "placement")
    check(placed["platforms"] == ["tpu"],
          f"params live on {placed['platforms']}, not on the TPU")
    n = ctx["device"]["count"]
    fields = placed["fields_per_device"]
    want = -(-39 // n)          # padded fields per chip: 10 of 40 on four
    check(len(fields) == n and set(fields.values()) == {want},
          f"each of {n} chips should hold {want} field slots: {fields}")
    if n == 1:
        # The one-chip loop holds its tables row-major (lane-padded):
        # any other layout is two whole-table copies a table a step.
        check(placed["table_layouts"] == [[0, 1]],
              f"tables are not row-major on the chip: "
              f"{placed['table_layouts']}")
    return {
        "argv": argv[1:],
        "losses": losses,
        "fields_per_device": fields,
        "param_bytes_per_device": placed["param_bytes_per_device"],
        "table_layouts": placed["table_layouts"],
        "table_device_bytes": placed["table_device_bytes"],
        "memory_after_placement": placed["memory"],
        "memory_after_last_step": first(docs, "memory_after_fit"),
    }


def phase_train_default(ctx) -> dict:
    return _train(ctx, [], model_out=MODEL)


def phase_train_winner(ctx) -> dict:
    """The recipe the registry itself states for config 3."""
    from fm_spark_tpu.ops import pallas_interpret

    aux = (["--host-dedup"] if ctx["device"]["count"] == 1 else
           ["--compact-device", "--collective-dtype", "bfloat16",
            "--score-sharded"])
    out = _train(ctx, ["--param-dtype", "bfloat16",
                       "--compute-dtype", "bfloat16",
                       "--sparse-update", "dedup_sr", *aux,
                       "--compact-cap", str(CAP3),
                       "--gfull-fused", "--segtotal-pallas"])
    check(not pallas_interpret(),
          "segment_totals ran interpreted, not compiled")
    return {**out, "pallas_interpret": False}


def _kernel_cases():
    """``name -> [(label, probe, run)]`` for every registered kernel.
    ``probe()`` is the kernel's own build-time check (None = it expects
    to compile); ``run()`` executes it COMPILED and returns ``(got,
    want, dtype)`` against a plain jax.numpy reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fm_spark_tpu.ops import (
        PallasUnavailable,
        pallas_fm,
        pallas_fused,
        pallas_segsum,
    )

    rng = np.random.default_rng(0)
    f32 = jnp.float32

    def normal(shape, dtype=f32, scale=1.0):
        return jnp.asarray(rng.normal(size=shape) * scale, f32).astype(dtype)

    # Non-decreasing DENSE ranks, as both compact-aux builders emit.
    seg = jnp.asarray(np.unique(np.sort(rng.integers(0, CAP3, B3)),
                                return_inverse=True)[1], jnp.int32)
    ids3 = jnp.asarray(rng.integers(0, BUCKET3, B3), jnp.int32)

    def own_check(fn, *args):
        """Probe for kernels whose check is a raising guard."""
        def probe():
            try:
                fn(*args)
            except PallasUnavailable as e:
                return str(e)
            return None
        return probe

    def gather_case(dtype):
        table = normal((BUCKET3, W3), dtype)
        return (f"config3/{dtype}",
                own_check(pallas_fm._require_compilable, W3, B3, False,
                          "gather_rows"),
                lambda: (pallas_fm.gather_rows(table, ids3,
                                               interpret=False),
                         table[ids3], dtype))

    def update_case(dtype):
        table = normal((BUCKET3, W3), dtype)
        uids = jnp.asarray(rng.permutation(BUCKET3)[:B3], jnp.int32)
        valid = jnp.ones((B3,), jnp.int32)
        delta = normal((B3, W3))
        return (f"config3/{dtype}",
                own_check(pallas_fm._require_compilable, W3, 2 * B3, False,
                          "update_rows_add"),
                lambda: (pallas_fm.update_rows_add(
                             jnp.copy(table), uids, valid, delta,
                             interpret=False),
                         table.at[uids].add(delta.astype(dtype)), dtype))

    def segtotal_case():
        sdelta = normal((B3, W3))
        return ("config3/float32",
                lambda: None,   # must compile: phase 2 runs on it
                lambda: (pallas_segsum.segment_totals(
                             sdelta, seg, CAP3, interpret=False),
                         jax.ops.segment_sum(sdelta, seg,
                                             num_segments=CAP3),
                         "float32"))

    def fwd_case(dtype):
        tables = [normal((BUCKET3, W3), dtype, 0.1) for _ in range(3)]
        ids = jnp.asarray(rng.integers(0, BUCKET3, (B3, 3)), jnp.int32)
        vals = jnp.ones((B3, 3), f32)

        def ref():
            rows = [t[ids[:, f]].astype(f32) for f, t in enumerate(tables)]
            xv = jnp.stack([r[:, :W3 - 1] for r in rows], axis=1)
            s = jnp.sum(xv, axis=1)
            return (0.5 * (jnp.sum(s * s, axis=1)
                           - jnp.sum(xv * xv, axis=(1, 2)))
                    + sum(r[:, W3 - 1] for r in rows))

        return (f"config3/{dtype}",
                lambda: pallas_fused.fm_fwd_supported(B3, W3),
                lambda: (pallas_fused.fm_fused_scores(
                             tables, ids, vals, interpret=False)[0],
                         ref(), dtype))

    def bwd_case(dtype):
        k = W3 - 1
        urows = normal((CAP3, W3), dtype, 0.1)
        s1s = normal((B3, W3), dtype)
        ds, x = normal((B3,), dtype), normal((B3,), dtype)
        tch = jnp.ones((B3,), dtype)
        rv = jnp.asarray([1e-4] * k + [1e-5], dtype)
        neg_lr = f32(-0.05)

        def ref():
            rows = urows[seg].astype(dtype)
            xv = rows * x[:, None]
            base = ds[:, None] * (
                s1s - jnp.where(jnp.arange(W3) < k, xv, 0))
            g = base * x[:, None] + rv * rows * tch[:, None]
            return jax.ops.segment_sum((neg_lr * g).astype(f32), seg,
                                       num_segments=CAP3)

        return (f"config3/{dtype}",
                lambda: pallas_fused.fm_bwd_supported(
                    CAP3, W3, jnp.dtype(dtype).itemsize),
                lambda: (pallas_fused.fm_bwd_segment_totals(
                             urows, s1s, ds, x, tch, seg, neg_lr, rv,
                             k=k, cap=CAP3, interpret=False),
                         ref(), dtype))

    def ffm_inputs(dtype):
        rstk = normal((B4, F4, F4 * K4), dtype, 0.1)
        vals = jnp.asarray(rng.uniform(0.5, 1.5, (B4, F4)), dtype)
        sel = rstk.reshape(B4, F4, F4, K4) * vals[:, :, None, None]
        probe = lambda: pallas_fused.ffm_sel_supported(     # noqa: E731
            F4, K4, jnp.dtype(dtype).itemsize)
        return rstk, vals, sel, probe

    def ffm_fwd_case(dtype):
        rstk, vals, sel, probe = ffm_inputs(dtype)

        def ref():
            prod = jnp.sum(sel * jnp.swapaxes(sel, 1, 2), axis=-1)
            return (jnp.sum(prod, axis=(1, 2))
                    - jnp.trace(prod, axis1=1, axis2=2))

        return (f"avazu/{dtype}", probe,
                lambda: (pallas_fused.ffm_sel_scores(
                             rstk, vals, interpret=False),
                         ref(), dtype))

    def ffm_bwd_case(dtype):
        rstk, vals, sel, probe = ffm_inputs(dtype)
        ds = normal((B4,), dtype)

        def ref():
            dv = (ds[:, None, None, None] * jnp.swapaxes(sel, 1, 2)
                  * vals[:, :, None, None])
            off = 1 - jnp.eye(F4, dtype=dtype)
            return (dv * off[None, :, :, None]).reshape(B4, F4, F4 * K4)

        return (f"avazu/{dtype}", probe,
                lambda: (pallas_fused.ffm_sel_bwd(
                             rstk, vals, ds, interpret=False),
                         ref(), dtype))

    both = ("float32", "bfloat16")
    return {
        "pallas_fm.gather_rows": [gather_case(d) for d in both],
        "pallas_fm.update_rows_add": [update_case(d) for d in both],
        "pallas_segsum.segment_totals": [segtotal_case()],
        "pallas_fused.fm_fused_scores": [fwd_case(d) for d in both],
        "pallas_fused.fm_bwd_segment_totals": [bwd_case(d) for d in both],
        "pallas_fused.ffm_sel_scores": [ffm_fwd_case(d) for d in both],
        "pallas_fused.ffm_sel_bwd": [ffm_bwd_case(d) for d in both],
    }


def phase_kernels(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from fm_spark_tpu.ops import pallas_fused

    cases = _kernel_cases()
    check(set(cases) == set(pallas_fused.interpret_smokes()),
          "kernel cases do not cover the registry: "
          f"{set(cases) ^ set(pallas_fused.interpret_smokes())}")
    table, bad = [], []
    for name, rows in cases.items():
        for label, probe, run in rows:
            predicted = probe()
            row = {"kernel": name, "case": label}
            t0 = time.perf_counter()
            try:
                got, want, dtype = run()
                jax.block_until_ready(got)
            except Exception as e:  # noqa: BLE001 — the refusal IS the datum
                # Classified, never passed by itself: a refusal counts
                # only when the kernel's own probe saw it coming.
                words = " ".join(f"{type(e).__name__}: {e}".split())
                row.update(verdict="refused", predicted_by_probe=predicted,
                           compiler=words[:600])
                if predicted is None:
                    traceback.print_exc()
                    bad.append(f"{name} [{label}]: the probe said yes, "
                               f"the compiler said no — {words[:300]}")
            else:
                got = jnp.asarray(got, jnp.float32)
                want = jnp.asarray(want, jnp.float32)
                check(got.shape == want.shape,
                      f"{name} [{label}]: shape {got.shape} != {want.shape}")
                err = float(jnp.max(jnp.abs(got - want))
                            / jnp.max(jnp.abs(want)))
                row.update(verdict="ok", rel_err=err, tol=TOL[dtype],
                           seconds=round(time.perf_counter() - t0, 2))
                if not err <= TOL[dtype]:   # also catches NaN
                    bad.append(f"{name} [{label}]: rel err {err:.3g} over "
                               f"the written {TOL[dtype]:g}")
                if predicted is not None:
                    bad.append(f"{name} [{label}]: compiled, yet its probe "
                               f"refuses it — {predicted}")
            table.append(row)
            print(f"chip_smoke: {json.dumps(row)}", file=sys.stderr,
                  flush=True)
    ctx["kernels"] = table
    check(not bad, "; ".join(bad))
    return {"kernels": table}


def _reference_scores(ids, vals):
    """The FM formula in float32 NumPy on the SAVED parameters:
    ``w0 + Σ_f w_f x_f + ½ Σ_k [(Σ_f v_fk x_f)² − Σ_f (v_fk x_f)²]``."""
    import numpy as np

    from fm_spark_tpu.models.io import load_array

    k = W3 - 1
    s = np.zeros((ids.shape[0], k), np.float32)
    ssq = np.zeros((ids.shape[0],), np.float32)
    lin = np.zeros((ids.shape[0],), np.float32)
    for f in range(ids.shape[1]):
        rows = load_array(MODEL, f"vw/{f}")[ids[:, f]].astype(np.float32)
        xv = rows[:, :k] * vals[:, f:f + 1]
        s += xv
        ssq += np.sum(xv * xv, axis=1)
        lin += rows[:, k] * vals[:, f]
    return (load_array(MODEL, "w0").astype(np.float32) + lin
            + np.float32(0.5) * (np.sum(s * s, axis=1) - ssq))


def phase_score(ctx) -> dict:
    import numpy as np

    from fm_spark_tpu import data as data_lib
    from fm_spark_tpu import models
    from fm_spark_tpu.data.packed import field_local
    from fm_spark_tpu.serve import PredictEngine

    preds_path = os.path.join(OUT, "preds.txt")
    docs = run_cli(["serve", "--model", MODEL, "--synthetic", "4096",
                    "--batch-size", "64", "--buckets", "1,64,512",
                    "--max-requests", "64", "--out", preds_path,
                    "--obs-dir", os.path.join(OUT, "obs")])
    check(first(docs, "device") == ctx["device"],
          "cli serve names another device than the smoke found")
    serving = [d for d in docs if d.get("serving")][0]
    summary = first(docs, "serve_summary")
    check(summary["served_requests"] == 64 and summary["served_rows"] == 4096,
          f"served {summary['served_requests']} requests / "
          f"{summary['served_rows']} rows, wanted 64 / 4096")
    check(not summary["degraded"], "serving ended degraded")

    # The request stream cli serve answered (cli._batches_for_model).
    spec, params = models.load_model(MODEL)
    ids, vals, _ = data_lib.synthetic_ctr(4096, spec.num_features,
                                          spec.num_fields, seed=1)
    ids = field_local(ids, spec.bucket)
    want = _reference_scores(ids, vals)
    want_p = 1.0 / (1.0 + np.exp(-want.astype(np.float64)))
    served = np.loadtxt(preds_path)
    check(served.shape == (4096,) and np.all(np.isfinite(served)),
          f"cli serve wrote {served.shape} predictions, not 4096 finite")
    # --out prints 6 significant digits; the sharp check is below.
    cli_err = float(np.max(np.abs(served - want_p) / want_p))
    check(cli_err <= SCORE_TOL,
          f"cli serve predictions off by {cli_err:.3g} relative")

    # One 512-row batch through PredictEngine, compared as SCORES: the
    # logit of an fp32 probability resolves the score to ~3e-7, where
    # comparing probabilities near 0.5 would hide a 4x larger error.
    engine = PredictEngine(spec, params, buckets=(1, 64, 512),
                           latency_budget_ms=0.0)
    try:
        warm = engine.warmup()
        p = np.asarray(engine.score(ids[:512], vals[:512]), np.float64)
    finally:
        engine.close()
    check(p.shape == (512,) and np.all(np.isfinite(p)),
          f"PredictEngine returned {p.shape}, not 512 finite scores")
    got = np.log(p / (1.0 - p))
    scale = float(np.max(np.abs(want[:512])))
    err = float(np.max(np.abs(got - want[:512]))) / scale
    check(err <= SCORE_TOL,
          f"PredictEngine scores off by {err:.3g} relative to the float32 "
          f"NumPy FM (tolerance {SCORE_TOL:g})")
    return {
        "served_requests": summary["served_requests"],
        "request_ms": summary["request_ms"],
        "warmup_s": serving["warmup_s"],
        "fresh_compiles": serving["fresh_compiles"],
        "engine_fresh_compiles": warm["fresh_compiles"],
        "cli_pred_rel_err": cli_err,
        "score_rel_err": err,
        "score_scale": scale,
        "tol": SCORE_TOL,
    }


def phase_cache(ctx) -> dict:
    from fm_spark_tpu.utils import compile_cache

    stats = compile_cache.cache_stats()
    check(stats["enabled"] and stats["requests"] > 0,
          f"the compile cache never saw a request: {stats}")
    check(stats["entries"] > 0, f"nothing was cached under {stats['dir']}")
    return stats


PHASES = [
    ("device", phase_device),
    ("train_default", phase_train_default),
    ("train_winner", phase_train_winner),
    ("kernels", phase_kernels),
    ("score", phase_score),
    ("cache", phase_cache),
]


def main() -> int:
    # Before anything touches the chip: is the rest of the repo here?
    import fm_spark_tpu  # noqa: F401

    ctx: dict = {}
    ok = True
    for name, fn in PHASES:
        t0 = time.perf_counter()
        try:
            line = {"ok": True, **fn(ctx)}
        except Exception as e:  # noqa: BLE001 — reported as ok: false
            traceback.print_exc()
            line = {"ok": False, "error": f"{type(e).__name__}: {e}"[:4000]}
        print(json.dumps({"phase": name, **line,
                          "seconds": round(time.perf_counter() - t0, 2)}),
              flush=True)
        if not line["ok"]:
            ok = False
            break    # later phases build on this one's output
    shutil.rmtree(MODEL, ignore_errors=True)     # 2.7 GB of random weights
    print(json.dumps({"ok": ok, "device": ctx.get("device")}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
