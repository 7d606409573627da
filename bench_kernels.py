"""Per-kernel pricing harness: measured time vs a bytes-moved model for
every Pallas kernel family on the FieldFM/FFM hot-path shapes (ISSUE 8).

Families priced (ops/pallas_fm.py, pallas_segsum.py, pallas_fused.py):

  gather            XLA take vs the pipelined-DMA row gather
  update            XLA scatter-add / dedup vs the Pallas unique-row RMW
  segsum            Pallas sorted-run segment totals vs the blocked prefix
  fused_fwd         fused gather→FM-interaction forward (fm_fused_scores)
  fused_bwd         fused g_full + segment-totals backward
                    (fm_bwd_segment_totals) vs the gfull+reorder+segtotal
                    reference composition it subsumes
  ffm_sel           sel-blocked FFM interaction fwd/bwd (ffm_sel_scores /
                    ffm_sel_bwd) vs the XLA sel-blocked loop

Each row carries a BYTES-MOVED MODEL — the kernel's designed HBM
traffic at that shape — next to the measured time, so the report says
not just "X is faster" but "X moves the bytes its design claims" (a
kernel near the attachment's streaming bandwidth is done; one far from
it has a dispatch/overlap problem, not a traffic problem).

Run on a real TPU for decision-grade numbers:

    python bench_kernels.py [--rows 262144] [--width 65] [--batch 131072]
                            [--cap 12288] [--dtype float32|bfloat16]

On CPU (JAX_PLATFORMS=cpu) the kernels run in INTERPRET mode: timings
are emulation overhead, meaningless for the XLA-vs-Pallas decision, but
the bytes-moved models, shapes, and plumbing are identical — that is
the CI/smoke mode (--interpret-ok, or implied by a cpu backend), and
what keeps the harness runnable between chip windows.

Output: one JSON line per kernel on stdout, and the full report at
``artifacts/obs/<run_id>/kernel_pricing.json`` (the PR-7 obs run-dir
convention; --report-dir overrides, 'none' disables).
"""

import argparse
import json
import os
import sys
import time


def _bytes(*terms) -> int:
    """Sum of (count, itemsize) traffic terms, in bytes."""
    return int(sum(c * i for c, i in terms))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=262_144)
    ap.add_argument("--width", type=int, default=65,
                    help="FM table width k+1 (fused-linear layout)")
    ap.add_argument("--batch", type=int, default=131_072)
    ap.add_argument("--cap", type=int, default=12_288,
                    help="compact capacity for the segsum/fused_bwd "
                         "families (the measured floor cap)")
    ap.add_argument("--ffm-fields", type=int, default=23, dest="ffm_fields")
    ap.add_argument("--ffm-rank", type=int, default=16, dest="ffm_rank")
    ap.add_argument("--ffm-batch", type=int, default=8192, dest="ffm_batch",
                    help="batch for the ffm_sel rows (the [B, F, F·k] "
                         "operand is ~45x an FM row set)")
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--interpret-ok", action="store_true",
                    dest="interpret_ok",
                    help="proceed on a non-TPU backend (interpret-mode "
                         "smoke: plumbing + bytes models only, timings "
                         "are emulation overhead)")
    ap.add_argument("--scale", type=float, default=None,
                    help="shrink every shape by this divisor (smoke "
                         "runs: --scale 64 prices the plumbing in "
                         "seconds)")
    ap.add_argument("--families", default=None,
                    help="comma-separated subset of: gather,update,"
                         "segsum,fused_fwd,fused_bwd,ffm_sel")
    ap.add_argument("--report-dir", default=None, dest="report_dir",
                    help="directory for kernel_pricing.json (default: "
                         "artifacts/obs/<run_id>/; 'none' disables)")
    args = ap.parse_args()

    if args.scale:
        s = args.scale
        args.rows = max(1024, int(args.rows / s))
        args.batch = max(1024, int(args.batch / s))
        args.cap = max(512, int(args.cap / s))
        args.ffm_batch = max(256, int(args.ffm_batch / s))

    import jax

    from fm_spark_tpu.ops import pallas_interpret

    on_cpu = os.environ.get("JAX_PLATFORMS", "").strip() == "cpu"
    backend = jax.default_backend()
    interpret = pallas_interpret()
    if interpret and not (on_cpu or args.interpret_ok):
        raise SystemExit(
            "bench_kernels needs the real TPU for decision-grade "
            "numbers; pass --interpret-ok (or JAX_PLATFORMS=cpu) for "
            "the interpret-mode smoke"
        )
    if interpret:
        print("bench_kernels: INTERPRET mode — timings are emulation "
              "overhead, bytes models are real", file=sys.stderr)

    import jax.numpy as jnp
    import numpy as np

    from fm_spark_tpu.ops import pallas_fm, pallas_fused, pallas_segsum
    from fm_spark_tpu.ops.scatter import apply_row_updates

    dtype = jnp.dtype(args.dtype)
    isz = dtype.itemsize
    cd = jnp.float32  # compute dtype for the fused families
    rng = np.random.default_rng(0)
    w = args.width
    k = w - 1
    B = args.batch
    cap = min(args.cap, B)

    table = jnp.asarray(rng.normal(size=(args.rows, w)) * 0.01, dtype)
    # Zipf-skewed ids like real CTR traffic.
    ids = jnp.asarray(rng.zipf(1.3, size=B) % args.rows, jnp.int32)
    delta = jnp.asarray(rng.normal(size=(B, w)) * 1e-3, jnp.float32)

    rows_out = []

    def _fence(out):
        np.asarray(jax.tree_util.tree_leaves(out)[0].ravel()[0])

    def timed(name, family, fn, model_bytes, threaded=None, note=None,
              **shape):
        """Time fn; ``threaded`` names the first arg, re-fed from the
        output each iteration (required for donated/aliased tables).
        ``model_bytes`` is the kernel's designed HBM traffic at this
        shape — the pricing denominator. A kernel that cannot serve
        this (backend, shape) — on-chip lane/SMEM limits the
        interpret smoke never hits — prices as a SKIPPED row, so one
        unservable family can never kill the report (the fused_bwd
        decision numbers are the whole point of the TPU run)."""
        from fm_spark_tpu.ops import PallasUnavailable

        state = threaded
        try:
            out = fn(state) if state is not None else fn()
        except PallasUnavailable as e:
            row = {"kernel": name, "family": family,
                   "skipped": str(e)[:200], "backend": backend, **shape}
            rows_out.append(row)
            print(json.dumps(row), flush=True)
            return None
        _fence(out)
        if state is not None:
            state = out
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = fn(state) if state is not None else fn()
            if state is not None:
                state = out
        _fence(out)
        dt = (time.perf_counter() - t0) / args.iters
        row = {
            "kernel": name, "family": family,
            "ms": round(dt * 1e3, 3),
            "bytes_moved_model": model_bytes,
            "model_gbps": round(model_bytes / dt / 1e9, 2),
            "dtype": args.dtype, "backend": backend,
            "interpret": interpret, **shape,
        }
        if note:
            row["note"] = note
        rows_out.append(row)
        print(json.dumps(row), flush=True)
        return out

    fams = (set(args.families.split(",")) if args.families
            else {"gather", "update", "segsum", "fused_fwd", "fused_bwd",
                  "ffm_sel"})

    # ---- gather: XLA take vs pipelined-DMA row gather ------------------
    if "gather" in fams:
        g_model = _bytes((B * w, isz), (B * w, isz), (B, 4))
        gather_xla = jax.jit(lambda t, i: t[i])
        timed("gather_xla", "gather",
              lambda: gather_xla(table, ids), g_model, batch=B, width=w)
        timed("gather_pallas", "gather",
              lambda: pallas_fm.gather_rows(table, ids,
                                            interpret=interpret),
              g_model, batch=B, width=w)

    # ---- update: XLA scatter/dedup vs Pallas unique-row RMW ------------
    if "update" in fams:
        u_model = _bytes((B * w, isz), (B * w, isz), (B * w, 4), (B, 4))
        scatter_xla = jax.jit(
            lambda t, i, d: t.at[i].add(d.astype(t.dtype)))
        timed("scatter_add_xla", "update",
              lambda t: scatter_xla(t, ids, delta), u_model,
              threaded=jnp.copy(table), batch=B, width=w)
        dedup_xla = jax.jit(
            lambda t, i, d: apply_row_updates(t, i, d, mode="dedup"))
        timed("scatter_dedup_xla", "update",
              lambda t: dedup_xla(t, ids, delta), u_model,
              threaded=jnp.copy(table), batch=B, width=w)
        # Pallas RMW needs unique valid lanes: segment-sum dedup outside
        # the timed region, exactly as the fused step would feed it (the
        # sort+segment XLA ops are timed separately in scatter_dedup_xla).
        from fm_spark_tpu.ops.scatter import _dedup

        sid, summed, run_start, _order = jax.jit(_dedup)(ids, delta)
        uids = jnp.where(run_start, sid, 0)
        valid = run_start.astype(jnp.int32)
        n_unique = int(jnp.sum(run_start))
        timed("update_pallas_unique", "update",
              lambda t: pallas_fm.update_rows_add(t, uids, valid, summed,
                                                  interpret=interpret),
              _bytes((2 * n_unique * w, isz), (B * w, 4), (2 * B, 4)),
              threaded=jnp.copy(table), batch=B, width=w,
              note=f"{n_unique} unique ids "
                   f"({n_unique / B:.3f} of batch)")

    # ---- segsum: blocked prefix vs Pallas sorted-run totals ------------
    seg = jnp.asarray(
        np.sort(rng.integers(0, cap, size=B)).astype(np.int32))
    sdelta = jnp.asarray(rng.normal(size=(B, w)) * 1e-3, jnp.float32)
    if "segsum" in fams:
        # Pallas design traffic: one streaming read + the [cap, w] write.
        timed("segtotal_pallas", "segsum",
              lambda: pallas_segsum.segment_totals(sdelta, seg, cap,
                                                   interpret=interpret),
              _bytes((B * w, 4), (B, 4), (cap * w, 4)),
              batch=B, width=w, cap=cap)
        # The blocked prefix it replaces: read + full prefix write+read.
        blk = 512

        @jax.jit
        def prefix_ref(sd):
            nb = sd.shape[0] // blk
            bl = jnp.cumsum(sd.reshape(nb, blk, w), axis=1)
            off = jnp.cumsum(bl[:, -1, :], axis=0)
            return bl, off

        pad = (-B) % blk
        sd_pad = jnp.pad(sdelta, ((0, pad), (0, 0))) if pad else sdelta
        timed("segtotal_prefix_xla", "segsum",
              lambda: prefix_ref(sd_pad),
              _bytes((B * w, 4), (2 * B * w, 4)),
              batch=B, width=w, cap=cap,
              note="prefix build only (boundary gathers excluded)")

    # ---- fused_fwd: gather→FM-interaction forward ----------------------
    if "fused_fwd" in fams:
        F_fm = 8  # per-field slice of the batch's tables
        ftabs = [table for _ in range(F_fm)]
        fids = jnp.stack([ids for _ in range(F_fm)], axis=1)
        fvals = jnp.asarray(rng.uniform(0.5, 1.5, (B, F_fm)), jnp.float32)
        # Per field: read B rows via DMA + RW the [B, w+1] accumulator.
        ffwd_model = _bytes((F_fm * B * w, isz),
                            (F_fm * 2 * B * (w + 1), 4), (F_fm * B, 4))
        timed("fm_fused_fwd_pallas", "fused_fwd",
              lambda: pallas_fused.fm_fused_scores(
                  ftabs, fids, fvals, interpret=interpret)[0],
              ffwd_model, batch=B, width=w, fields=F_fm)

        @jax.jit
        def fwd_xla(tabs, fi, fv):
            rows = [tabs[f][fi[:, f]].astype(cd) for f in range(F_fm)]
            xvs = [r[:, :k] * fv[:, f:f + 1]
                   for f, r in enumerate(rows)]
            s = sum(xvs)
            ssq = sum(jnp.sum(x * x, axis=1) for x in xvs)
            sc = 0.5 * (jnp.sum(s * s, axis=1) - ssq)
            return sc + sum(r[:, k] * fv[:, f]
                            for f, r in enumerate(rows))

        # XLA reference traffic: gather write+read of every field's rows.
        timed("fm_fwd_xla", "fused_fwd",
              lambda: fwd_xla(ftabs, fids, fvals),
              _bytes((F_fm * B * w, isz), (2 * F_fm * B * w, 4),
                     (F_fm * B, 4)),
              batch=B, width=w, fields=F_fm)

    # ---- fused_bwd: on-chip g_full + totals vs the reference chain -----
    if "fused_bwd" in fams:
        from fm_spark_tpu.ops import pallas_fused as pf

        reason = pf.fm_bwd_supported(cap, w, isz)
        if reason:
            # Pre-check skips land in rows_out too: an unservable
            # family must price as a null ledger record, not a gap.
            row = {"kernel": "fm_bwd_segment_totals",
                   "family": "fused_bwd", "skipped": reason,
                   "backend": backend}
            rows_out.append(row)
            print(json.dumps(row), flush=True)
        else:
            urows = jnp.asarray(rng.normal(size=(cap, w)) * 0.01, dtype)
            s1s = jnp.asarray(rng.normal(size=(B, w)), cd)
            lane = jnp.asarray(rng.normal(size=B), cd)
            tch = jnp.ones((B,), cd)
            rv = jnp.asarray([1e-4] * k + [1e-5], cd)
            # Design traffic: the sorted s1 rows + 4 scalar streams +
            # the resident urows/totals pair — the F × [B, w] gradient
            # set does NOT appear.
            fbwd_model = _bytes((B * w, 4), (4 * B, 4),
                                (cap * w, isz), (cap * w, 4))
            timed("fm_bwd_fused_pallas", "fused_bwd",
                  lambda: pf.fm_bwd_segment_totals(
                      urows, s1s, lane, lane, tch, seg,
                      jnp.float32(-0.05), rv, k=k, cap=cap,
                      interpret=interpret),
                  fbwd_model, batch=B, width=w, cap=cap)

            # Reference composition (what the kernel subsumes): build
            # g_full (gfull_fused form), reorder, segment-total. Its
            # traffic ≈ expand-read + g_full write+read + sdelta
            # write+read + totals write: ~5·B·w.
            @jax.jit
            def ref_chain(ur, s1, ds, x, tc):
                rows = ur[jnp.minimum(seg, cap - 1)].astype(cd)
                colmask = jnp.arange(w) < k
                xv = rows * x[:, None]
                base = ds[:, None] * (
                    s1 - jnp.where(colmask, xv, 0.0))
                g = base * x[:, None] + rv * rows * tc[:, None]
                return pallas_segsum.segment_totals(
                    (-0.05 * g).astype(jnp.float32), seg, cap,
                    interpret=interpret)

            timed("fm_bwd_reference_chain", "fused_bwd",
                  lambda: ref_chain(urows, s1s, lane, lane, tch),
                  _bytes((5 * B * w, 4), (cap * w, isz + 4), (B, 4)),
                  batch=B, width=w, cap=cap,
                  note="gfull expand + segtotal composition "
                       "(the subsumed path)")

    # ---- ffm_sel: tile-resident sel/dsel vs the XLA blocked loop -------
    if "ffm_sel" in fams:
        Ff, kf, Bf = args.ffm_fields, args.ffm_rank, args.ffm_batch
        reason = pallas_fused.ffm_sel_supported(Ff, kf, 4)
        if reason:
            row = {"kernel": "ffm_sel", "family": "ffm_sel",
                   "skipped": reason, "backend": backend}
            rows_out.append(row)
            print(json.dumps(row), flush=True)
        else:
            rstk = jnp.asarray(
                rng.normal(size=(Bf, Ff, Ff * kf)) * 0.01, jnp.float32)
            fv = jnp.asarray(rng.uniform(0.5, 1.5, (Bf, Ff)), jnp.float32)
            ds = jnp.asarray(rng.normal(size=Bf), jnp.float32)
            sel_bytes = Bf * Ff * Ff * kf * 4
            timed("ffm_sel_fwd_pallas", "ffm_sel",
                  lambda: pallas_fused.ffm_sel_scores(
                      rstk, fv, interpret=interpret),
                  _bytes((sel_bytes, 1), (Bf * Ff, 4), (Bf, 4)),
                  batch=Bf, fields=Ff, rank=kf)
            timed("ffm_sel_bwd_pallas", "ffm_sel",
                  lambda: pallas_fused.ffm_sel_bwd(
                      rstk, fv, ds, interpret=interpret),
                  _bytes((2 * sel_bytes, 1), (Bf * Ff, 4), (Bf, 4)),
                  batch=Bf, fields=Ff, rank=kf)

            @jax.jit
            def ffm_xla(R, x, d):
                Rv = R.reshape(Bf, Ff, Ff, kf)
                out = []
                for i in range(Ff):
                    selT_i = Rv[:, :, i, :] * x[:, :, None]
                    dsel_i = d[:, None, None] * selT_i
                    dsel_i = dsel_i.at[:, i, :].set(0)
                    out.append((dsel_i * x[:, i, None, None])
                               .reshape(Bf, Ff * kf))
                return jnp.stack(out, axis=1)

            timed("ffm_sel_bwd_xla", "ffm_sel",
                  lambda: ffm_xla(rstk, fv, ds),
                  _bytes((2 * sel_bytes, 1), (Bf * Ff, 4), (Bf, 4)),
                  batch=Bf, fields=Ff, rank=kf,
                  note="XLA blocked loop (fusion-dependent residency)")

    # ---- report under the obs run-dir convention -----------------------
    report_dir = args.report_dir
    if report_dir != "none":
        from fm_spark_tpu import obs
        from fm_spark_tpu.obs.ledger import runtime_versions

        run_id = obs.new_run_id()
        if report_dir is None:
            report_dir = os.path.join("artifacts", "obs", run_id)
        os.makedirs(report_dir, exist_ok=True)
        path = os.path.join(report_dir, "kernel_pricing.json")
        with open(path, "w") as f:
            json.dump({
                "tool": "bench_kernels", "backend": backend,
                "interpret": interpret, "dtype": args.dtype,
                "iters": args.iters, "run_id": run_id,
                "shapes": {"rows": args.rows, "width": w, "batch": B,
                           "cap": cap, "ffm_fields": args.ffm_fields,
                           "ffm_rank": args.ffm_rank,
                           "ffm_batch": args.ffm_batch},
                "ts": round(time.time(), 3),
                "kernels": rows_out,
            }, f, indent=1)
        # Every pricing row also lands in the cross-run perf ledger
        # (ISSUE 9): value = the bytes-model GB/s (higher is better, so
        # the sentinel's improved/regressed signs apply unchanged);
        # skipped rows record as nulls, never gaps. Interpret-mode rows
        # are recorded too — their fingerprint's device_kind ('cpu')
        # keeps them in their own cohort, away from on-chip history.
        try:
            # Sibling-of-the-run-dir convention (artifacts/obs/
            # ledger.jsonl); normpath so a trailing slash cannot land
            # the ledger INSIDE the run dir and fork the history.
            ledger = obs.PerfLedger(os.path.join(
                os.path.dirname(os.path.normpath(report_dir)) or ".",
                "ledger.jsonl"))
            sentinel = obs.Sentinel(ledger)
            vers = runtime_versions()
            for row in rows_out:
                fingerprint = obs.measurement_fingerprint(
                    variant=row["kernel"],
                    model=f"kernel/{row['family']}",
                    batch=row.get("batch"), rank=row.get("rank"),
                    # The same kernel at a different shape/dtype is
                    # a different cohort — a bf16 or resized run
                    # must not be judged against the fp32 band.
                    extra={k: row[k]
                           for k in ("dtype", "width", "cap",
                                     "rows", "fields", "interpret")
                           if k in row},
                    device_kind=backend,
                    jax_version=vers["jax_version"],
                    libtpu_version=vers["libtpu_version"],
                    # A capability/shape skip is NOT weather: the
                    # attachment is fine, there is just no number
                    # (classifies insufficient_history, and the
                    # 'skipped' field above carries the reason).
                    attachment_health="healthy",
                )
                sentinel.observe({
                    "kind": "kernel_pricing",
                    "leg": f"kernel/{row['family']}",
                    "run_id": run_id, "variant": row["kernel"],
                    "value": row.get("model_gbps"), "unit": "GB/s",
                    "ms": row.get("ms"),
                    "bytes_moved_model": row.get("bytes_moved_model"),
                    "skipped": row.get("skipped"),
                    "fingerprint": fingerprint,
                })
                if row.get("ms") is not None \
                        and row.get("bytes_moved_model"):
                    # Cost attribution (ISSUE 14): the measured-time x
                    # bytes-model pairing also lands under the ONE
                    # `cost_attribution` kind the autotuner (and
                    # run_doctor's cost table) reads, next to bench.py's
                    # whole-step rows — kernel-grain evidence and
                    # step-grain evidence in the same stream.
                    ledger.append({
                        "kind": "cost_attribution",
                        "leg": f"cost/kernel/{row['family']}",
                        "run_id": run_id, "variant": row["kernel"],
                        "value": row.get("model_gbps"),
                        "unit": "GB/s(model)",
                        "step_ms": row.get("ms"),
                        "bytes_per_step": row.get("bytes_moved_model"),
                        "families": {row["family"]:
                                     row.get("bytes_moved_model")},
                        "fingerprint": fingerprint,
                    })
        except Exception as e:  # noqa: BLE001 — ledger is best-effort
            print(f"bench_kernels: ledger append failed: {e!r}",
                  file=sys.stderr)
        print(json.dumps({"report": path, "kernels": len(rows_out),
                          "run_id": run_id}), flush=True)


if __name__ == "__main__":
    main()
