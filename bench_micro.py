"""Microbenchmarks behind PERF.md's measured facts 1-5.

Each subcommand reproduces one design-driving measurement so the
architecture rationale stays checkable on any machine:

  dispatch   fact 1: per-dispatch host overhead (trivial scalar add,
             timed per call), the fori_loop amortization, and whether
             block_until_ready fences (0.28 ms per dispatch and yes on
             the v5e, 2026-09-26 — PERF.md "Chip bring-up").
  gather     fact 2: per-index gather rate vs table BYTES (the ~34MB
             cliff that motivates per-field sub-tables).
  scatter    fact 3: scatter-add rate vs operand size (the ~128MB cliff
             and per-index bound that motivate single-owner sub-tables).
  matmul     fact 4: MXU peak check (compute is not the binding
             constraint).
  cast       fact 5: dense streaming bandwidth (why per-step shadow
             recasts are off the table).
  ladder     the scatter_add write by lanes and table: plain against
             coalesced (ops/scatter.update_lanes' constants; PERF.md §6,
             PR 35 and PR 37).
  all        run everything.

Prints one JSON line per measurement: {"bench": ..., "config": ...,
"value": ..., "unit": ...}, after a first line naming the device.
Timing uses a device->host transfer of one scalar as the completion
fence.
"""

import argparse
import json
import sys
import time


def _log(msg):
    print(f"bench_micro: {msg}", file=sys.stderr, flush=True)


def _out(bench, config, value, unit):
    print(json.dumps({"bench": bench, "config": config,
                      "value": round(value, 3), "unit": unit}), flush=True)


def _fence(x):
    """Reliable completion fence: device->host transfer of one scalar."""
    import jax.numpy as jnp

    return float(jnp.ravel(x)[0])


def _make_timed(prefix, base_cfg, unit):
    """Shared timing protocol for every probe: jit the thunk, run once
    (compile + warmup), time one fenced run, emit one JSON line. Single
    definition so a protocol change (extra warmup, median-of-N) lands in
    every probe at once. The jitted fn must RETURN everything it touches
    (nothing may be DCE'd)."""
    import jax

    def timed(name, fn, *xs, extra=None):
        f = jax.jit(fn)

        def run():
            return _fence(jax.tree_util.tree_leaves(f(*xs))[0])

        run()  # compile
        t0 = time.perf_counter()
        run()
        dt = time.perf_counter() - t0
        cfg = dict(base_cfg)
        if extra:
            cfg.update(extra)
        _out(f"{prefix}_{name}", cfg, dt * 1e3, unit)
        return dt

    return timed


def bench_dispatch(args):
    import jax
    import jax.numpy as jnp
    from jax import lax

    one = jnp.float32(1.0)

    @jax.jit
    def add(x):
        return x + 1.0

    @jax.jit
    def add_n(x, n):
        return lax.fori_loop(0, n, lambda i, c: c + 1.0, x)

    _fence(add(one))           # compile
    _fence(add_n(one, jnp.int32(2)))
    t0 = time.perf_counter()
    x = one
    for _ in range(args.calls):
        x = add(x)
    _fence(x)
    per_call = (time.perf_counter() - t0) / args.calls
    _out("dispatch", {"calls": args.calls}, per_call * 1e3,
         "ms/dispatch")

    t0 = time.perf_counter()
    _fence(add_n(one, jnp.int32(args.calls)))
    per_iter = (time.perf_counter() - t0) / args.calls
    _out("dispatch_fori", {"iters": args.calls}, per_iter * 1e6,
         "us/iter (same adds inside one fori_loop program)")

    # Does block_until_ready fence? One program long enough to tell (a
    # chain of matmuls), timed to (a) the call returning, (b)
    # block_until_ready, (c) a device->host scalar: (b) fences iff it
    # lands beside (c), not beside (a).
    a = jnp.ones((4096, 4096), jnp.bfloat16)

    @jax.jit
    def chain(x):
        return lax.fori_loop(
            0, 64, lambda i, c: (c @ c) * jnp.bfloat16(1 / 4096), x)

    _fence(chain(a))           # compile
    t0 = time.perf_counter()
    y = chain(a)
    t_call = time.perf_counter() - t0
    y.block_until_ready()
    t_bur = time.perf_counter() - t0
    t0 = time.perf_counter()
    _fence(chain(a))
    t_d2h = time.perf_counter() - t0
    _out("fence", {"program": "64 x (4096^3 bf16 matmul)"}, t_bur * 1e3,
         f"ms to block_until_ready (call returned at {t_call * 1e3:.3f} "
         f"ms; device->host scalar fence {t_d2h * 1e3:.3f} ms)")


def _gather_once(rows, width, dtype, n_idx, seed=0):
    import jax
    import jax.numpy as jnp
    import numpy as np

    table = jnp.zeros((rows, width), dtype)
    ids = jnp.asarray(
        np.random.default_rng(seed).integers(0, rows, n_idx), jnp.int32
    )

    @jax.jit
    def g(t, i):
        return jnp.sum(t[i].astype(jnp.float32))

    _fence(g(table, ids))  # compile
    t0 = time.perf_counter()
    _fence(g(table, ids))
    return time.perf_counter() - t0


def bench_gather(args):
    import numpy as np

    for rows, dtype in [(1 << 17, "float32"), (1 << 18, "bfloat16"),
                        (1 << 18, "float32"), (1 << 19, "float32"),
                        (1 << 20, "float32")]:
        dt = _gather_once(rows, args.width, dtype, args.n_idx)
        tbl_mb = rows * args.width * (2 if dtype == "bfloat16" else 4) / 2**20
        _out("gather", {"rows": rows, "width": args.width, "dtype": dtype,
                        "table_mb": round(tbl_mb, 1), "n_idx": args.n_idx},
             args.n_idx / dt / 1e6, "M idx/s")


def bench_scatter(args):
    import jax
    import jax.numpy as jnp
    import numpy as np

    for rows, dtype in [(1 << 17, "float32"), (1 << 18, "float32"),
                        (1 << 19, "float32"), (1 << 20, "float32")]:
        table = jnp.zeros((rows, args.width), dtype)
        ids = jnp.asarray(
            np.random.default_rng(0).integers(0, rows, args.n_idx),
            jnp.int32,
        )
        upd = jnp.ones((args.n_idx, args.width), dtype)

        @jax.jit
        def sc(t, i, u):
            return t.at[i].add(u, mode="drop")

        _fence(sc(table, ids, upd))  # compile
        t0 = time.perf_counter()
        _fence(sc(table, ids, upd))
        dt = time.perf_counter() - t0
        op_mb = rows * args.width * 4 / 2**20
        _out("scatter", {"rows": rows, "width": args.width, "dtype": dtype,
                         "operand_mb": round(op_mb, 1), "n_idx": args.n_idx},
             args.n_idx / dt / 1e6, "M idx/s")


def bench_matmul(args):
    import jax
    import jax.numpy as jnp

    n = args.size
    a = jnp.ones((n, n), jnp.bfloat16)

    @jax.jit
    def mm(x):
        return x @ x

    _fence(mm(a))  # compile
    t0 = time.perf_counter()
    _fence(mm(a))
    dt = time.perf_counter() - t0
    _out("matmul", {"size": n, "dtype": "bfloat16"},
         2 * n**3 / dt / 1e12, "TFLOP/s")


def bench_cast(args):
    import jax
    import jax.numpy as jnp

    tables = [jnp.ones((args.rows, args.width), jnp.float32)
              for _ in range(args.tables)]
    total_gb = args.tables * args.rows * args.width * 4 / 2**30

    @jax.jit
    def cast_all(ts):
        return [t.astype(jnp.bfloat16) for t in ts]

    _fence(cast_all(tables)[0])  # compile
    t0 = time.perf_counter()
    _fence(cast_all(tables)[0])
    dt = time.perf_counter() - t0
    _out("cast", {"tables": args.tables, "rows": args.rows,
                  "width": args.width, "read_gb": round(total_gb, 2)},
         total_gb / dt, "GB/s (fp32 read side)")


def bench_dedup(args):
    """Probes behind the host-assisted dedup lever (PERF.md round 3):
    the headline step's 39-field update cost under each write strategy,
    all fields in ONE jitted program (matching the fused step's shape).

    Answers two real-chip questions the design hinges on:
    (a) does XLA scatter get cheaper when duplicate lanes become
        OOB-drop no-ops (unique-only writes)?
    (b) how much of the device-side dedup cost is the argsort that a
        host prefetch thread could precompute?
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    F, rows, width, b = args.tables, args.rows, args.width + 1, args.n_idx
    rng = np.random.default_rng(0)
    ids_np = (rng.zipf(1.3, size=(b, F)) % rows).astype(np.int32)
    uniq_frac = np.mean(
        [np.unique(ids_np[:, f]).size for f in range(F)]
    ) / b
    ids = jnp.asarray(ids_np)
    upd = jnp.full((b, width), 1e-3, jnp.float32)
    tables = [jnp.zeros((rows, width), jnp.float32) for _ in range(F)]

    # Host-side aux (what the prefetch thread would ship): per-field sort
    # order and run-start mask, one vectorized numpy pass for all fields.
    order_np = np.argsort(ids_np, axis=0, kind="stable").astype(np.int32)
    sid_np = np.take_along_axis(ids_np, order_np, axis=0)
    run_np = np.concatenate(
        [np.ones((1, F), bool), sid_np[1:] != sid_np[:-1]], axis=0
    )
    order = jnp.asarray(order_np)
    run_start = jnp.asarray(run_np)
    sid_dev = jnp.asarray(sid_np)
    # Compacted per-field segment map: seg[p] = segment index of sorted
    # lane p; useg[s] = the unique id segment s writes to (OOB-padded) —
    # both host-computable, so the device never sorts or re-expands.
    seg_np = run_np.cumsum(axis=0).astype(np.int32) - 1
    useg_np = np.full((b, F), rows, np.int32)
    for f in range(F):
        u = sid_np[run_np[:, f], f]
        useg_np[: u.size, f] = u
    seg_dev = jnp.asarray(seg_np)
    useg = jnp.asarray(useg_np)

    timed = _make_timed(
        "dedup",
        {"fields": F, "rows": rows, "width": width, "batch": b,
         "uniq_frac": round(float(uniq_frac), 3)},
        "ms/step-equivalent",
    )

    def scatter_all(ts, idx):
        return [t.at[idx[:, f]].add(upd, mode="drop")
                for f, t in enumerate(ts)]

    timed("scatter_zipf", scatter_all, tables, ids)
    # Duplicate lanes routed out-of-bounds: same index count, unique
    # writes only — isolates whether dropped lanes are cheaper.
    oob_ids = jnp.where(run_start, sid_dev, rows)
    timed("scatter_dropped_dups", scatter_all, tables, oob_ids)

    def argsort_all(idx):
        return [jnp.argsort(idx[:, f]) for f in range(F)]

    timed("argsort_only", argsort_all, ids)

    def dedup_device_all(ts, idx):
        from fm_spark_tpu.ops.scatter import apply_row_updates
        return [apply_row_updates(t, idx[:, f], upd, mode="dedup")
                for f, t in enumerate(ts)]

    timed("device_full", dedup_device_all, tables, ids)

    def dedup_hostaux_all(ts, o, sg, u):
        # Device work: ONE batch-to-batch gather (delta reorder), one
        # segment_sum, one unique-target scatter. No sort, no [seg]
        # re-expansion.
        out = []
        for f, t in enumerate(ts):
            sdelta = upd[o[:, f]]
            summed = jax.ops.segment_sum(sdelta, sg[:, f], num_segments=b)
            out.append(t.at[u[:, f]].add(summed, mode="drop"))
        return out

    timed("hostaux", dedup_hostaux_all, tables, order, seg_dev, useg)


def bench_split(args):
    """Probe behind the sub-split lever: each headline field table is
    262144x65 fp32 = 68MB — ABOVE the ~34MB gather cliff (fact 2). Does
    storing each field as S row-slabs (each under the cliff) win, given
    gather then costs S x b lanes at the fast rate instead of b at the
    slow rate, and scatter costs S x b lanes with (S-1)/S of them
    OOB-dropped?  Run with --n-idx 131072 for the headline shape.

    Emits, for S in {1, 2, 4}: the 39-field gather time and scatter time
    of one step-equivalent. The OOB question (are dropped scatter lanes
    charged?) falls out of scatter_s1 vs scatter_s2/s4.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    F, rows, width, b = args.tables, args.rows, args.width + 1, args.n_idx
    rng = np.random.default_rng(0)
    ids = jnp.asarray(
        (rng.zipf(1.3, size=(b, F)) % rows).astype(np.int32)
    )
    upd = jnp.full((b, width), 1e-3, jnp.float32)

    timed = _make_timed(
        "split", {"fields": F, "rows": rows, "width": width, "batch": b},
        "ms/step-equivalent",
    )

    for s in (1, 2, 4):
        half = rows // s
        shift = int(np.log2(half))
        assert half * s == rows and 1 << shift == half
        slabs = [
            [jnp.zeros((half, width), jnp.float32) for _ in range(s)]
            for _ in range(F)
        ]
        slab_mb = half * width * 4 / 2**20

        def gather_all(ts, idx, s=s, shift=shift, half=half):
            # Per field: S masked gathers from slab-local ids + a select
            # chain — every id has exactly one owning slab.
            out = []
            for f, field_slabs in enumerate(ts):
                i = idx[:, f]
                hi, lo = i >> shift, i & (half - 1)
                r = None
                for j, t in enumerate(field_slabs):
                    rj = t[jnp.where(hi == j, lo, 0)]
                    r = rj if r is None else jnp.where(
                        (hi == j)[:, None], rj, r
                    )
                out.append(jnp.sum(r))
            return out

        def scatter_all(ts, idx, s=s, shift=shift, half=half):
            # Per field: S drop-scatters; non-owned lanes go OOB.
            out = []
            for f, field_slabs in enumerate(ts):
                i = idx[:, f]
                hi, lo = i >> shift, i & (half - 1)
                for j, t in enumerate(field_slabs):
                    out.append(
                        t.at[jnp.where(hi == j, lo, half)].add(
                            upd, mode="drop"
                        )
                    )
            return out

        timed(f"gather_s{s}", gather_all, slabs, ids,
              extra={"slabs": s, "slab_mb": round(slab_mb, 1)})
        timed(f"scatter_s{s}", scatter_all, slabs, ids,
              extra={"slabs": s, "slab_mb": round(slab_mb, 1)})


def bench_compact(args):
    """Probe behind the COMPACT host-dedup lever (round-2 finding: OOB-
    dropped scatter lanes are charged like live ones — dedup_scatter_
    dropped_dups ~= dedup_scatter_zipf — so winning requires REDUCING the
    lane count against the big tables, not masking lanes).

    With host-sorted ids and a static per-field unique-capacity ``cap``:
      forward:  urows = t[useg]         (cap sorted lanes vs B from 68MB)
                rows  = urows[inv]      (B lanes from a [cap,w] buffer)
      backward: sdelta = delta[order]   (B lanes, [B,w] buffer)
                csum   = cumsum(sdelta) (one streaming pass, no scatter)
                segsum = csum[seg_end] - csum[seg_end - run_len]
                t.at[useg].add(segsum, unique + sorted, cap lanes)

    vs the shipped chain: t[ids] gather (B lanes, 68MB table) +
    t.at[ids].add (B lanes). Run with --n-idx 131072.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    F, rows, width, b = args.tables, args.rows, args.width + 1, args.n_idx
    cap = args.cap
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    rng = np.random.default_rng(0)
    ids_np = (rng.zipf(1.3, size=(b, F)) % rows).astype(np.int32)
    nu = max(np.unique(ids_np[:, f]).size for f in range(F))
    if nu > cap:
        raise SystemExit(f"cap {cap} < max unique {nu}; raise --cap")

    # Host aux from the SHIPPED builder (one implementation of the
    # useg/segstart/segend/order/inv contract — the probe must measure
    # the same layout the step consumes).
    from fm_spark_tpu.ops.scatter import compact_aux

    useg_np, segstart_np, segend_np, order_np, inv_np = compact_aux(
        ids_np, cap
    )
    order = jnp.asarray(order_np.T)   # probe uses [B, F]-major layouts
    useg = jnp.asarray(useg_np)
    segend = jnp.asarray(segend_np)
    segstart = jnp.asarray(segstart_np)
    inv = jnp.asarray(inv_np.T)
    ids = jnp.asarray(ids_np)
    tables = [jnp.zeros((rows, width), dtype) for _ in range(F)]
    delta = jnp.full((b, width), 1e-3, jnp.float32)

    timed = _make_timed(
        "compact",
        {"fields": F, "rows": rows, "width": width, "batch": b,
         "cap": cap, "max_unique": int(nu), "dtype": args.dtype},
        "ms/step-equivalent",
    )

    def baseline_chain(ts, idx):
        out = []
        for f, t in enumerate(ts):
            r = t[idx[:, f]].astype(jnp.float32)
            out.append(t.at[idx[:, f]].add(
                (r * 1e-4 + delta).astype(t.dtype), mode="drop"))
        return out

    timed("baseline_gather_scatter", baseline_chain, tables, ids)

    def compact_chain(ts, useg, inv, order, segend, segstart, skip=()):
        # ``skip`` disables pieces so their marginal cost can be
        # bracketed on chip: 'expand' (per-lane row expansion),
        # 'reorder' (the delta[order] gather), 'cumsum' (the segment
        # reduction).
        out = []
        for f, t in enumerate(ts):
            u = useg[f]
            urows = t[jnp.clip(u, 0, rows - 1)]        # cap sorted lanes
            if "expand" in skip:
                d = delta
            else:
                r = urows[inv[:, f]]                   # B lanes, tiny buf
                d = r.astype(jnp.float32) * 1e-4 + delta
            sdelta = d if "reorder" in skip else d[order[:, f]]
            if "cumsum" in skip:
                segsum = sdelta[segstart[f]]
            else:
                csum = jnp.cumsum(sdelta, axis=0)
                lo = csum[segstart[f]] - sdelta[segstart[f]]
                segsum = csum[segend[f]] - lo          # exact per-segment
            out.append(
                t.at[u].add(segsum.astype(t.dtype), mode="drop",
                            unique_indices=True, indices_are_sorted=True)
            )
        return out

    timed("chain", compact_chain, tables, useg, inv, order, segend,
          segstart)
    import functools

    for piece in ("expand", "reorder", "cumsum"):
        timed(
            f"chain_minus_{piece}",
            functools.partial(compact_chain, skip=(piece,)),
            tables, useg, inv, order, segend, segstart,
            extra={"skipped": piece},
        )

    def compact_scatter_only(ts, useg):
        return [
            t.at[useg[f]].add(jnp.ones((cap, width), t.dtype),
                              mode="drop", unique_indices=True,
                              indices_are_sorted=True)
            for f, t in enumerate(ts)
        ]

    timed("scatter_unique_sorted_only", compact_scatter_only, tables,
          useg)

    def compact_gather_only(ts, useg):
        return [jnp.sum(t[jnp.clip(useg[f], 0, rows - 1)]
                        .astype(jnp.float32))
                for f, t in enumerate(ts)]

    timed("gather_cap_only", compact_gather_only, tables, useg)


def bench_cumsum(args):
    """The compact chain's cumsum is its biggest removable piece (~46ms
    of the 127ms bf16 chain — `compact` probe, chain vs chain_minus_
    cumsum). This probe isolates how the prefix cost responds to width
    (TPU minor-dim lane padding: widths 1..128 should cost the SAME
    physical bandwidth), dtype, orientation, and the blocked two-level
    formulation, plus the totals-only lower bound (one read pass).
    Shapes: 39 x [131072, w] like the headline backward buffers.
    """
    import jax
    import jax.numpy as jnp

    F, b = args.tables, args.n_idx
    timed = _make_timed("cumsum", {"fields": F, "batch": b},
                        "ms/39-field")

    for w, dt_ in ((65, jnp.float32), (64, jnp.float32),
                   (128, jnp.float32), (33, jnp.float32),
                   (65, jnp.bfloat16)):
        xs = [jnp.full((b, w), 1e-3, dt_) for _ in range(F)]
        timed(
            f"w{w}_{dt_.__name__}",
            lambda ts: [jnp.cumsum(t, axis=0) for t in ts], xs,
            extra={"width": w, "dtype": dt_.__name__},
        )

    xs65 = [jnp.full((b, 65), 1e-3, jnp.float32) for _ in range(F)]
    # Totals-only lower bound: one read pass, [w] out per field.
    timed("sum_only_w65", lambda ts: [jnp.sum(t, axis=0) for t in ts],
          xs65, extra={"width": 65, "dtype": "float32"})

    # Blocked two-level prefix: per-block local cumsum -> tiny cumsum of
    # block totals -> add offsets. Same output as cumsum. (Round 3: this
    # formulation SHIPPED in ops/scatter.compact_apply and lifted the
    # headline 1.06M -> 1.18M; the block sweep picks _CSUM_BLOCK.)
    def blocked(ts, blk):
        out = []
        for t in ts:
            pad = (-b) % blk  # same padding as the shipped compact_apply
            if pad:
                t = jnp.pad(t, ((0, pad), (0, 0)))
            r = t.reshape(-1, blk, t.shape[-1])
            bl = jnp.cumsum(r, axis=1)
            off = jnp.cumsum(bl[:, -1, :], axis=0)
            off = jnp.concatenate(
                [jnp.zeros_like(off[:1]), off[:-1]], axis=0
            )
            out.append(
                (bl + off[:, None, :]).reshape(-1, t.shape[-1])[:b]
            )
        return out

    for blk in (256, 512, 1024):
        timed(f"blocked{blk}_w65",
              lambda ts, blk=blk: blocked(ts, blk), xs65,
              extra={"width": 65, "dtype": "float32"})

    # What compact_apply actually pays: it never materializes the full
    # prefix — it GATHERS bl/off at 2·cap boundary positions.
    cap = args.cap or 16384
    pos = jnp.sort(
        jax.random.randint(jax.random.key(0), (cap,), 0, b, jnp.int32)
    )

    def boundaries_only(ts, blk):
        out = []
        for t in ts:
            pad = (-b) % blk
            if pad:
                t = jnp.pad(t, ((0, pad), (0, 0)))
            r = t.reshape(-1, blk, t.shape[-1])
            bl = jnp.cumsum(r, axis=1)
            off = jnp.cumsum(bl[:, -1, :], axis=0)
            off = jnp.concatenate(
                [jnp.zeros_like(off[:1]), off[:-1]], axis=0
            )
            out.append(bl[pos // blk, pos % blk] + off[pos // blk])
        return out

    for blk in (256, 512, 1024):
        timed(f"boundaries{blk}_w65",
              lambda ts, blk=blk: boundaries_only(ts, blk), xs65,
              extra={"width": 65, "dtype": "float32", "cap": cap})

    # Transposed orientation: prefix along the LANE-major axis.
    xsT = [jnp.full((65, b), 1e-3, jnp.float32) for _ in range(F)]
    timed("transposed_w65",
          lambda ts: [jnp.cumsum(t, axis=1) for t in ts],
          xsT, extra={"width": 65, "dtype": "float32", "layout": "[w,B]"})


def bench_merge(args):
    """Is the compact chain's per-field gather/scatter cost a FIXED
    per-op overhead (x39 fields) rather than per-lane or per-byte? The
    `compact` probe measured ~1.7ms/table for a 16k-lane cap-gather —
    barely cheaper than 131k lanes — suggesting op-count or table-scan
    cost, not lane count, is what the cap path still pays. If per-op,
    ONE gather over a stacked monolith at cap*F lanes should crush 39
    per-field gathers even at the monolith's slow per-lane rate.
    Scatter is probed both ways too — the >128MB operand cliff (fact 3)
    predicts the merged scatter LOSES; per-field writes should stay.

    Index construction: the monolith has ``cap`` PADDING rows appended
    per field (shape [(rows+cap)*F, w]); field f's real ids live at
    ``f*(rows+cap) + id`` and its sentinel lanes map to the padding
    rows ``f*(rows+cap) + rows + s`` — so the flattened index vector is
    genuinely ascending AND unique (both XLA promises hold; padding
    rows absorb the sentinel writes, which is timing-equivalent to
    dropping them).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    F, rows, width = args.tables, args.rows, args.width + 1
    cap = args.cap
    b = args.n_idx
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    rng = np.random.default_rng(0)
    ids_np = (rng.zipf(1.3, size=(b, F)) % rows).astype(np.int32)
    from fm_spark_tpu.ops.scatter import compact_aux

    useg_np = compact_aux(ids_np, cap)[0]             # [F, cap]
    useg = jnp.asarray(useg_np)
    stride = rows + cap
    sent = useg_np >= rows                             # sentinel lanes
    within = np.where(
        sent,
        rows + (useg_np.argsort(axis=1).argsort(axis=1)),  # stable slots
        useg_np,
    )
    # Per-field ascending (real ids ascend below rows; sentinel slots
    # ascend from rows), plus field-major strides => globally ascending
    # and unique.
    gids = jnp.asarray(
        (within + (np.arange(F)[:, None] * stride)).astype(np.int32)
        .reshape(-1)
    )
    tables = [jnp.zeros((rows, width), dtype) for _ in range(F)]
    mono = jnp.zeros((F * stride, width), dtype)
    upd = jnp.full((F * cap, width), 1e-3, jnp.float32)

    timed = _make_timed(
        "merge",
        {"fields": F, "rows": rows, "width": width, "cap": cap,
         "dtype": args.dtype},
        "ms",
    )

    timed("gather_per_field",
          lambda ts, u: [t[jnp.clip(u[f], 0, rows - 1)]
                         for f, t in enumerate(ts)],
          tables, useg)
    timed("gather_monolith",
          lambda m, g: m.at[g].get(mode="clip", indices_are_sorted=True,
                                   unique_indices=True),
          mono, gids)
    timed("scatter_per_field",
          lambda ts, u: [t.at[u[f]].add(
              upd[f * cap:(f + 1) * cap].astype(t.dtype), mode="drop",
              unique_indices=True, indices_are_sorted=True)
              for f, t in enumerate(ts)],
          tables, useg)
    timed("scatter_monolith",
          lambda m, g: m.at[g].add(upd.astype(m.dtype), mode="drop",
                                   unique_indices=True,
                                   indices_are_sorted=True),
          mono, gids)


def bench_stackfuse(args):
    """Does issuing the chain's buffer work as 39 per-field ops cost
    more than ONE op over the stacked [39, B, w] array? (It did not on
    this chip — sum/cumsum/boundary came out equal, refuting the
    per-fusion-overhead hypothesis; the cost is per-work. Kept so the
    conclusion stays reproducible.)
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    F, width, b = args.tables, args.width + 1, args.n_idx
    cap = args.cap
    rng = np.random.default_rng(0)
    xs = [jnp.full((b, width), 1e-3, jnp.float32) for _ in range(F)]
    xstk = jnp.stack(xs)                              # [F, B, w]
    small = [jnp.full((cap, width), 1e-3, jnp.float32) for _ in range(F)]
    smallstk = jnp.stack(small)                       # [F, cap, w]
    inv = jnp.asarray(rng.integers(0, cap, size=(F, b)), jnp.int32)
    bnd = jnp.asarray(rng.integers(0, b, size=(F, cap)), jnp.int32)

    timed = _make_timed(
        "stackfuse",
        {"fields": F, "batch": b, "width": width, "cap": cap},
        "ms",
    )

    timed("sum_per_field",
          lambda ts: [jnp.sum(t, axis=0) for t in ts], xs)
    timed("sum_stacked", lambda t: jnp.sum(t, axis=1), xstk)
    timed("cumsum_per_field",
          lambda ts: [jnp.cumsum(t, axis=0) for t in ts], xs)
    timed("cumsum_stacked", lambda t: jnp.cumsum(t, axis=1), xstk)
    timed("expand_per_field",
          lambda ss, iv: [s[iv[f]] for f, s in enumerate(ss)],
          small, inv)
    timed("expand_stacked",
          lambda s, iv: jnp.take_along_axis(s, iv[:, :, None], axis=1),
          smallstk, inv)
    timed("boundary_per_field",
          lambda ts, bd: [t[bd[f]] for f, t in enumerate(ts)], xs, bnd)
    timed("boundary_stacked",
          lambda t, bd: jnp.take_along_axis(t, bd[:, :, None], axis=1),
          xstk, bnd)


def bench_scanmodel(args):
    """Pins the round-2 cost model: big-table ops cost ~= stream(operand
    bytes)/BW + lanes * ~20ns, i.e. gather SCANS the table no matter how
    few lanes it fetches. Probes (39 fields, headline rows/width):

    - cap-gather at cap in {1024, 16384, B}: flat => scan confirmed;
    - gather at fp8 / bf16 / fp32 tables: scan cost should track BYTES;
    - sorted segment_sum into cap segments (tiny [cap, w] operand) vs
      the cumsum+boundary formulation the chain ships;
    - cumsum with bf16 INPUT, fp32 accumulation (halves the read side).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    F, rows, width, b = args.tables, args.rows, args.width + 1, args.n_idx
    rng = np.random.default_rng(0)
    timed = _make_timed(
        "scanmodel", {"fields": F, "rows": rows, "width": width}, "ms",
    )

    for cap_try in (1024, 16384, min(b, rows)):
        ids = jnp.asarray(
            np.sort(rng.choice(rows, size=(F, cap_try))).astype(np.int32),
            jnp.int32,
        )
        tables = [jnp.zeros((rows, width), jnp.bfloat16)
                  for _ in range(F)]
        timed(f"gather_cap{cap_try}_bf16",
              lambda ts, u: [jnp.sum(t[u[f]].astype(jnp.float32))
                             for f, t in enumerate(ts)],
              tables, ids, extra={"cap": cap_try, "table_dtype": "bf16"})

    for dt_name in ("float8_e4m3fn", "bfloat16", "float32"):
        dt_ = getattr(jnp, dt_name)
        ids = jnp.asarray(
            np.sort(rng.choice(rows, size=(F, 16384))).astype(np.int32),
            jnp.int32,
        )
        tables = [jnp.zeros((rows, width), dt_) for _ in range(F)]
        timed(f"gather_cap16384_{dt_name}",
              lambda ts, u: [jnp.sum(t[u[f]].astype(jnp.float32))
                             for f, t in enumerate(ts)],
              tables, ids, extra={"cap": 16384, "table_dtype": dt_name})

    # Segment reduction alternatives at the chain's shapes.
    cap = args.cap
    seg = jnp.asarray(
        np.sort(rng.integers(0, cap, size=(F, b)), axis=1).astype(np.int32)
    )
    sdelta = [jnp.full((b, width), 1e-3, jnp.float32) for _ in range(F)]

    timed("segsum_sorted_capsegs",
          lambda ds, sg: [
              jax.ops.segment_sum(d, sg[f], num_segments=cap,
                                  indices_are_sorted=True)
              for f, d in enumerate(ds)
          ],
          sdelta, seg, extra={"cap": cap})

    bnd = jnp.asarray(rng.integers(0, b, size=(F, cap)), jnp.int32)
    timed("cumsum_boundary_fp32",
          lambda ds, bd: [
              jnp.cumsum(d, axis=0)[bd[f]] for f, d in enumerate(ds)
          ],
          sdelta, bnd, extra={"cap": cap})
    sdelta_bf = [d.astype(jnp.bfloat16) for d in sdelta]
    timed("cumsum_boundary_bf16in",
          lambda ds, bd: [
              jnp.cumsum(d, axis=0, dtype=jnp.float32)[bd[f]]
              for f, d in enumerate(ds)
          ],
          sdelta_bf, bnd, extra={"cap": cap})


def bench_transpose(args):
    """Table-layout probe: [rows, 65] pads the minor dim to 128 lanes
    (physical bytes ~2x nominal), and the scan model says big-table ops
    track OPERAND bytes. A transposed [65, rows] table has no lane
    padding (rows % 128 == 0) — if the scan really tracks physical
    bytes, column-gather/scatter on the transposed layout should cost
    about half. Also probes width 256 on the row layout (2 lane-tiles)
    to confirm the padding model itself.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    F, rows, width = args.tables, args.rows, args.width + 1
    cap = args.cap
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    rng = np.random.default_rng(0)
    from fm_spark_tpu.ops.scatter import compact_aux

    ids_np = (rng.zipf(1.3, size=(args.n_idx, F)) % rows).astype(np.int32)
    useg = jnp.asarray(compact_aux(ids_np, cap)[0])
    upd_row = jnp.full((cap, width), 1e-3, jnp.float32)
    upd_col = jnp.full((width, cap), 1e-3, jnp.float32)

    timed = _make_timed(
        "transpose",
        {"fields": F, "rows": rows, "width": width, "cap": cap,
         "dtype": args.dtype},
        "ms",
    )

    tables = [jnp.zeros((rows, width), dtype) for _ in range(F)]
    timed("row_gather_cap",
          lambda ts, u: [jnp.sum(t[jnp.clip(u[f], 0, rows - 1)]
                                 .astype(jnp.float32))
                         for f, t in enumerate(ts)],
          tables, useg)
    timed("row_scatter_cap",
          lambda ts, u: [t.at[u[f]].add(upd_row.astype(t.dtype),
                                        mode="drop", unique_indices=True,
                                        indices_are_sorted=True)
                         for f, t in enumerate(ts)],
          tables, useg)
    del tables

    tablesT = [jnp.zeros((width, rows), dtype) for _ in range(F)]
    timed("col_gather_cap",
          lambda ts, u: [jnp.sum(t[:, jnp.clip(u[f], 0, rows - 1)]
                                 .astype(jnp.float32))
                         for f, t in enumerate(ts)],
          tablesT, useg)
    timed("col_scatter_cap",
          lambda ts, u: [t.at[:, u[f]].add(upd_col.astype(t.dtype),
                                           mode="drop",
                                           unique_indices=True,
                                           indices_are_sorted=True)
                         for f, t in enumerate(ts)],
          tablesT, useg)
    del tablesT

    tables256 = [jnp.zeros((rows, 256), dtype) for _ in range(F)]
    timed("row_gather_cap_w256",
          lambda ts, u: [jnp.sum(t[jnp.clip(u[f], 0, rows - 1)]
                                 .astype(jnp.float32))
                         for f, t in enumerate(ts)],
          tables256, useg, extra={"width": 256})


def bench_gfull(args):
    """The g_full construction A/B (PERF.md round-4 lever): per-field
    ``concat([g_v, g_l])`` vs the fused ``ds·x·(s1 − mask·xv_full)``
    form (one s1 concat total). Both arms start from (rows, vals, ds, s)
    — including the xv recompute each form implies — and are timed two
    ways: bare construction (sum consumer) and with the compact chain's
    first consumer, a per-field reorder gather, so fusion INTO the
    gather is captured. If XLA already fuses the concats away, the arms
    tie and the lever is refuted.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    F, k, b = args.tables, args.width, args.n_idx
    w = k + 1
    rng = np.random.default_rng(0)
    rows = [jnp.asarray(rng.normal(size=(b, w)), jnp.float32)
            for _ in range(F)]
    vals = jnp.asarray(rng.uniform(0.5, 1.5, size=(b, F)), jnp.float32)
    ds = jnp.asarray(rng.normal(size=(b,)), jnp.float32)
    s = jnp.asarray(rng.normal(size=(b, k)), jnp.float32)
    order = jnp.asarray(
        np.stack([rng.permutation(b) for _ in range(F)]), jnp.int32)

    timed = _make_timed(
        "gfull", {"fields": F, "batch": b, "width": w}, "ms",
    )

    def g_concat(rows, vals, ds, s):
        out = []
        for f in range(F):
            xv = rows[f][:, :k] * vals[:, f : f + 1]
            g_v = ds[:, None] * vals[:, f : f + 1] * (s - xv)
            g_l = ds * vals[:, f]
            out.append(jnp.concatenate([g_v, g_l[:, None]], axis=1))
        return out

    def g_fused(rows, vals, ds, s):
        s1 = jnp.concatenate(
            [s, jnp.ones((ds.shape[0], 1), jnp.float32)], axis=1)
        colmask = jnp.arange(w) < k
        out = []
        for f in range(F):
            xvf = rows[f] * vals[:, f : f + 1]
            out.append(ds[:, None] * vals[:, f : f + 1] * (
                s1 - jnp.where(colmask, xvf, jnp.zeros((), jnp.float32))))
        return out

    timed("concat_sum",
          lambda *xs: [jnp.sum(g) for g in g_concat(*xs)],
          rows, vals, ds, s)
    timed("fused_sum",
          lambda *xs: [jnp.sum(g) for g in g_fused(*xs)],
          rows, vals, ds, s)
    timed("concat_reorder",
          lambda o, *xs: [jnp.sum(g[o[f]])
                          for f, g in enumerate(g_concat(*xs))],
          order, rows, vals, ds, s)
    timed("fused_reorder",
          lambda o, *xs: [jnp.sum(g[o[f]])
                          for f, g in enumerate(g_fused(*xs))],
          order, rows, vals, ds, s)


# (table rows, table lanes, delta width): configs 4, 5 and 3 as the
# one-chip loop holds their tables.
_LADDER_SHAPES = [(1 << 17, 384, 369), (1 << 18, 128, 17), (1 << 18, 128, 65)]
_LADDER_LANES = (2048, 4096, 8192, 16384, 32768, 65536, 131072)
# PR 37's points, (table, lanes), around where XLA changes its lowering
# of the plain add: compiled for a v5e it sorts the update first from
# one lane over an eighth of the table's ROWS.
_DLRM = (1 << 19, 128, 128)         # rows that ARE lane tiles
_MESH = (1 << 18, 65, 65)           # config 3's as a mesh holds them:
#                                     nothing padded on, bucket-minor
_BIG = (1 << 20, 128, 65)           # config 3's batch, four times the rows
_LADDER_POINTS_37 = (
    # DLRM's batch is 55,296 lanes; its table's eighth is 65,536.
    [(_DLRM, b) for b in (32768, 49152, 55296, 61440, 65536, 66560, 131072)]
    + [(s, b) for s in _LADDER_SHAPES for b in (49152, 55296, 61440)]
    # What tells rows from the update's elements: 7.86M and 8.65M of
    # them, both over this table's eighth (16,384 lanes).
    + [(_LADDER_SHAPES[0], b) for b in (20480, 22528)]
    + [(_MESH, b) for b in (32768, 65536, 131072)]
    + [(_BIG, b) for b in (131072, 132096)])    # its eighth, a chunk over
# ``plain_padded``: one chunk over an eighth of DLRM's rows, the fewest
# whole chunks at which XLA sorts the plain add (65,536, the eighth
# itself, read 4.856 ms: dear, as 55,296 unpadded).
_LADDER_PADDED_LANES = 66560


def bench_ladder(args):
    """The ``scatter_add`` write's batch-size ladder (PERF.md §6, PR 35
    and PR 37; what ``ops/scatter.update_lanes``' two constants are read
    from): for one field's write into ``f32[131072,384]`` (delta 369
    wide: config 4), ``f32[262144,128]`` (17: config 5; 65: config 3)
    and ``f32[524288,128]`` (128: DLRM), at 2,048 to 131,072 lanes of
    Zipf(1.5) ids as ``synthetic_ctr`` draws them, the plain add, the
    coalesced add, and the coalesce alone; uniform ids, the coalesced
    add's worst case, at 8,192 lanes and at DLRM's 55,296; the mesh's
    ``f32[262144,65]`` and a table of 2^20 rows on either side of an
    eighth of their rows; and, for the record only, DLRM's plain add
    padded to 66,560 lanes with dropped ids (``plain_padded``: over
    XLA's switch by construction, NOT shipped). One jitted program a
    point: ``reps`` rounds over four donated tables, each round with ids
    of its own, ms per table per round at the median of three fenced
    calls."""
    import statistics

    import jax
    import jax.numpy as jnp
    import numpy as np

    from fm_spark_tpu.ops import scatter

    tables_n, reps = 4, 16

    def plain(t, i, d):
        return t.at[i].add(scatter._to_table_width(d, t), mode="drop")

    def plain_padded(t, i, d):
        extra = _LADDER_PADDED_LANES - i.shape[0]
        i = jnp.concatenate([i, jnp.full((extra,), t.shape[0], i.dtype)])
        return plain(t, i, jnp.pad(d, ((0, extra), (0, 0))))

    def coalesce_only(t, i, d):
        useg, totals, n = scatter.coalesce(i, d)
        # Everything the coalesce makes is read (nothing DCE'd), one
        # lane written.
        keep = (totals.sum(0) + useg.sum() + n)[None, :]
        return t.at[jnp.zeros((1,), jnp.int32)].add(
            scatter._to_table_width(keep, t))

    variants = {"plain": plain, "coalesced": scatter.coalesced_add,
                "coalesce_only": coalesce_only,
                "plain_padded": plain_padded}
    three = ("plain", "coalesced", "coalesce_only")

    def point(rows, lanes_w, width, b, draw, variant):
        rng = np.random.default_rng(b)
        ids = jnp.asarray(draw(rng, (reps, tables_n, b), rows), jnp.int32)
        base = jnp.asarray(rng.normal(size=(b + reps, width)) * 1e-3,
                           jnp.float32)
        fn = variants[variant]

        def rounds(ts, ids, base):
            def one(r, ts):
                d = jax.lax.dynamic_slice(base, (r, 0), (b, width))
                return tuple(fn(t, ids[r, k], d)
                             for k, t in enumerate(ts))
            return jax.lax.fori_loop(0, reps, one, ts)

        f = jax.jit(rounds, donate_argnums=0)
        ts = tuple(jnp.zeros((rows, lanes_w), jnp.float32)
                   for _ in range(tables_n))
        ts = f(ts, ids, base)
        _fence(ts[-1])
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            ts = f(ts, ids, base)
            _fence(ts[-1])
            times.append(time.perf_counter() - t0)
        unique = float(np.mean(
            [len(np.unique(np.asarray(ids[0, k]))) for k in range(tables_n)]))
        return statistics.median(times) / (reps * tables_n) * 1e3, unique

    zipf = lambda rng, size, rows: rng.zipf(1.5, size=size) % rows
    uniform = lambda rng, size, rows: rng.integers(0, rows, size=size)
    # PR 37's points first, then PR 35's as they stood.
    ladder = [(s, b, "zipf", zipf, three) for s, b in _LADDER_POINTS_37]
    ladder += [(_DLRM, 55296, "uniform", uniform, three),
               (_DLRM, 55296, "zipf", zipf, ("plain_padded",))]
    ladder += [(s, b, "zipf", zipf, three) for s in _LADDER_SHAPES
               for b in _LADDER_LANES]
    ladder += [(s, 8192, "uniform", uniform, three)
               for s in _LADDER_SHAPES[:2]]
    for (rows, lanes_w, width), b, name, draw, vs in ladder:
        for variant in vs:
            ms, unique = point(rows, lanes_w, width, b, draw, variant)
            _out(f"ladder_{variant}",
                 {"table": [rows, lanes_w], "delta_width": width,
                  "lanes": b, "ids": name, "unique_rows": round(unique)},
                 ms, "ms/field")


BENCHES = {
    "dispatch": bench_dispatch,
    "gather": bench_gather,
    "scatter": bench_scatter,
    "matmul": bench_matmul,
    "cast": bench_cast,
    "dedup": bench_dedup,
    "split": bench_split,
    "compact": bench_compact,
    "cumsum": bench_cumsum,
    "merge": bench_merge,
    "stackfuse": bench_stackfuse,
    "scanmodel": bench_scanmodel,
    "transpose": bench_transpose,
    "gfull": bench_gfull,
    "ladder": bench_ladder,
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("bench", choices=[*BENCHES, "all"])
    ap.add_argument("--calls", type=int, default=30)
    ap.add_argument("--n-idx", type=int, default=None,
                    help="index count. Default depends on the probe: the "
                    "single-table probes (gather/scatter) use B*F = "
                    "5242880 (the headline step's total index count); "
                    "the per-field batch probes (dedup/split/compact/"
                    "cumsum/merge/stackfuse/scanmodel/transpose/gfull) use "
                    "B = 131072 (the headline batch) — passing the B*F "
                    "default to those would build a 204M-id host aux")
    ap.add_argument("--width", type=int, default=64)
    ap.add_argument("--rows", type=int, default=1 << 18)
    ap.add_argument("--tables", type=int, default=39)
    ap.add_argument("--size", type=int, default=8192)
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="compact/merge probes: table storage dtype")
    ap.add_argument("--cap", type=int, default=16384,
                    help="compact probe: static per-field unique-id "
                    "capacity")
    args = ap.parse_args()

    import copy

    from fm_spark_tpu.utils import device as device_lib

    print(json.dumps({"device": device_lib.describe()}), flush=True)

    for name in (BENCHES if args.bench == "all" else [args.bench]):
        a = copy.copy(args)
        if a.n_idx is None:
            a.n_idx = 5_242_880 if name in ("gather", "scatter") else 1 << 17
        _log(f"running {name}...")
        BENCHES[name](a)


if __name__ == "__main__":
    main()
