"""Multi-chip projection model for the field-sharded fused steps.

The 8-chip aggregate has not been measured (a four-chip host is what
the chip tool reaches — PERF.md "Chip bring-up" records its first run).
What CAN be committed is (a) exact per-chip work and collective-traffic
counts for each sharded program, derivable from its construction
(parallel/field_step.py), and (b) a time model whose every input is a
measured single-chip number or a named assumption — so a reviewer can
audit the arithmetic and swap assumptions. VERDICT r2 #6 asked for the
FM model; VERDICT r3 #4 for the FFM and DeepFM traffic models (the FFM
sel all_to_all is ~F× the FM psum bytes at headline shapes — whether
config 4 scales is a traffic question, answered here).
``__graft_entry__.dryrun_multichip`` prints the result so the driver's
MULTICHIP artifact carries it.

Model (1-D ``feat`` mesh; the 2-D row axis adds only the h/ownership
psums noted per model):

- Each chip owns ``F_pad/n`` fields and performs only their big-table
  index ops: ``cap`` gather + ``cap`` scatter lanes per owned field on
  the compact path (B lanes each on the plain path).
- The per-field [B]-lane work (expand, reorder, cumsum) also shards by
  ``n`` — it is per owned field. FFM's [B, F_pad, k] sel blocks and
  DeepFM's MLP are per owned field / replicated-cheap respectively.
- What does NOT shard: per-dispatch overhead and the replicated score /
  dscores math ([B, k] reductions over the FULL global batch — every
  chip repeats it, so in weak scaling this term GROWS with n; the model
  scales it with B explicitly, which round-3's constant-input version
  under-counted).
- ICI traffic per chip per step (exact counts per model below): the
  batch all_to_all (ids+vals), labels/weights all_gathers, and the
  model's activation collectives. ``collective_dtype='bfloat16'``
  (TrainConfig) halves the ACTIVATION collective bytes — the score
  psum group (FM), + the sel all_to_all (FFM), + the h gather/psum
  (DeepFM); the batch re-shard stays int32/fp32.

Time decomposition: the measured single-chip step time ``T1(B) =
B/rate`` splits into ``t_fixed`` (dispatch), ``t_rep(B)`` (replicated
score math, linear in B), and ``t_sharded = T1 − t_fixed − t_rep``
(everything that divides by ``n``). Then

    t(n) = t_fixed + t_rep(B) + t_sharded(B)/n + ici_bytes(n)/ici_bw
    aggregate(n) = B / t(n)        # global samples per second
"""

from __future__ import annotations

_WIRE_BYTES = {"float32": 4, "bfloat16": 2}


def _base_counts(B: int, F: int, k: int, n: int, cap: int,
                 device_aux: bool, n_total: int | None = None) -> dict:
    """Work + batch-reshard ICI counts shared by all three models.

    ``n_total`` (2-D meshes): the batch enters example-sharded over
    EVERY mesh axis (field_step.field_batch_specs), so the batch
    a2a / labels all_gather cross ``n_total`` chips while the
    feat-axis activation collectives cross only ``n`` — the two recv
    fractions differ (ADVICE r4)."""
    f_pad = -(-F // n) * n
    f_local = f_pad // n
    lanes = cap if cap else B
    ring = 2 * (n - 1) / n  # ring all-reduce traffic factor
    recv = (n - 1) / n      # fraction of an all_to_all/all_gather that
    #                         crosses ICI (the rest is already local)
    nt = n_total if n_total is not None else n
    recv_batch = (nt - 1) / nt  # batch-reshard fraction (total chips)
    a2a_cols = f_local * (8 if device_aux or not cap else 4)
    # host-compact skips the ids all_to_all (field_step._field_forward);
    # its aux arrives host->device, not over ICI.
    return dict(
        f_pad=f_pad, f_local=f_local, lanes=lanes, ring=ring, recv=recv,
        per_chip={
            # Index ops against the BIG tables — the measured bottleneck
            # (PERF.md facts 2-3). This is the n-fold reduction
            # scale-out buys.
            "big_table_gather_lanes": lanes * f_local,
            "big_table_scatter_lanes": lanes * f_local,
            # [B]-lane work per owned field against SMALL (cap- or
            # B-sized) operands: compact expand + delta reorder + cumsum.
            "small_operand_lanes": (3 * B * f_local) if cap else 0,
            # Device-built aux only: one [B] stable sort per owned field.
            "aux_sort_lanes": (B * f_local) if (cap and device_aux) else 0,
        },
        ici={
            "a2a_batch": int(B * a2a_cols * recv_batch),
            "allgather_labels_weights": int(8 * B * recv_batch),
        },
    )


def field_sharded_costs(B: int, F: int, k: int, n: int, cap: int = 0,
                        device_aux: bool = False,
                        psum_dtype: str = "float32",
                        model: str = "fm", n_row: int = 1,
                        deep_sharded: bool = False) -> dict:
    """Exact per-chip work + ICI traffic counts for one step of the
    field-sharded fused step of ``model`` ('fm' | 'ffm' | 'deepfm').
    ``cap=0`` = plain (non-compact) path. ``psum_dtype`` is the wire
    dtype of the ACTIVATION collectives (TrainConfig.collective_dtype);
    ids stay int32 and the batch re-shard fp32. ``n_row`` > 1 models
    the 2-D (feat, row) mesh's EXTRA activation collective for FFM (the
    sel psum over ``row`` that completes the ownership-masked partials;
    ``n`` is then the feat extent, total chips = n·n_row). Byte counts
    per activation collective, by construction (field_step.py):

    - fm:     psum of (s[B,k], sq[B], lin[B])             → ring·w·B·(k+2)
    - ffm:    + sel all_to_all [B, f_local, F_pad, k]     → w·B·f_local·f_pad·k·recv
              (+ 2-D: sel psum over row                   → 2(r−1)/r·w·B·f_local·f_pad·k)
              (score psums are 2·[B] — pair, lin)
    - deepfm: fm's psum group + h all_gather [B, f_pad·k] → w·B·f_pad·k·recv
    """
    c = _base_counts(B, F, k, n, cap, device_aux,
                     n_total=n * n_row if n_row > 1 else None)
    w = _WIRE_BYTES[psum_dtype]
    ici = c["ici"]
    if n_row > 1 and model == "fm":
        raise ValueError(
            "n_row adds no FM activation collective to model (the "
            "score psums widen their axis set at the same [B, k+2] "
            "bytes — a ring-factor nuance, not a new term); pass the "
            "TOTAL chip count as n for a 2-D FM estimate"
        )
    row_ring = 2 * (n_row - 1) / n_row if n_row > 1 else 0.0
    if model == "fm":
        ici["psum_scores"] = int(c["ring"] * w * B * (k + 2))
    elif model == "ffm":
        # FFM sel-exchange optimality (VERDICT r4 #4 — the "pair-blocked
        # sel exchange" REFUTATION): the implemented all_to_all already
        # ships exactly the consumed data — split_axis=2 sends chip d
        # only the [B, f_local, f_local_d, k] target blocks it consumes
        # — so the per-chip wire below (≈ w·B·f_local·F_pad·k) is the
        # per-ordered-pair-block-once total, and that total is a LOWER
        # BOUND for exact training: the forward pair term needs the two
        # k-vectors of each cross-chip pair (i, j) to meet once
        # (≥ B·k bytes for one direction), and the backward needs
        # dsel_i[j] = ds·sel_j[i] ON the chip owning i — either sel_j[i]
        # crosses to chip i (the other direction of the same pair) or
        # the computed dsel block of identical size crosses back.
        # Candidate "savings" all tie or lose:
        #   - half-exchange (ship i<j only): saves F²/2 forward blocks,
        #     pays exactly F²/2 dsel return blocks — a wash, plus an
        #     extra collective's latency;
        #   - example-resharding sel (the score-sharded analog): the
        #     re-shard a2a moves the same B·F²k/n per chip, and the
        #     dsel must come BACK to the field owners — 2× the wire;
        #   - pair-block ring pipelining: same bytes, only overlaps the
        #     pair dot products (~0.25 MAC/byte — negligible next to
        #     the wire it rides under).
        # What remains is the wire dtype (bfloat16 halves it — shipped)
        # and weak scaling (per-chip sel bytes divide by n at fixed
        # per-chip batch — --batch-per-chip; see the dryrun's
        # ffm_projected_aggregate_weak_scaling row).
        sel_bytes = w * B * c["f_local"] * c["f_pad"] * k
        ici["a2a_sel"] = int(sel_bytes * c["recv"])
        if n_row > 1:
            ici["psum_sel_row"] = int(row_ring * sel_bytes)
        ici["psum_scores"] = int(c["ring"] * w * B * 2)
    elif model == "deepfm":
        ici["psum_scores"] = int(c["ring"] * w * B * (k + 2))
        if deep_sharded:
            # Example-sharded deep head (TrainConfig.deep_sharded): the
            # h all_gather becomes one forward a2a (each chip ships its
            # [B, f_local·k] columns, receives its [B/n, f_pad·k]
            # example rows — ≈ B·f_local·k bytes either direction), one
            # reverse a2a of the same size for the pullback, and a
            # [B]-scalar deep-score all_gather. The MLP-grad psum is
            # EXCLUDED: its bytes are the (fixed) MLP parameter count ·
            # ring, independent of B — ~4MB at config 5's head vs the
            # ~150MB h terms — and the model carries no MLP-size input.
            a2a_h = int(w * B * c["f_local"] * k * c["recv"])
            ici["a2a_h_fwd"] = a2a_h
            ici["a2a_dh_bwd"] = a2a_h
            ici["allgather_deep_scores"] = int(w * B * c["recv"])
        else:
            ici["allgather_h"] = int(w * B * c["f_pad"] * k * c["recv"])
        if n_row > 1:
            # The h completion psum runs BEFORE the feat all_gather /
            # a2a, on each chip's [B, f_local·k] block (deepfm_step.py)
            # — first-order, comparable to allgather_h.
            ici["psum_h_row"] = int(row_ring * w * B * c["f_local"] * k)
    else:
        raise ValueError(f"unknown model {model!r}")
    ici["total"] = sum(v for kk, v in ici.items() if kk != "total")
    per_chip = c["per_chip"]
    per_chip["ici_bytes_per_step"] = ici
    per_chip["f_local"] = c["f_local"]
    return per_chip


def project_aggregate(single_chip_rate: float, B: int, F: int, k: int,
                      n: int, cap: int = 0, device_aux: bool = False,
                      psum_dtype: str = "float32", model: str = "fm",
                      score_sharded: bool = False, n_row: int = 1,
                      deep_sharded: bool = False,
                      dispatch_ms: float = 2.5,
                      replicated_score_ms_per_128k: float = 2.0,
                      measured_B: int = 131072,
                      ici_gbps: float = 100.0) -> dict:
    """Projected n-chip aggregate throughput from a MEASURED single-chip
    rate. Every assumption is a named argument echoed in the output:

    - ``dispatch_ms``: per-step dispatch overhead (bench_micro
      ``dispatch``: 2.5ms on the attachment this model was built
      against; 0.28ms measured on the v5e host, PERF.md "Chip
      bring-up" — the default is not yet re-priced).
    - ``replicated_score_ms_per_128k``: the [B, k] score/dscores math
      every chip repeats on the full global batch, measured at
      ``measured_B`` (≈ one read pass over s·s + loss grads; estimated
      from the measured 35-90 GB/s effective stream rate). Scaled
      LINEARLY with B — in weak scaling this term grows with n, which
      is exactly why it is separated from the shardable remainder
      (round-3's constant-input model under-counted it).
    - ``ici_gbps``: assumed effective per-chip ICI bandwidth. Not
      measurable here; 100 GB/s is conservative for a v5e torus link
      set (nominal is several hundred GB/s).

    The measured single-chip rate is the FM step's; for 'ffm'/'deepfm'
    pass that model's own measured rate (bench.py variants) — the
    traffic model is per-model either way.

    ``score_sharded`` (TrainConfig.score_sharded, FM only): the score/
    dscores math shards over examples, so ``t_rep`` moves into the
    divided term and one [B] fp32 dscores all_gather joins the ICI
    counts — the lever that removes the model's only non-shardable
    B-proportional term.
    """
    if deep_sharded and model != "deepfm":
        raise ValueError("deep_sharded is the DeepFM step's lever")
    costs = field_sharded_costs(B, F, k, n, cap, device_aux,
                                psum_dtype=psum_dtype, model=model,
                                n_row=n_row, deep_sharded=deep_sharded)
    t1 = B / single_chip_rate
    t_fixed = dispatch_ms / 1e3
    t_rep = replicated_score_ms_per_128k / 1e3 * (B / measured_B)
    t_sharded = max(t1 - t_fixed - t_rep, 0.0)
    if score_sharded:
        if model != "fm":
            raise ValueError("score_sharded is the FM step's lever")
        ici = costs["ici_bytes_per_step"]
        ici["allgather_dscores"] = int(4 * B * (n - 1) / n)
        ici["total"] += ici["allgather_dscores"]
        t_sharded = t_sharded + t_rep
        t_rep = 0.0
    t_ici = costs["ici_bytes_per_step"]["total"] / (ici_gbps * 1e9)
    t_n = t_fixed + t_rep + t_sharded / n + t_ici
    return {
        "model": "t(n) = t_fixed + t_rep(B) + (T1 - t_fixed - t_rep)/n"
                 " + ici/bw",
        "inputs": {
            "single_chip_rate": round(single_chip_rate),
            "B": B, "F": F, "k": k, "n": n, "cap": cap,
            "device_aux": device_aux, "psum_dtype": psum_dtype,
            "step_model": model, "score_sharded": score_sharded,
            "deep_sharded": deep_sharded, "n_row": n_row,
            "dispatch_ms": dispatch_ms,
            "replicated_score_ms_per_128k": replicated_score_ms_per_128k,
            "ici_gbps": ici_gbps,
        },
        "per_chip": costs,
        "t_single_chip_ms": round(t1 * 1e3, 2),
        "t_projected_ms": round(t_n * 1e3, 2),
        "projected_aggregate_samples_per_sec": round(B / t_n),
        "projected_per_chip_samples_per_sec": round(
            B / t_n / (n * n_row)),
    }
