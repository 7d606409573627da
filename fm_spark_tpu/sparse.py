"""Fused sparse train steps: row updates in place, no dense gradient.

Why this exists (SURVEY.md §6 feasibility math): at Criteo scale the FM
table is 10M × 64 (2.6 GB fp32). The generic ``jax.grad`` + optax path
materializes a *dense* gradient table every step — ~8 GB of HBM traffic for
a parameter update that only touches ``batch × nnz ≤ 5M`` rows. For plain
SGD (the reference's optimizer, and every body's here but one) the update
is a pure scatter-add; the FieldFFM body also takes per-coordinate AdaGrad
(:func:`make_field_ffm_adagrad_body`: coalesce, read, rule, set — each
unique row and its accumulator row once). Either way the
step computes the analytic per-row gradients — exactly the reference's
``computeGradient`` rule, ``x_i(s_f − v_{i,f}x_i)`` per BASELINE.json:5 —
and applies them in place with ``.at[ids].add``:

    HBM traffic/step ≈ gather(B·nnz·k) + scatter(2·B·nnz·k)  ≪  3·n·k.

Semantics vs the dense path:
- reg == 0: bitwise-equal math (same sums, same schedule), verified in
  tests/test_sparse.py.
- reg > 0: L2 decay is applied *lazily* — only rows touched by the batch
  decay, scaled by nothing (the standard lazy-regularization trade-off in
  sparse FM/FTRL training). Exactness with the reference's global decay is
  therefore approximate; use the dense path when that matters.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from fm_spark_tpu.models import rows as rows_lib
from fm_spark_tpu.ops import losses as losses_lib
from fm_spark_tpu.train import TrainConfig


def _lr_at(config: TrainConfig):
    """The reference's 1-based ``stepSize/√iter`` schedule (or constant),
    as a traced-step function — single definition for every fused body."""
    if config.lr_schedule == "inv_sqrt":
        return lambda i: config.learning_rate / jnp.sqrt(
            i.astype(jnp.float32) + 1.0
        )
    if config.lr_schedule == "constant":
        return lambda i: jnp.float32(config.learning_rate)
    raise ValueError(f"unknown lr_schedule {config.lr_schedule!r}")


# What a fused body that writes its tables by plain SGD says of any other
# table rule (one wording for the FieldFM body and the mesh steps).
_SGD_ONLY = (
    "{what} writes its tables by plain SGD only (optimizer='sgd', not "
    "{got!r}); per-coordinate 'adagrad' on the tables is implemented by "
    "the one-chip FieldFFM body (sparse.make_field_ffm_adagrad_body) and, "
    "with 'ftrl', by the flat-table optim.make_sparse_adaptive_step")


def _sr_base_key(config: TrainConfig):
    return jax.random.key(config.seed + 0x5EED)


def _check_host_dedup(config: TrainConfig, loss: str):
    """Shared host_dedup/compact preconditions for the fused bodies
    (single definition so the factories can never drift). ``loss`` is the
    step's loss name: the 'error' overflow policy's -inf sentinel is only
    unambiguous for non-negative losses (_fold_overflow), so membership
    in the known-non-negative set is asserted here (ADVICE r4)."""
    if config.compact_device:
        if config.compact_cap <= 0:
            raise ValueError("compact_device requires compact_cap > 0")
        if (config.compact_overflow == "error"
                and loss not in losses_lib.NON_NEGATIVE_LOSSES):
            raise ValueError(
                "compact_overflow='error' signals overflow by poisoning "
                "the loss to -inf, which is only unambiguous for "
                "non-negative losses "
                f"{sorted(losses_lib.NON_NEGATIVE_LOSSES)}; loss "
                f"{loss!r} is not in that set — add it to "
                "losses.NON_NEGATIVE_LOSSES only after verifying it "
                "cannot go negative (or use compact_overflow='drop')"
            )
        if config.host_dedup:
            raise ValueError(
                "compact_device builds the aux in-step; host_dedup is "
                "exclusive with it"
            )
    if config.compact_cap > 0 and not (
        config.host_dedup or config.compact_device
    ):
        raise ValueError(
            "compact_cap requires host_dedup=True or compact_device=True"
        )
    if config.compact_overflow not in ("error", "drop", "split"):
        raise ValueError(
            f"unknown compact_overflow {config.compact_overflow!r}"
        )
    if config.compact_overflow != "error" and config.compact_cap <= 0:
        # Without a cap there is nothing to overflow — accepting the
        # policy would be a silent no-op (no-silent-fallback rule).
        raise ValueError(
            f"compact_overflow={config.compact_overflow!r} has no "
            "effect without compact_cap > 0"
        )
    if config.compact_overflow == "drop" and not config.compact_device:
        raise ValueError(
            "compact_overflow='drop' is the device-side policy; the "
            "host aux builder detects overflow before the step (use "
            "'error' or 'split')"
        )
    if config.compact_overflow == "split" and config.compact_device:
        raise ValueError(
            "compact_overflow='split' is the host-pipeline policy; the "
            "device path cannot reshape a batch in-step (use 'error' "
            "or 'drop')"
        )
    if config.segtotal_pallas and config.compact_cap <= 0:
        # The kernel replaces the compact update's segment-sum stage;
        # without a cap there is no such stage (no-silent-fallback).
        raise ValueError(
            "segtotal_pallas requires the compact path (compact_cap > 0)"
        )
    if not (config.host_dedup or config.compact_device):
        return
    if config.sparse_update not in ("dedup", "dedup_sr"):
        raise ValueError(
            "host_dedup/compact_device require sparse_update='dedup' "
            "or 'dedup_sr'"
        )
    if config.use_pallas:
        raise ValueError("host_dedup/compact_device and use_pallas are "
                         "exclusive")


def _compact_gather_all(tables, aux, cd, mask_overflow=False, width=None):
    """COMPACT forward table access (``config.compact_cap`` > 0): gather
    each field's ``cap`` unique rows once from the big table, expand
    per-lane rows from the small [cap, w] buffer via the inverse map
    (ops/scatter.compact_aux or device_compact_aux). Returns ``(urows,
    rows)`` — ``urows`` in storage dtype (the dedup_sr old-row operand),
    ``rows`` in compute dtype, shaped exactly like :func:`_gather_all`'s
    output so the bodies' math is unchanged.

    ``mask_overflow`` (device-built aux only): lanes whose segment index
    reached past ``cap`` — possible because the device builder cannot
    raise — expand to ZERO rows (absent-feature drop semantics) instead
    of whatever the clipped expansion gather returns. The host builder
    guarantees ``inv < cap``, so its callers skip the extra [B, w]
    multiply.

    ``width``: the model's row width where a table may be held wider
    (models/rows.py says why); the ``cap`` whole rows are gathered, then
    cut, so ``urows`` and ``rows`` are ``width`` wide."""
    from fm_spark_tpu.ops import scatter as scatter_lib

    useg, inv = aux[0], aux[4]
    cap = useg.shape[-1]
    urows = [
        scatter_lib.compact_gather(t, useg[f])[:, :width]
        for f, t in enumerate(tables)
    ]
    rows = []
    for f, u in enumerate(urows):
        r = u.astype(cd).at[inv[f]].get(mode="clip")
        if mask_overflow:
            r = r * (inv[f] < cap)[:, None].astype(cd)
        rows.append(r)
    return urows, rows


def _compact_apply_all(tables, g_fulls, urows, config: TrainConfig,
                       sr_base_key, step_idx, lr, aux, field_offset=0):
    """COMPACT update: one cumsum-derived segment total and one
    unique+sorted cap-lane write per field (ops/scatter.compact_apply);
    the counterpart of :func:`_apply_field_updates` for
    ``config.compact_cap`` > 0. ``urows`` is :func:`_compact_gather_all`'s
    first output (no second gather for the SR write-back).
    ``field_offset`` shifts the SR key stream for the field-sharded
    caller (global field = offset + local f), exactly like
    :func:`_apply_field_updates`."""
    from fm_spark_tpu.ops import scatter as scatter_lib

    new = []
    for f, g_full in enumerate(g_fulls):
        key = (
            scatter_lib.sr_key(sr_base_key, step_idx, field_offset + f)
            if config.sparse_update == "dedup_sr"
            else None
        )
        new.append(
            scatter_lib.compact_apply(
                tables[f], -lr * g_full, tuple(a[f] for a in aux),
                config.sparse_update, key, urows[f],
                segtotal_pallas=config.segtotal_pallas,
            )
        )
    return new


def _device_compact_aux_all(ids, cap: int, f_count: int,
                            extra_segs=None):
    """In-step compact aux for ``f_count`` local id columns
    (ops/scatter.device_compact_aux per field, stacked to the host
    builder's ``[F, ...]`` layout so every downstream compact helper is
    shared verbatim). Returns ``(aux, ovf)`` — ``ovf`` is the worst
    per-field REAL-segment overflow past ``cap`` (0 = every field fit).
    ``extra_segs`` ([f_count] int) discounts segments that are dropped
    BY DESIGN — the 2-D mesh's ownership-mask sentinel segment sorts
    last, so when it spills past ``cap`` that is correct masking, not
    data loss."""
    from fm_spark_tpu.ops import scatter as scatter_lib

    # vmap over the field axis instead of a Python loop: ONE batched
    # [f_count, B] sort (plus batched scatters/cumsums) replaces
    # f_count separately-traced argsort chains — smaller HLO, one sort
    # dispatch. The aux is all-int32, so the vmapped form is BITWISE
    # identical to the per-field loop (pinned against the host builder
    # in tests/test_compact_device.py); outputs arrive already stacked
    # in the host builder's [F, ...] layout.
    aux, nsegs = jax.vmap(
        lambda col: scatter_lib.device_compact_aux(col, cap),
        in_axes=1,
    )(ids[:, :f_count])
    if extra_segs is not None:
        nsegs = nsegs - extra_segs
    ovf = jnp.maximum(jnp.max(nsegs) - cap, 0)
    return aux, ovf


def _fold_overflow(loss, ovf, config: TrainConfig):
    """Overflow policy for the device-compact path: 'error' poisons the
    loss to MINUS infinity (the training loop's periodic loss fetch
    turns that into an actionable failure — no extra device→host sync
    per step); 'drop' accepts the documented absent-feature semantics
    silently. −inf, not +inf: every shipped loss (logistic, squared,
    hinge) is a weighted mean of non-negative terms, so a genuinely
    diverging run reaches +inf but never −inf — the sentinel is
    unambiguous (ADVICE r3: a diverging run must not be reported as a
    cap overflow)."""
    if ovf is None or config.compact_overflow == "drop":
        return loss
    return jnp.where(ovf > 0, jnp.float32(-jnp.inf), loss)


# The parameter keys of the tables the fused bodies below read and write:
# their rows are cut to the model's width on read (_rows_for) and padded
# back on write (ops/scatter._to_table_width), so the one-chip loop may
# hold exactly these wider than the model (models/rows.hold). A
# ``fused_linear=False`` spec's ``v`` / ``w`` are neither cut nor padded
# by its body and stay as they are.
FUSED_TABLE_KEYS = ("vw",)


def _rows_for(compact, tables, aux, cd, gat, ids, width,
              device_cap: int = 0):
    """The fused bodies' shared forward table access: the compact
    cap-lane path (host- or device-built aux) or the plain per-lane
    gather. Returns ``(urows, rows, aux, ovf)`` — ``urows``/``ovf`` are
    None on the plain path; ``aux`` is echoed (host) or freshly built
    (device) so the update half consumes one object either way. One
    definition so the three fused factories (FM/FFM/DeepFM) can never
    drift. ``urows`` and ``rows`` are ``width`` wide, the model's,
    whatever the tables are: a lane-padded table (models/rows.py has
    why the one-chip loop holds one) gives up whole rows (asking the
    gather for the leading columns only, ``table[idx, :width]``,
    compiles to a serial loop of one dynamic-slice a row on the TPU,
    10-50x slower: PERF.md §6, PR 27) and they are cut here, so the
    bodies' arithmetic never sees the padding; the writes (ops/scatter)
    pad it back."""
    if device_cap > 0:
        aux, ovf = _device_compact_aux_all(ids, device_cap,
                                           len(tables))
        urows, rows = _compact_gather_all(tables, aux, cd,
                                          mask_overflow=True, width=width)
        return urows, rows, aux, ovf
    if compact:
        urows, rows = _compact_gather_all(tables, aux, cd, width=width)
        return urows, rows, aux, None
    rows = [r[:, :width] for r in _gather_all(gat, tables, ids, cd)]
    return None, rows, aux, None


def _updates_for(compact, tables, ids, g_fulls, rows, urows,
                 config: TrainConfig, sr_base_key, step_idx, lr, aux):
    """The fused bodies' shared update dispatch, counterpart of
    :func:`_rows_for` (same single-definition rationale)."""
    if compact:
        return _compact_apply_all(
            tables, g_fulls, urows, config, sr_base_key, step_idx, lr, aux,
        )
    return _apply_field_updates(
        tables, ids, g_fulls, rows, config, sr_base_key, step_idx, lr,
        aux=aux,
    )


def _collective_dtype(config: TrainConfig):
    """Validate ``config.collective_dtype`` and return the wire dtype
    for the sharded steps' activation collectives (None = no cast).
    Single definition shared by every sharded factory."""
    if config.collective_dtype == "float32":
        return None
    if config.collective_dtype == "bfloat16":
        return jnp.bfloat16
    raise ValueError(
        f"unknown collective_dtype {config.collective_dtype!r} "
        "(expected 'float32' or 'bfloat16')"
    )


def _psum_wire(x, axes, wire, cd):
    """The sharded forwards' wire-dtype allreduce: cast to the wire
    dtype for the collective, back to compute dtype on arrival (plain
    psum when no wire override). One definition so the FM and FFM
    forwards can never diverge on the wire contract."""
    if wire is None:
        return jax.lax.psum(x, axes)
    return jax.lax.psum(x.astype(wire), axes).astype(cd)


def _reject_collective_dtype(config: TrainConfig, what: str):
    """Guard for factories that do not implement the wire-precision
    knob (single-chip programs have no collectives; the dense optax
    step's grad psum has a different precision contract): fail loudly
    instead of silently training at a precision the caller did not get
    (no-silent-fallback rule)."""
    if config.collective_dtype != "float32":
        raise ValueError(
            f"collective_dtype={config.collective_dtype!r} is not "
            f"supported by {what}; it is a field-sharded-step knob"
        )


def _s1_and_rv(s, n_lanes, k, cd, use_linear: bool, config: TrainConfig):
    """The fused g_full construction's shared operands: ``s1`` =
    ``[s, lin_on]`` ([B, k+1], col k carrying 1/0 for the linear term)
    and ``rv`` = the per-column reg vector (factor cols → reg_factors,
    col k → reg_linear; None when both regs are off, matching the
    conditional add). ONE definition consumed by :func:`_gfull_grads`
    (the XLA reference) and :func:`_fused_compact_updates` (the Pallas
    backward's host-side operands) — the fp32 bit-exactness contract
    between them rests on these never forking."""
    lin_on = 1.0 if use_linear else 0.0
    s1 = jnp.concatenate(
        [s, jnp.full((n_lanes, 1), lin_on, cd)], axis=1)
    rv = None
    if config.reg_factors or config.reg_linear:
        rv = jnp.asarray(
            [config.reg_factors] * k
            + [config.reg_linear if use_linear else 0.0], cd)
    return s1, rv


def _gfull_grads(dscores, vals_c, s, xv_fulls, rows, touched, k, cd,
                 use_linear: bool, config: TrainConfig, extra=None):
    """The fused g_full construction (``config.gfull_fused``), shared by
    the single-chip and field-sharded FM/DeepFM bodies so the numerics
    can never diverge: per field,

        g_full = (ds·(s1 − mask·xv_full) + extra_f)·x + rv·rows·touched

    with ``s1 = [s, lin_on]`` built ONCE — col f<k gives
    ``ds·x·(s_f − xv_f)`` (the reference's computeGradient rule, plus
    the deep head's pullback when ``extra`` is set), col k gives
    ``ds·x·lin_on`` — the same arithmetic as the per-field
    ``concat([g_v, g_l])`` construction up to association (the shared
    ·x factors right-distribute here: one [B, k+1] multiply instead of
    two; ≤ a few ULP under XLA contraction, tests/test_gfull.py), with
    no per-field concat copy pass. ``jnp.where`` (not ·mask) so a
    non-finite factor row cannot poison the linear column. ``rv`` is
    the per-column reg vector (factor cols → reg_factors, col k →
    reg_linear), so every reg split stays column-exact. ``extra``
    (DeepFM) is the deep-head pullback as ONE zero-padded
    [B, F_local, k+1] tensor (col k zero — the head never touches the
    linear weight), built with a single pad instead of F concats."""
    s1, rv = _s1_and_rv(s, dscores.shape[0], k, cd, use_linear, config)
    colmask = jnp.arange(k + 1) < k
    g_fulls = []
    for f in range(len(rows)):
        base = dscores[:, None] * (
            s1 - jnp.where(colmask, xv_fulls[f], jnp.zeros((), cd)))
        if extra is not None:
            base = base + extra[:, f]
        g = base * vals_c[:, f : f + 1]
        if rv is not None:
            g = g + rv * rows[f] * touched[:, None]
        g_fulls.append(g)
    return g_fulls


def _reject_score_sharded(config: TrainConfig, what: str):
    """Guard for factories that do not implement the score-sharded
    backward (it is the FM sharded step's lever; see
    TrainConfig.score_sharded): fail loudly instead of silently
    computing replicated scores (no-silent-fallback rule)."""
    if config.score_sharded:
        raise ValueError(
            f"score_sharded is implemented for the field-sharded FM "
            f"step only, not {what}"
        )


def _reject_deep_sharded(config: TrainConfig, what: str):
    """Guard for factories that do not implement the example-sharded
    deep head (the field-sharded DeepFM step's lever; see
    TrainConfig.deep_sharded): fail loudly instead of silently running
    the replicated head (no-silent-fallback rule)."""
    if config.deep_sharded:
        raise ValueError(
            f"deep_sharded is implemented for the field-sharded DeepFM "
            f"step only, not {what}"
        )


def _reject_gfull(config: TrainConfig, what: str):
    """Guard for step factories that do not implement the gfull_fused
    backward: hard-fail instead of silently training with the concat
    construction (no-silent-fallback rule)."""
    if config.gfull_fused:
        raise ValueError(
            f"gfull_fused is implemented for the FieldFM and "
            f"FieldDeepFM fused bodies, not {what}"
        )


def _reject_sel_blocked(config: TrainConfig, what: str):
    """Guard for step factories that have no ``sel`` tensor to block
    (everything but the FFM bodies): hard-fail instead of silently
    ignoring the flag (no-silent-fallback rule)."""
    if config.sel_blocked:
        raise ValueError(
            f"sel_blocked is the FieldFFM fused body's lever (it blocks "
            f"the [B, F, F, k] interaction tensor), not {what}"
        )


def fused_embed_plan(spec, config: TrainConfig):
    """Resolve ``TrainConfig.fused_embed`` against (spec, config,
    backend): returns ``(family, reason)`` — ``family`` is the fused
    Pallas kernel family that will serve this step,
    ``'fm_compact_bwd'`` (the FieldFM compact backward,
    ops/pallas_fused.fm_bwd_segment_totals) or ``'ffm_sel'`` (the
    sel-blocked FieldFFM interaction kernels), or None with ``reason``
    naming why the XLA path runs instead.

    The SINGLE decision point for the lever: the step factories, the
    CLI's fallback notice, and bench.py's skip-fallback-legs guard all
    consult it — so an ``'auto'`` fallback is silent only in the step's
    outputs, never in its provenance."""
    from fm_spark_tpu.models.field_ffm import FieldFFMSpec
    from fm_spark_tpu.models.field_fm import FieldFMSpec

    if config.fused_embed not in ("off", "auto", "require"):
        raise ValueError(
            f"unknown fused_embed {config.fused_embed!r} "
            "(expected 'off', 'auto', or 'require')")
    if config.fused_embed == "off":
        return None, "fused_embed='off'"
    from fm_spark_tpu.ops import pallas_fused

    if type(spec) is FieldFMSpec:
        if config.compact_cap <= 0:
            return None, ("the fused FM backward rides the compact "
                          "update; it needs compact_cap > 0")
        if not spec.fused_linear:
            return None, "the fused FM backward needs fused_linear=True"
        reason = pallas_fused.fm_bwd_supported(
            config.compact_cap, spec.rank + 1,
            jnp.dtype(spec.pdtype).itemsize)
        if reason:
            return None, reason
        return "fm_compact_bwd", None
    if type(spec) is FieldFFMSpec:
        if not config.sel_blocked:
            return None, ("the Pallas FFM kernels mirror the "
                          "sel-blocked body (set sel_blocked=True)")
        reason = pallas_fused.ffm_sel_supported(
            spec.num_fields, spec.rank, jnp.dtype(spec.cdtype).itemsize)
        if reason:
            return None, reason
        return "ffm_sel", None
    return None, f"no fused kernel family for {type(spec).__name__}"


def _resolve_fused_embed(spec, config: TrainConfig):
    """Factory-side resolution of the lever: the plan's family (or
    None on 'off'/'auto' fallback), with ``'require'`` escalated to the
    structured kernel-unavailable error so an attachment that cannot
    serve the kernel fails actionably instead of silently measuring
    the XLA path."""
    family, reason = fused_embed_plan(spec, config)
    if family is None and config.fused_embed == "require":
        from fm_spark_tpu.ops import PallasUnavailable

        raise PallasUnavailable(
            f"fused_embed='require' cannot be served: {reason}")
    return family


def _reject_fused_embed_require(config: TrainConfig, what: str):
    """Guard for step factories outside the fused Pallas families (the
    sharded steps, the dense paths, the flat-table FM step):
    ``fused_embed='auto'`` resolves to the XLA path there — that IS the
    auto contract, queryable via :func:`fused_embed_plan` — but an
    explicit ``'require'`` must hard-fail instead of silently training
    without the kernel (no-silent-fallback rule)."""
    if config.fused_embed not in ("off", "auto", "require"):
        raise ValueError(
            f"unknown fused_embed {config.fused_embed!r} "
            "(expected 'off', 'auto', or 'require')")
    if config.fused_embed == "require":
        raise ValueError(
            f"fused_embed='require' is served by the single-chip "
            f"FieldFM compact backward and sel-blocked FieldFFM fused "
            f"bodies, not {what}; use 'auto' for fallback-to-XLA "
            "semantics")


def _reject_embed_tier_require(config: TrainConfig, what: str):
    """Guard for step factories that keep their tables fully
    HBM-resident: ``embed_tier='auto'`` falls back to in-HBM tables
    there — queryably, via :func:`fm_spark_tpu.embed.tier_plan` — but
    an explicit ``'require'`` must hard-fail instead of silently
    training without the tiered store (the ``fused_embed`` lever's
    no-silent-fallback rule, applied to the memory hierarchy)."""
    if config.embed_tier not in ("off", "auto", "require"):
        raise ValueError(
            f"unknown embed_tier {config.embed_tier!r} "
            "(expected 'off', 'auto', or 'require')")
    if config.embed_tier == "require":
        raise ValueError(
            f"embed_tier='require' is served by the tiered flat-FM "
            f"trainer (fm_spark_tpu.embed.TieredTrainer), not {what}; "
            "use 'auto' for fallback-to-in-HBM semantics")


def _fused_compact_updates(tables, urows, aux, s, dscores, vals_c,
                           touched, config: TrainConfig, sr_base_key,
                           step_idx, lr, k, cd, use_linear: bool):
    """COMPACT update via the fused Pallas backward
    (ops/pallas_fused.fm_bwd_segment_totals): per field, the sorted
    scalar streams (dscores, the field's x, touched, dense segment
    ranks) plus the shared ``[s, lin_on]`` rows drive ONE kernel that
    rebuilds ``-lr·g_full`` on-chip from the VMEM-resident ``urows``
    block and accumulates the per-segment totals in the same pass — the
    F × [B, k+1] gradient set of :func:`_gfull_grads` (ROADMAP item 4's
    dominant HBM term) never materializes off-chip. The totals land
    through ``scatter.compact_apply_totals`` (the same write half as
    ``compact_apply``), so fp32 results are BIT-EXACT against the
    gfull_fused + segtotal_pallas reference composition
    (tests/test_pallas_fused.py)."""
    from fm_spark_tpu.ops import pallas_fused, pallas_interpret
    from fm_spark_tpu.ops import scatter as scatter_lib

    order, inv = aux[3], aux[4]
    cap = aux[0].shape[-1]
    s1, rv = _s1_and_rv(s, dscores.shape[0], k, cd, use_linear, config)
    interpret = pallas_interpret()
    new = []
    for f in range(len(tables)):
        o = order[f]
        totals = pallas_fused.fm_bwd_segment_totals(
            urows[f], s1[o], dscores[o], vals_c[o, f], touched[o],
            inv[f][o], -lr, rv, k=k, cap=cap, interpret=interpret)
        key = (
            scatter_lib.sr_key(sr_base_key, step_idx, f)
            if config.sparse_update == "dedup_sr"
            else None
        )
        new.append(
            scatter_lib.compact_apply_totals(
                tables[f], totals, tuple(a[f] for a in aux),
                config.sparse_update, key, urows[f],
            )
        )
    return new


def _reject_host_aux(config: TrainConfig, what: str):
    """Guard for step factories that take no aux operand (the sharded
    steps): hard-fail an explicit fast-path request rather than
    silently training without it. Single definition so a future
    factory cannot forget the check's wording or semantics."""
    if config.host_dedup or config.compact_cap:
        raise ValueError(
            f"the HOST-built dedup/compact aux is not supported by "
            f"{what}; drop host_dedup (compact_device=True is the "
            "form that composes with sharded layouts where supported)"
        )
    if config.segtotal_pallas:
        # Requires the compact fused path (cap > 0) — which this
        # factory just rejected above; a bare flag is equally a no-op.
        raise ValueError(
            f"segtotal_pallas rides the compact fused update, which is "
            f"not part of {what}"
        )


def _apply_field_updates(tables, ids, g_fulls, rows, config: TrainConfig,
                         sr_base_key, step_idx, lr, field_offset=0,
                         aux=None):
    """Write ``-lr·g_full`` into each field's table via the configured
    sparse-update mode (ops/scatter.py); shared by the FieldFM, FieldFFM,
    and field-sharded bodies so mode/key semantics can never diverge.
    ``field_offset`` shifts the SR key stream for sharded callers (global
    field index = offset + local f). ``aux`` is the host-precomputed
    dedup tuple of [F, B] arrays (ops/scatter.dedup_aux), sliced per
    field here."""
    from fm_spark_tpu.ops import scatter as scatter_lib

    new = []
    for f, g_full in enumerate(g_fulls):
        key = (
            scatter_lib.sr_key(sr_base_key, step_idx, field_offset + f)
            if config.sparse_update == "dedup_sr"
            else None
        )
        new.append(
            scatter_lib.apply_row_updates(
                tables[f], ids[:, f], -lr * g_full,
                mode=config.sparse_update, key=key, old_rows=rows[f],
                use_pallas=config.use_pallas,
                aux=None if aux is None else tuple(a[f] for a in aux),
            )
        )
    return new


def _gather_fn(config: TrainConfig):
    """Row-gather routing for the fused bodies: XLA ``table[idx]`` or the
    Pallas pipelined-DMA kernel (``config.use_pallas``)."""
    if not config.use_pallas:
        return lambda table, idx: table[idx]
    from fm_spark_tpu.ops.scatter import pallas_gather

    return pallas_gather


def _gather_all(gat, tables, ids, cd):
    """One routed gather per field, cast to compute dtype — the single
    definition of the fused bodies' ``rows`` idiom (five call sites across
    sparse.py and parallel/field_step.py must not drift)."""
    return [gat(tables[f], ids[:, f]).astype(cd) for f in range(len(tables))]


def make_field_sparse_sgd_body(spec, config: TrainConfig):
    """Unjitted fused-step body for :class:`FieldFMSpec` (see the jitted
    wrapper :func:`make_field_sparse_sgd_step`); exposed separately so
    callers (bench, training loops) can roll many steps into one
    ``lax.fori_loop`` program and amortize dispatch overhead."""
    from fm_spark_tpu.models.field_fm import FieldFMSpec

    if type(spec) is not FieldFMSpec:
        raise ValueError("expected a FieldFMSpec")
    if config.optimizer != "sgd":
        raise ValueError(_SGD_ONLY.format(what="the FieldFM body",
                                          got=config.optimizer))
    if config.sparse_update != "scatter_add" and not spec.fused_linear:
        raise ValueError("dedup/dedup_sr modes require fused_linear=True")
    if config.use_pallas and not spec.fused_linear:
        raise ValueError("use_pallas requires fused_linear=True")
    _reject_embed_tier_require(config, "the single-chip FieldFM body")
    _check_host_dedup(config, spec.loss)
    compact = config.compact_cap > 0
    if compact and not spec.fused_linear:
        raise ValueError("compact_cap requires fused_linear=True")
    if config.gfull_fused and not spec.fused_linear:
        raise ValueError("gfull_fused targets the fused-linear g_full "
                         "construction; it requires fused_linear=True")
    _reject_collective_dtype(config, "the single-chip FieldFM body")
    _reject_score_sharded(config, "the single-chip FieldFM body")
    _reject_sel_blocked(config, "the single-chip FieldFM body")
    _reject_deep_sharded(config, "the single-chip FieldFM body")
    # Fused Pallas backward (ISSUE 8): resolved ONCE at build time —
    # 'auto' with no serving kernel family compiles the XLA path (the
    # reason stays queryable via fused_embed_plan), 'require' raises
    # PallasUnavailable here.
    fused_bwd = _resolve_fused_embed(spec, config) == "fm_compact_bwd"
    per_example_loss = losses_lib.loss_fn(spec.loss)
    cd = spec.cdtype
    F = spec.num_fields
    sr_base_key = _sr_base_key(config)
    lr_at = _lr_at(config)
    gat = _gather_fn(config)
    k = spec.rank
    device_cap = config.compact_cap if config.compact_device else 0

    def step(params, step_idx, ids, vals, labels, weights, aux=None):
        if config.host_dedup and aux is None:
            raise ValueError(
                "host_dedup step needs the batch's dedup_aux operand"
            )
        w0 = params["w0"]
        vals_c = vals.astype(cd)
        ovf = None
        if spec.fused_linear:
            # Compact = cap unique rows per field from the big tables,
            # per-lane rows expanded from the small buffers (the
            # [B]-lane work never touches table-sized operands).
            urows, rows, aux, ovf = _rows_for(
                compact, params["vw"], aux, cd, gat, ids,
                spec.table_width, device_cap=device_cap,
            )                                           # F × [B, k+1]
        else:
            urows = None
            rows = spec.gather_rows(params, ids)        # F × [B, width]
        gfull_fused = config.gfull_fused
        if gfull_fused:
            # Full-width x·row products, computed once: cols [:k] are the
            # interaction xv terms, col k is the linear term's l·x — the
            # backward reuses the same buffers so g_full needs no
            # per-field concat (see below). Values are bitwise-identical
            # to the sliced formulation (same elementwise products).
            xv_fulls = [r * vals_c[:, f : f + 1] for f, r in enumerate(rows)]
            xvs = [x[:, :k] for x in xv_fulls]
        else:
            xvs = [r[:, :k] * vals_c[:, f : f + 1] for f, r in enumerate(rows)]
        s = sum(xvs)                                    # [B, k]
        sum_sq = sum(jnp.sum(x * x, axis=1) for x in xvs)
        scores = 0.5 * (jnp.sum(s * s, axis=1) - sum_sq)
        if spec.use_linear:
            if gfull_fused:
                scores = scores + sum(x[:, k] for x in xv_fulls)
            else:
                if spec.fused_linear:
                    lins = [r[:, k] for r in rows]
                else:
                    lins = [params["w"][f][ids[:, f]].astype(cd)
                            for f in range(F)]
                scores = scores + sum(
                    l * vals_c[:, f] for f, l in enumerate(lins)
                )
        if spec.use_bias:
            scores = scores + w0.astype(cd)

        wsum = jnp.maximum(jnp.sum(weights), 1.0)

        def batch_loss(sc):
            return jnp.sum(per_example_loss(sc, labels) * weights) / wsum

        loss, dscores = jax.value_and_grad(batch_loss)(scores)
        lr = lr_at(step_idx)
        touched = weights > 0

        def factor_grad(f):
            g = dscores[:, None] * vals_c[:, f : f + 1] * (s - xvs[f])
            if config.reg_factors:
                g = g + config.reg_factors * rows[f][:, :k] * touched[:, None]
            return g

        def linear_grad(f):
            g = dscores * vals_c[:, f]
            if config.reg_linear:
                g = g + config.reg_linear * lins[f] * touched
            return g

        if spec.fused_linear:
            if fused_bwd:
                # Fused Pallas backward: -lr·g_full is rebuilt on-chip
                # from the sorted scalar streams + the resident urows
                # block and segment-summed in the SAME kernel — the
                # F × [B, k+1] gradient set never touches HBM.
                new_vw = _fused_compact_updates(
                    params["vw"], urows, aux, s, dscores, vals_c,
                    touched, config, sr_base_key, step_idx, lr, k, cd,
                    spec.use_linear,
                )
                out = {"w0": w0, "vw": new_vw}
                if spec.use_bias:
                    out["w0"] = w0 - lr * (
                        jnp.sum(dscores) + config.reg_bias * w0)
                return out, _fold_overflow(loss, ovf, config)
            # ONE row-update per field: interaction grads in cols [:k], the
            # linear grad in col k (zeroed if the linear term is disabled).
            if gfull_fused:
                g_fulls = _gfull_grads(
                    dscores, vals_c, s, xv_fulls, rows, touched, k, cd,
                    spec.use_linear, config,
                )
            else:
                g_fulls = []
                for f in range(F):
                    g_lin = (
                        linear_grad(f)[:, None]
                        if spec.use_linear
                        else jnp.zeros((dscores.shape[0], 1), cd)
                    )
                    g_fulls.append(
                        jnp.concatenate([factor_grad(f), g_lin], axis=1))
            new_vw = _updates_for(
                compact, params["vw"], ids, g_fulls, rows, urows, config,
                sr_base_key, step_idx, lr, aux,
            )
            out = {"w0": w0, "vw": new_vw}
        else:
            new_v = [
                params["v"][f]
                .at[ids[:, f]]
                .add((-lr * factor_grad(f)).astype(spec.pdtype))
                for f in range(F)
            ]
            new_w = (
                [
                    params["w"][f]
                    .at[ids[:, f]]
                    .add((-lr * linear_grad(f)).astype(spec.pdtype))
                    for f in range(F)
                ]
                if spec.use_linear
                else params["w"]
            )
            out = {"w0": w0, "w": new_w, "v": new_v}
        if spec.use_bias:
            out["w0"] = w0 - lr * (jnp.sum(dscores) + config.reg_bias * w0)
        return out, _fold_overflow(loss, ovf, config)

    return step


def make_field_sparse_sgd_step(spec, config: TrainConfig):
    """Jitted fused sparse-SGD step for :class:`FieldFMSpec` — the CTR fast
    path. Per-field small-table gathers/scatters (see field_fm.py for the
    measured rationale); same semantics as :func:`make_sparse_sgd_step`.
    Tables are donated so updates are in-place in HBM."""
    return jax.jit(
        make_field_sparse_sgd_body(spec, config), donate_argnums=(0,)
    )


def make_field_sparse_multistep(spec, config: TrainConfig, n: int):
    """Roll ``n`` fused steps into ONE compiled program (``lax.fori_loop``)
    — the production-loop version of bench.py's dispatch amortization.
    (One dispatch costs 0.28 ms on the v5e — PERF.md "Chip bring-up" —
    against a ~90 ms step, so what this buys there is small; it was
    built when a dispatch cost tens of milliseconds.)

    Works for the pure-SGD fused bodies (FieldFM / FieldFFM — no
    optimizer state in the carry). Returns ``mstep(params, step0, m,
    ids, vals, labels, weights, aux=None) → (params, last_loss)`` over
    batches STACKED on a leading ``[n, ...]`` axis
    (data/pipeline.StackedBatches); ``m ≤ n`` (dynamic) is how many
    stacked steps actually execute — the training loop's tail call passes
    the remainder and the unused slices are never touched. ``step0 + j``
    is the global step fed to the lr schedule and SR keys, so the math is
    IDENTICAL to ``n`` separate step calls (equivalence-tested).
    """
    from fm_spark_tpu.models.field_ffm import FieldFFMSpec

    if n < 1:
        raise ValueError(f"steps per call must be >= 1, got {n}")
    if config.optimizer != "sgd":
        raise ValueError(
            f"the multistep roll carries no optimizer state and takes "
            f"optimizer='sgd', not {config.optimizer!r} ('adagrad' on the "
            "FieldFFM tables runs one step a call: "
            "make_field_ffm_adagrad_step)")
    body = (
        make_field_ffm_sparse_sgd_body(spec, config)
        if isinstance(spec, FieldFFMSpec)
        else make_field_sparse_sgd_body(spec, config)
    )

    @functools.partial(jax.jit, donate_argnums=(0,))
    def mstep(params, step0, m, ids, vals, labels, weights, aux=None):
        def fbody(j, carry):
            p, prev = carry
            a = (
                None if aux is None
                else jax.tree_util.tree_map(lambda x: x[j], aux)
            )
            p, loss = body(p, step0 + j, ids[j], vals[j], labels[j],
                           weights[j], a)
            # Sticky −inf: the compact-overflow 'error' poison
            # (_fold_overflow) must survive to the returned loss even
            # when a later inner step is clean — otherwise a fori roll
            # would silently swallow the failure signal.
            return p, jnp.where(jnp.isneginf(prev), prev, loss)

        return jax.lax.fori_loop(0, m, fbody, (params, jnp.float32(0)))

    return mstep


def _field_ffm_grads(spec, config: TrainConfig):
    """The FieldFFM bodies' shared forward and analytic backward, up to
    the per-lane row gradients: ``grads(params, step_idx, ids, vals,
    labels, weights, aux) -> (loss, dscores, lr, g_fulls, rows, urows,
    aux, ovf)``. What is done with ``g_fulls`` (F x [B, F·k+1], the L2
    term of every occurrence inside) is the caller's: the SGD body
    scatters ``-lr·g``, the AdaGrad body coalesces it per unique row.

    Analytic backward of the field-aware interaction (the reference's
    field-aware `computeGradient` analog, BASELINE.json:10): with
    ``sel[b,i,j] = v[id_i, field j]·x_i``, the pairwise term is
    ``½ Σ_{i≠j} ⟨sel[b,i,j], sel[b,j,i]⟩``, so

        ∂L/∂sel[b,i,j] = dscore_b · sel[b,j,i]   (i ≠ j; diagonal 0)
        ∂L/∂v[id_i, field j] = ∂L/∂sel[b,i,j] · x_i

    — one [B, F, F, k] transpose, then one row update per field, same
    index-op count as the FieldFM step.
    """
    from fm_spark_tpu.models.field_ffm import FieldFFMSpec

    if type(spec) is not FieldFFMSpec:
        raise ValueError("expected a FieldFFMSpec")
    _reject_gfull(config, "the FieldFFM body")
    _reject_embed_tier_require(config, "the single-chip FieldFFM body")
    _reject_collective_dtype(config, "the single-chip FieldFFM body")
    _reject_score_sharded(config, "the single-chip FieldFFM body")
    _reject_deep_sharded(config, "the single-chip FieldFFM body")
    # Pallas sel-blocked kernels (ISSUE 8): resolved once at build time
    # (same contract as the FM body's fused_bwd).
    ffm_pallas = _resolve_fused_embed(spec, config) == "ffm_sel"
    _check_host_dedup(config, spec.loss)
    compact = config.compact_cap > 0
    per_example_loss = losses_lib.loss_fn(spec.loss)
    cd = spec.cdtype
    F, k = spec.num_fields, spec.rank
    lr_at = _lr_at(config)
    gat = _gather_fn(config)

    def grads(params, step_idx, ids, vals, labels, weights, aux):
        if config.host_dedup and aux is None:
            raise ValueError(
                "host_dedup step needs the batch's dedup_aux operand"
            )
        w0 = params["w0"]
        vals_c = vals.astype(cd)
        urows, rows, aux, ovf = _rows_for(
            compact, params["vw"], aux, cd, gat, ids, spec.table_width,
            device_cap=config.compact_cap if config.compact_device else 0,
        )                                               # F × [B, F·k+1]
        rstk = None
        if ffm_pallas:
            # Pallas sel-blocked kernels (ISSUE 8): the same per-owner-
            # field loop as the XLA sel_blocked branch below, but the
            # [T, F, k] sel/selT pair is GUARANTEED tile-resident inside
            # the kernel instead of relying on XLA fusing the blocked
            # slices — loops mirror the XLA body operation-for-operation
            # so fp32 results are bit-exact (tests/test_pallas_fused.py).
            from fm_spark_tpu.ops import pallas_fused, pallas_interpret

            interp = pallas_interpret()
            rstk = jnp.stack([r[:, : F * k] for r in rows], axis=1)
            scores = 0.5 * pallas_fused.ffm_sel_scores(
                rstk, vals_c, interpret=interp)
        elif config.sel_blocked:
            # Per-owner-field blocks: sel[b, i, j] = Rv[i][b, j] * x_i
            # and its transpose-slice selT_i[b, j] = Rv[j][b, i] * x_j
            # are built on the fly from the (already needed) gathered
            # rows — the [B, F, F, k] sel tensor never exists; the
            # FORWARD's largest live array is one [B, F, k] pair.
            # (The backward below still accumulates the per-field
            # gradient set dvs — F × [B, F·k], the same total bytes as
            # the default body's dv — so the lever removes the sel/dsel
            # materialization traffic, not the gradient set.) Unrolled
            # over the static F (≤ ~40): each iteration is a handful
            # of fused slice/multiply/reduce ops.
            Rv = [r[:, : F * k].reshape(-1, F, k) for r in rows]

            def _selT(i):
                return jnp.stack(
                    [Rv[j][:, i, :] for j in range(F)], axis=1
                ) * vals_c[:, :, None]                  # [B, F, k]

            acc = jnp.zeros_like(vals_c[:, 0])
            for i in range(F):
                sel_i = Rv[i] * vals_c[:, i, None, None]  # [B, F, k]
                selT_i = _selT(i)
                prod = jnp.sum(sel_i * selT_i, axis=-1)   # [B, F]
                acc = acc + jnp.sum(prod, axis=1) - prod[:, i]
            scores = 0.5 * acc
        else:
            sel = spec._sel(rows, vals_c)               # [B, F, F, k]
            a = jnp.sum(sel * jnp.swapaxes(sel, 1, 2), axis=-1)
            diag = jnp.trace(a, axis1=1, axis2=2)
            scores = 0.5 * (jnp.sum(a, axis=(1, 2)) - diag)
        if spec.use_linear:
            lins = [r[:, F * k] for r in rows]
            scores = scores + sum(
                l * vals_c[:, i] for i, l in enumerate(lins)
            )
        if spec.use_bias:
            scores = scores + w0.astype(cd)

        wsum = jnp.maximum(jnp.sum(weights), 1.0)

        def batch_loss(sc):
            return jnp.sum(per_example_loss(sc, labels) * weights) / wsum

        loss, dscores = jax.value_and_grad(batch_loss)(scores)
        lr = lr_at(step_idx)
        touched = weights > 0

        if ffm_pallas:
            # The Pallas dvs backward: dsel stays tile-resident; only
            # the per-owner-field gradient set the scatter consumes is
            # written (stacked [B, F, F·k], sliced per field below).
            from fm_spark_tpu.ops import pallas_fused

            dvs_stk = pallas_fused.ffm_sel_bwd(
                rstk, vals_c, dscores.astype(cd), interpret=interp)
            dvs = [dvs_stk[:, i, :] for i in range(F)]
        elif config.sel_blocked:
            # d/dsel[b, i, j] = ds_b · sel[b, j, i] (zero diagonal), so
            # per owner i the whole [B, F·k] factor gradient is one
            # recomputed selT_i slice — the [B, F, F, k] dsel tensor is
            # never materialized. The per-field gradients dvs (F ×
            # [B, F·k], all live until _updates_for) ARE — the same
            # set the default body builds.
            ds_cd = dscores.astype(cd)
            dvs = []
            for i in range(F):
                dsel_i = ds_cd[:, None, None] * _selT(i)
                dsel_i = dsel_i.at[:, i, :].set(0)
                dvs.append(
                    (dsel_i * vals_c[:, i, None, None]).reshape(-1, F * k)
                )
        else:
            # d/dsel = ds · selᵀ with a zeroed diagonal.
            dsel = dscores[:, None, None, None] * jnp.swapaxes(sel, 1, 2)
            eye = jnp.eye(F, dtype=cd)[None, :, :, None]
            dsel = dsel * (1.0 - eye)
            # dv[id_i, :, :] = dsel[b, i, :, :] · x_i → flat [B, F·k]
            # per field.
            dv = (dsel * vals_c[:, :, None, None]).reshape(-1, F, F * k)

        g_fulls = []
        for f in range(F):
            g_v = dvs[f] if config.sel_blocked else dv[:, f, :]
            if config.reg_factors:
                g_v = g_v + config.reg_factors * rows[f][:, : F * k] * touched[:, None]
            if spec.use_linear:
                g_l = dscores * vals_c[:, f]
                if config.reg_linear:
                    g_l = g_l + config.reg_linear * lins[f] * touched
            else:
                g_l = jnp.zeros_like(dscores)
            g_fulls.append(jnp.concatenate([g_v, g_l[:, None]], axis=1))
        return loss, dscores, lr, g_fulls, rows, urows, aux, ovf

    return grads


def _new_bias(spec, config: TrainConfig, w0, dscores, lr):
    """The bias after a step: plain SGD under every table rule (one
    scalar needs no per-coordinate rate; optim/ has the contract)."""
    if not spec.use_bias:
        return w0
    return w0 - lr * (jnp.sum(dscores) + config.reg_bias * w0)


def make_field_ffm_sparse_sgd_body(spec, config: TrainConfig):
    """Unjitted fused sparse-SGD body for :class:`FieldFFMSpec`: the
    shared gradients (:func:`_field_ffm_grads`), then one scatter of
    ``-lr·g`` per field."""
    if config.optimizer != "sgd":
        raise ValueError(_SGD_ONLY.format(what="the FieldFFM SGD body",
                                          got=config.optimizer))
    grads = _field_ffm_grads(spec, config)
    compact = config.compact_cap > 0
    sr_base_key = _sr_base_key(config)

    def step(params, step_idx, ids, vals, labels, weights, aux=None):
        loss, dscores, lr, g_fulls, rows, urows, aux, ovf = grads(
            params, step_idx, ids, vals, labels, weights, aux)
        new_vw = _updates_for(
            compact, params["vw"], ids, g_fulls, rows, urows, config,
            sr_base_key, step_idx, lr, aux,
        )
        out = {"w0": _new_bias(spec, config, params["w0"], dscores, lr),
               "vw": new_vw}
        return out, _fold_overflow(loss, ovf, config)

    return step


def make_field_ffm_sparse_sgd_step(spec, config: TrainConfig):
    """Jitted fused sparse-SGD step for :class:`FieldFFMSpec`."""
    return jax.jit(
        make_field_ffm_sparse_sgd_body(spec, config), donate_argnums=(0,)
    )


def make_field_ffm_adagrad_body(spec, config: TrainConfig):
    """Unjitted fused body for :class:`FieldFFMSpec` under
    per-coordinate AdaGrad on every table, as Juan et al. (RecSys 2016,
    Algorithm 1) and libffm train the model. Returns ``(body,
    init_slots)``; ``body(params, slots, step_idx, ids, vals, labels,
    weights) -> (params, slots, loss, stats)``.

    The gradients are the SGD body's (:func:`_field_ffm_grads`). The
    write is not: an adaptive rule reads and writes a row's state, so
    per field the batch's rows are COALESCED first (``ops/scatter
    .coalesce``: each unique row's TOTAL gradient, L2 of every
    occurrence inside), the row and its accumulator row are gathered at
    the unique ids, ``optim.adagrad_rows`` is applied (``G += g²``, then
    the step over the UPDATED ``sqrt(G)``), and both are set back once:
    ``scatter.RULE_CHUNK`` lanes at a time, as many chunks as hold the
    field's unique rows (gather and scatter cost by the lane on the
    chip, and a skewed batch has far fewer unique rows than lanes).
    A coordinate whose total gradient is exactly zero (an FFM row's own
    diagonal block, a lane of zero weight) keeps its bits, row and
    accumulator. No table-shaped temporary exists. The bias keeps plain
    SGD (:func:`_new_bias`).

    ``slots`` is ``{"vw": {"n": [F tables]}}``: one float32 accumulator
    table per parameter table, whatever ``param_dtype`` is, in the form
    its table is held in (``models/rows.hold``; a lane-padded table's
    slot is lane-padded, its padding zero like the table's).
    ``init_slots(params)`` builds them at ``config
    .adagrad_init_accumulator`` (``optim.init_field_slots``).
    ``stats["unique_rows"]`` is what coalescing made of the batch: its
    unique rows summed over the fields.

    The update runs under four named scopes, ``opt/coalesce``,
    ``opt/gather``, ``opt/rule`` and ``opt/write``, which the
    benchmark's ``opt_update_ms`` reads from a device trace."""
    from fm_spark_tpu import optim
    from fm_spark_tpu.ops import scatter as scatter_lib

    if config.optimizer != "adagrad":
        raise ValueError(
            f"the FieldFFM AdaGrad body takes optimizer='adagrad', not "
            f"{config.optimizer!r}")
    # Levers of the SGD write: each names how rows are ADDED (a rounded
    # sum, a Pallas accumulate, an aux or a cap built for an add); the
    # rule coalesces in the step and sets each row once.
    refused = {
        "sparse_update='dedup_sr'": config.sparse_update == "dedup_sr",
        "use_pallas": config.use_pallas,
        "host_dedup": config.host_dedup,
        "compact_device": config.compact_device,
        "compact_cap": config.compact_cap > 0,
        "segtotal_pallas": config.segtotal_pallas,
        "fused_embed": config.fused_embed != "off",
    }
    if any(refused.values()):
        raise ValueError(
            f"{sorted(k for k, v in refused.items() if v)} shape the SGD "
            "bodies' ADDED row updates and are not taken under "
            "optimizer='adagrad': that body coalesces in the step and "
            "sets each unique row and its accumulator row once")
    grads = _field_ffm_grads(spec, config)
    width = spec.table_width

    def init_slots(params):
        return optim.init_field_slots(
            config.optimizer, params, FUSED_TABLE_KEYS,
            config.adagrad_init_accumulator)

    def step(params, slots, step_idx, ids, vals, labels, weights,
             aux=None):
        loss, dscores, lr, g_fulls, _, _, _, _ = grads(
            params, step_idx, ids, vals, labels, weights, aux)
        batch = ids.shape[0]
        chunk = (scatter_lib.RULE_CHUNK
                 if batch % scatter_lib.RULE_CHUNK == 0 else batch)
        new_vw, new_n, unique = [], [], jnp.int32(0)
        for f, g_full in enumerate(g_fulls):
            with jax.named_scope("opt/coalesce"):
                useg, g_bar, n = scatter_lib.coalesce(ids[:, f], g_full)

            def one_chunk(c, held, useg=useg, g_bar=g_bar):
                table, slot = held
                with jax.named_scope("opt/gather"):
                    at = jax.lax.dynamic_slice(useg, (c * chunk,), (chunk,))
                    g = jax.lax.dynamic_slice(
                        g_bar, (c * chunk, 0), (chunk, width))
                    rows_u = scatter_lib.rows_at(table, at)[:, :width]
                    n_u = scatter_lib.rows_at(slot, at)[:, :width]
                with jax.named_scope("opt/rule"):
                    rows_u, n_u = optim.adagrad_rows(rows_u, n_u, g, lr)
                with jax.named_scope("opt/write"):
                    return (scatter_lib.set_rows_at(table, at, rows_u),
                            scatter_lib.set_rows_at(slot, at, n_u))

            # The unique rows lie at the front: as many chunks as hold
            # them (one, at this traffic's skew), the rest never touched.
            table, slot = jax.lax.fori_loop(
                0, (n + chunk - 1) // chunk, one_chunk,
                (params["vw"][f], slots["vw"]["n"][f]))
            new_vw.append(table)
            new_n.append(slot)
            unique = unique + n
        out = {"w0": _new_bias(spec, config, params["w0"], dscores, lr),
               "vw": new_vw}
        return out, {"vw": {"n": new_n}}, loss, {"unique_rows": unique}

    return step, init_slots


def make_field_ffm_adagrad_step(spec, config: TrainConfig):
    """Jitted :func:`make_field_ffm_adagrad_body`, tables and slots
    donated (both are updated in place); ``step.init_opt_state`` builds
    the slots, as the DeepFM step's builds its Adam state."""
    body, init_slots = make_field_ffm_adagrad_body(spec, config)
    _step = jax.jit(body, donate_argnums=(0, 1))

    def step(params, slots, step_idx, ids, vals, labels, weights):
        return _step(params, slots, step_idx, ids, vals, labels, weights)

    step.init_opt_state = init_slots
    return step


def make_field_deepfm_sparse_body(spec, config: TrainConfig):
    """UNJITTED fused hybrid body for :class:`FieldDeepFMSpec` — the CTR
    fast path for config 5 (BASELINE.json:11); exposed separately (like
    the FM/FFM bodies) so the multistep fori roll can carry the optax
    state through its loop. Returns ``(body, init_opt_state)``.

    Embedding tables (the 10M-row side) update via the analytic sparse
    scatter rule — the FM part is the reference's ``x_i(s_f − v_{i,f}x_i)``
    with the deep head's contribution added through one ``jax.vjp`` of
    the MLP wrt its input ``h = concat(xv)``:

        ∂L/∂rows_f[:, :k] = dscores·x_f·(s − xv_f)  +  g_h[:, f·k:(f+1)·k]·x_f

    (``g_h`` already carries dscores through the vjp). The MLP + bias —
    the only dense parameters — update with the configured optax
    optimizer (Adam for the registered config): no dense table gradient
    and no table-sized moment state ever exists.
    """
    from fm_spark_tpu.models.field_deepfm import FieldDeepFMSpec
    from fm_spark_tpu.train import make_optimizer

    if type(spec) is not FieldDeepFMSpec:
        raise ValueError("expected a FieldDeepFMSpec")
    _reject_collective_dtype(config, "the single-chip FieldDeepFM body")
    _reject_score_sharded(config, "the single-chip FieldDeepFM body")
    _reject_sel_blocked(config, "the single-chip FieldDeepFM body")
    _reject_deep_sharded(config, "the single-chip FieldDeepFM body")
    _reject_fused_embed_require(config, "the single-chip FieldDeepFM body")
    _reject_embed_tier_require(config, "the single-chip FieldDeepFM body")
    _check_host_dedup(config, spec.loss)
    compact = config.compact_cap > 0
    per_example_loss = losses_lib.loss_fn(spec.loss)
    cd = spec.cdtype
    F, k = spec.num_fields, spec.rank
    sr_base_key = _sr_base_key(config)
    lr_at = _lr_at(config)
    gat = _gather_fn(config)
    dense_opt = make_optimizer(config)

    import optax

    def dense_subtree(params):
        return {"w0": params["w0"], "mlp": params["mlp"]}

    def init_opt_state(params):
        return dense_opt.init(dense_subtree(params))

    def _step(params, opt_state, step_idx, ids, vals, labels, weights,
              aux=None):
        if config.host_dedup and aux is None:
            raise ValueError(
                "host_dedup step needs the batch's dedup_aux operand"
            )
        w0 = params["w0"]
        vals_c = vals.astype(cd)
        urows, rows, aux, ovf = _rows_for(
            compact, params["vw"], aux, cd, gat, ids, spec.table_width,
            device_cap=config.compact_cap if config.compact_device else 0,
        )                                           # F × [B, k+1]
        if config.gfull_fused:
            # Full-width products once, like the FM body's gfull path.
            xv_fulls = [r * vals_c[:, f : f + 1]
                        for f, r in enumerate(rows)]
            xvs = [x[:, :k] for x in xv_fulls]
        else:
            xvs = [r[:, :k] * vals_c[:, f : f + 1]
                   for f, r in enumerate(rows)]
        s = sum(xvs)
        sum_sq = sum(jnp.sum(x * x, axis=1) for x in xvs)
        fm_scores = 0.5 * (jnp.sum(s * s, axis=1) - sum_sq)
        if spec.use_linear:
            if config.gfull_fused:
                fm_scores = fm_scores + sum(x[:, k] for x in xv_fulls)
            else:
                fm_scores = fm_scores + sum(
                    r[:, k] * vals_c[:, f] for f, r in enumerate(rows)
                )
        h = jnp.concatenate(xvs, axis=1)                # [B, F·k]

        wsum = jnp.maximum(jnp.sum(weights), 1.0)

        def head_loss(dense, h_in):
            with jax.named_scope("deep/forward"):
                deep = spec.deep_scores(dense["mlp"], h_in)
            sc = fm_scores + deep
            if spec.use_bias:
                sc = sc + dense["w0"].astype(cd)
            per = per_example_loss(sc, labels) * weights
            return jnp.sum(per) / wsum, sc

        # One vjp covers the dense params AND the deep head's pullback to
        # h; dscores (for the analytic FM table rule) comes from a grad
        # wrt scores at the returned value — cheap closed forms.
        (loss, scores), vjp = jax.vjp(
            head_loss, dense_subtree(params), h, has_aux=False
        )
        with jax.named_scope("deep/backward"):
            g_dense, g_h = vjp(
                (jnp.ones_like(loss), jnp.zeros_like(scores)))

        def batch_loss(sc):
            return jnp.sum(per_example_loss(sc, labels) * weights) / wsum

        dscores = jax.grad(batch_loss)(scores)
        lr = lr_at(step_idx)
        touched = weights > 0

        if config.gfull_fused:
            # The deep-head pullback widened to [B, F, k+1] with ONE
            # zero pad (col k: the head never touches the linear
            # weight), then the shared fused construction.
            gh_pad = jnp.pad(
                g_h.reshape(-1, F, k), ((0, 0), (0, 0), (0, 1)))
            g_fulls = _gfull_grads(
                dscores, vals_c, s, xv_fulls, rows, touched, k, cd,
                spec.use_linear, config, extra=gh_pad,
            )
        else:
            g_fulls = []
            for f in range(F):
                g_v = (
                    dscores[:, None] * vals_c[:, f : f + 1] * (s - xvs[f])
                    + g_h[:, f * k : (f + 1) * k] * vals_c[:, f : f + 1]
                )
                if config.reg_factors:
                    g_v = g_v + config.reg_factors * rows[f][:, :k] * touched[:, None]
                if spec.use_linear:
                    g_l = dscores * vals_c[:, f]
                    if config.reg_linear:
                        g_l = g_l + config.reg_linear * rows[f][:, k] * touched
                else:
                    g_l = jnp.zeros_like(dscores)
                g_fulls.append(
                    jnp.concatenate([g_v, g_l[:, None]], axis=1))
        new_vw = _updates_for(
            compact, params["vw"], ids, g_fulls, rows, urows, config,
            sr_base_key, step_idx, lr, aux,
        )

        # Dense side: optax on {"w0", "mlp"} only (+ L2 per group).
        with jax.named_scope("deep/adam"):
            if config.reg_bias:
                g_dense["w0"] = g_dense["w0"] + config.reg_bias * w0
            if config.reg_factors:
                g_dense["mlp"] = jax.tree_util.tree_map(
                    lambda g, p: g + config.reg_factors * p,
                    g_dense["mlp"], params["mlp"],
                )
            updates, opt_state = dense_opt.update(
                g_dense, opt_state, dense_subtree(params)
            )
            new_dense = optax.apply_updates(dense_subtree(params), updates)
        return (
            {"w0": new_dense["w0"], "vw": new_vw, "mlp": new_dense["mlp"]},
            opt_state,
            _fold_overflow(loss, ovf, config),
        )

    return _step, init_opt_state


def make_field_deepfm_sparse_step(spec, config: TrainConfig):
    """Jitted fused hybrid step for :class:`FieldDeepFMSpec` (see
    :func:`make_field_deepfm_sparse_body`). Returns ``step(params,
    opt_state, step_idx, ids, vals, labels, weights) → (params,
    opt_state, loss)`` with ``step.init_opt_state``; ``opt_state``
    covers only ``{"w0", "mlp"}``."""
    body, init_opt_state = make_field_deepfm_sparse_body(spec, config)
    _step = functools.partial(jax.jit, donate_argnums=(0, 1))(body)

    def step(params, opt_state, step_idx, ids, vals, labels, weights,
             aux=None):
        return _step(params, opt_state, step_idx, ids, vals, labels,
                     weights, aux)

    step.init_opt_state = init_opt_state
    return step


def make_field_deepfm_multistep(spec, config: TrainConfig, n: int):
    """The DeepFM form of :func:`make_field_sparse_multistep` (VERDICT
    r3 #6): ``n`` hybrid steps in ONE compiled ``fori_loop`` program,
    with the dense head's optax state threaded through the carry —
    adam's count/moments advance exactly as in ``n`` separate calls
    (the state trees are shape-stable, so the carry is well-formed).
    Returns ``mstep(params, opt_state, step0, m, ids, vals, labels,
    weights, aux=None) → (params, opt_state, last_loss)`` over
    ``[n, ...]``-stacked batches; ``mstep.init_opt_state`` as usual.
    """
    if n < 1:
        raise ValueError(f"steps per call must be >= 1, got {n}")
    body, init_opt_state = make_field_deepfm_sparse_body(spec, config)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def mstep(params, opt_state, step0, m, ids, vals, labels, weights,
              aux=None):
        def fbody(j, carry):
            p, o, prev = carry
            a = (
                None if aux is None
                else jax.tree_util.tree_map(lambda x: x[j], aux)
            )
            p, o, loss = body(p, o, step0 + j, ids[j], vals[j],
                              labels[j], weights[j], a)
            # Sticky −inf, as in the FM/FFM roll.
            return p, o, jnp.where(jnp.isneginf(prev), prev, loss)

        return jax.lax.fori_loop(
            0, m, fbody, (params, opt_state, jnp.float32(0))
        )

    mstep.init_opt_state = init_opt_state
    return mstep


def make_sparse_sgd_step(spec, config: TrainConfig):
    """Build the fused sparse-SGD step for the plain-FM family.

    Returns ``step(params, step_idx, ids, vals, labels, weights) → (params,
    loss)``. Only ``optimizer='sgd'`` semantics (no momentum state); the
    learning-rate schedule matches :func:`fm_spark_tpu.train.make_optimizer`.
    """
    from fm_spark_tpu.models.fm import FMSpec

    if type(spec) is not FMSpec:
        raise ValueError("sparse step supports the plain FM family only")
    if config.optimizer != "sgd":
        raise ValueError(
            f"the flat-table sparse step implements plain SGD only "
            f"(optimizer='sgd', not {config.optimizer!r}); 'adagrad' and "
            "'ftrl' on flat tables are optim.make_sparse_adaptive_step's")
    _reject_gfull(config, "the flat-table FM step (it has no fused "
                  "g_full concat to eliminate)")
    _reject_collective_dtype(config, "the single-chip flat-table FM step")
    _reject_score_sharded(config, "the single-chip flat-table FM step")
    _reject_sel_blocked(config, "the single-chip flat-table FM step")
    _reject_deep_sharded(config, "the single-chip flat-table FM step")
    _reject_fused_embed_require(config, "the single-chip flat-table FM step")
    # NOT the tiered trainer itself: TieredTrainer builds THIS step over
    # its hot-tier window with embed_tier neutralized to 'off'.
    _reject_embed_tier_require(config, "the bare flat-table FM step "
                               "(drive it through embed.TieredTrainer)")
    per_example_loss = losses_lib.loss_fn(spec.loss)
    cd = spec.cdtype

    if config.lr_schedule == "inv_sqrt":
        lr_at = lambda i: config.learning_rate / jnp.sqrt(i.astype(jnp.float32) + 1.0)
    else:
        lr_at = lambda i: jnp.float32(config.learning_rate)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(params, step_idx, ids, vals, labels, weights):
        w0, w, v = params["w0"], params["w"], params["v"]
        vals_c = vals.astype(cd)
        rows = v[ids].astype(cd)                       # [B, nnz, k]
        xv = rows * vals_c[..., None]
        s = jnp.sum(xv, axis=1)                        # [B, k]
        sum_sq = jnp.sum(xv * xv, axis=(1, 2))
        scores = 0.5 * (jnp.sum(s * s, axis=1) - sum_sq)
        if spec.use_linear:
            scores = scores + jnp.sum(w[ids].astype(cd) * vals_c, axis=1)
        if spec.use_bias:
            scores = scores + w0.astype(cd)

        wsum = jnp.maximum(jnp.sum(weights), 1.0)

        def batch_loss(sc):
            return jnp.sum(per_example_loss(sc, labels) * weights) / wsum

        loss, dscores = jax.value_and_grad(batch_loss)(scores)

        # The reference's analytic rule (BASELINE.json:5):
        #   ∂ŷ/∂v[i,f] = x_i (s_f − v[i,f] x_i);  ∂ŷ/∂w[i] = x_i.
        g_rows = dscores[:, None, None] * vals_c[..., None] * (s[:, None, :] - xv)
        lr = lr_at(step_idx)
        if config.reg_factors:
            # Lazy L2: decay only the gathered rows.
            g_rows = g_rows + config.reg_factors * rows * (
                weights[:, None, None] > 0
            )
        v = v.at[ids].add((-lr * g_rows).astype(v.dtype))
        if spec.use_linear:
            g_w = dscores[:, None] * vals_c
            if config.reg_linear:
                g_w = g_w + config.reg_linear * w[ids].astype(cd) * (
                    weights[:, None] > 0
                )
            w = w.at[ids].add((-lr * g_w).astype(w.dtype))
        if spec.use_bias:
            g_w0 = jnp.sum(dscores) + config.reg_bias * w0
            w0 = w0 - lr * g_w0
        return {"w0": w0, "w": w, "v": v}, loss

    return step


# --------------------------------------------------------------------------
# AOT warm-start entries (the compile-before-data path).
#
# The fused step programs are deterministic functions of (spec, config,
# batch shape) — nothing about them needs real data or initialized
# tables. Lowering against ABSTRACT shapes and calling ``.compile()``
# runs the whole XLA pipeline eagerly, so:
#   * with the persistent compile cache (utils/compile_cache, on in
#     every entry point), the executable lands on disk and every later
#     process — bench, training, a retried attempt — deserializes it
#     instead of recompiling;
#   * the compile happens BEFORE any batch or table touches the device,
#     so a failure to compile costs no table initialisation.
# Sharded variants live next to their builders
# (parallel/step.py, parallel/field_step.py).
# --------------------------------------------------------------------------


def abstract_field_batch(spec, batch_size: int):
    """ShapeDtypeStructs of one ``(ids, vals, labels, weights)`` batch
    as every fused field step consumes it: ``[B, F]`` int32 ids, ``[B,
    F]`` f32 vals, ``[B]`` f32 labels/weights."""
    B, F = batch_size, spec.num_fields
    sds = jax.ShapeDtypeStruct
    return (
        sds((B, F), jnp.int32),
        sds((B, F), jnp.float32),
        sds((B,), jnp.float32),
        sds((B,), jnp.float32),
    )


def abstract_host_aux(config: TrainConfig, batch_size: int,
                      num_fields: int):
    """Abstract pytree of the host-built dedup/compact aux for a
    ``[B, F]`` batch, or None when the config ships no aux.

    Aux shapes depend only on ``(B, F, cap)``, never on id values, so a
    zeros-ids probe build (every field has one unique id — always under
    any positive cap) yields the exact structure the real producer
    ships."""
    if not config.host_dedup:
        return None
    import numpy as np

    from fm_spark_tpu.ops.scatter import compact_aux, dedup_aux

    ids = np.zeros((batch_size, num_fields), np.int32)
    aux = (compact_aux(ids, config.compact_cap) if config.compact_cap
           else dedup_aux(ids))
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype),
        aux,
    )


def _stack_abstract(tree, n: int):
    """Prepend a ``[n, ...]`` stack axis to every leaf (the multistep
    roll's batch layout, data/pipeline.StackedBatches)."""
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct((n, *s.shape), s.dtype,
                                       sharding=s.sharding), tree
    )


def lower_field_sparse_step(spec, config: TrainConfig, batch_size: int,
                            steps_per_call: int = 1, device=None):
    """Lower the single-chip fused step for ``spec``'s family — or the
    ``steps_per_call`` fori roll — against abstract shapes, for
    ``device`` (None: the default device; a described device of a
    topology lowers for a chip that is not attached).

    Returns a ``jax.stages.Lowered``; ``.compile()`` produces the
    executable (and, with the persistent cache enabled, persists it).
    Dispatches FieldFM / FieldFFM / FieldDeepFM exactly like the
    training loop's builders, tables in the form the loop holds them in
    on that device (``models/rows.hold``), so the compiled program is
    the one the loop's first dispatch would otherwise build on the
    critical path.
    """
    from fm_spark_tpu.models.field_deepfm import FieldDeepFMSpec
    from fm_spark_tpu.models.field_ffm import FieldFFMSpec

    if steps_per_call < 1:
        raise ValueError(
            f"steps per call must be >= 1, got {steps_per_call}"
        )
    sharding = (None if device is None
                else jax.sharding.SingleDeviceSharding(device))
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=sharding)

    def on_device(tree):
        return jax.tree_util.tree_map(lambda s: sds(s.shape, s.dtype), tree)

    params_abs = rows_lib.hold(
        on_device(jax.eval_shape(spec.init, jax.random.key(0))),
        FUSED_TABLE_KEYS, writes=True)[0]
    batch_abs = on_device(abstract_field_batch(spec, batch_size))
    aux_abs = on_device(
        abstract_host_aux(config, batch_size, spec.num_fields))
    i32 = sds((), jnp.int32)
    multi = steps_per_call > 1

    if isinstance(spec, FieldDeepFMSpec):
        if multi:
            mstep = make_field_deepfm_multistep(spec, config,
                                                steps_per_call)
            opt_abs = on_device(
                jax.eval_shape(mstep.init_opt_state, params_abs))
            return mstep.lower(
                params_abs, opt_abs, i32, i32,
                *_stack_abstract(batch_abs, steps_per_call),
                _stack_abstract(aux_abs, steps_per_call),
            )
        body, init_opt = make_field_deepfm_sparse_body(spec, config)
        opt_abs = on_device(jax.eval_shape(init_opt, params_abs))
        step = functools.partial(jax.jit, donate_argnums=(0, 1))(body)
        return step.lower(params_abs, opt_abs, i32, *batch_abs, aux_abs)

    if isinstance(spec, FieldFFMSpec) and config.optimizer == "adagrad":
        if multi:
            raise ValueError(
                "the FieldFFM AdaGrad body runs one step a call; the "
                "multistep roll implements plain SGD only")
        body, init_slots = make_field_ffm_adagrad_body(spec, config)
        slots_abs = rows_lib.hold(
            on_device(jax.eval_shape(
                init_slots, jax.eval_shape(spec.init, jax.random.key(0)))),
            FUSED_TABLE_KEYS, writes=True)[0]
        step = jax.jit(body, donate_argnums=(0, 1))
        return step.lower(params_abs, slots_abs, i32, *batch_abs)

    if multi:
        mstep = make_field_sparse_multistep(spec, config, steps_per_call)
        return mstep.lower(
            params_abs, i32, i32,
            *_stack_abstract(batch_abs, steps_per_call),
            _stack_abstract(aux_abs, steps_per_call),
        )
    step = (
        make_field_ffm_sparse_sgd_step(spec, config)
        if isinstance(spec, FieldFFMSpec)
        else make_field_sparse_sgd_step(spec, config)
    )
    return step.lower(params_abs, i32, *batch_abs, aux_abs)


def precompile_field_sparse_step(spec, config: TrainConfig,
                                 batch_size: int,
                                 steps_per_call: int = 1):
    """Eagerly compile the fused step (``lower().compile()``) — the
    warm-start producer: run once per (config, shape) to populate the
    persistent cache before data ever touches the device. Returns the
    ``jax.stages.Compiled`` (callable with concrete arrays)."""
    return lower_field_sparse_step(
        spec, config, batch_size, steps_per_call
    ).compile()
