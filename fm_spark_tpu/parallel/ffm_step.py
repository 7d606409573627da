"""Field-sharded FFM: the sel-transpose forward, step, roll support, eval.

Split out of ``parallel/field_step.py`` (round 4 — the module carried
three model families); pure move, no behavior change. The shared layout
and FM machinery stay in :mod:`fm_spark_tpu.parallel.field_step`, which
re-exports this module's public names so every existing import path
keeps working. Cross-module helpers are referenced through the module
object (``_fs``) so the field_step↔ffm_step import cycle resolves at call
time, not import time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from fm_spark_tpu.ops import losses as losses_lib
from fm_spark_tpu.parallel import field_step as _fs
from fm_spark_tpu.sparse import (
    COMPACT_LEVERS,
    SGD_TABLES,
    Serves,
    _apply_field_updates,
    _collective_dtype,
    _compact_apply_all,
    _compact_gather_all,
    _device_compact_aux_all,
    _fold_overflow,
    _gather_all,
    _lr_at,
    _psum_wire,
    _sr_base_key,
    declares,
    refuse_unserved,
)
from fm_spark_tpu.train import TrainConfig

# ---------------------------------------------------------------- FFM


def _ffm_field_forward(spec, g, vw, w0, ids, vals, labels, weights,
                       caux=None, device_cap: int = 0, wire=None):
    """The field-sharded FFM forward, shared by the train body and the
    eval step (config 4's multi-chip fast path, VERDICT r2 #3).

    Cross-field factors make this structurally different from FM: the
    chip owning field ``i`` holds ``sel[b, i, j] = v[id_i][j]·x_i`` for
    every target ``j`` locally (the packed [B, F·k+1] row carries all
    targets — field_ffm.py), but the pairwise term needs the TRANSPOSED
    blocks ``sel[b, j, i]``. ONE ``all_to_all`` of the sel activations
    over ``feat`` (split the target axis, concat the owner axis)
    delivers exactly those — activation traffic, never tables, the same
    pattern as DeepFM's ``h`` all_gather but n× cheaper than gathering
    the full [B, F, F, k] tensor on every chip.

    On a 2-D ``(feat, row)`` mesh (round 4 — VERDICT r3 #5) each row
    shard additionally owns a bucket range of its fields, exactly the
    FM step's ownership contract: non-owned lanes gather ZERO rows, so
    each shard's ``sel_loc`` is a partial sum that ONE ``psum`` over
    ``row`` completes before the transposing all_to_all — the same
    linear-reduction identity the FM partials use, lifted to the sel
    tensor (sel is linear in the gathered rows). Updates stay
    single-owner via the OOB-sentinel ``uidx`` / the ownership-masked
    device-compact aux. The extra collective is the price of bucket
    capacity: ~ring·|sel| bytes over ``row`` per step, on top of the
    1-D layout's a2a (projection.py models the 1-D layout; the row
    psum adds ``2(r−1)/r·|sel|`` on a 2-D mesh — use it for capacity,
    not speed).

    Returns ``(scores, rows, sel_loc, selT, vals_c, uidx, urows, aux,
    ovf, labels, weights)`` — scores replicated; sel_loc/selT are this
    chip's [B, f_local, F_pad, k] owner/transposed blocks for the
    analytic backward.
    """
    cd = spec.cdtype
    k = spec.rank
    F = spec.num_fields
    f_local, f_pad = g["f_local"], g["f_pad"]

    if caux is None:
        ids = lax.all_to_all(ids, "feat", split_axis=1, concat_axis=0,
                             tiled=True)
    vals = lax.all_to_all(vals, "feat", split_axis=1, concat_axis=0,
                          tiled=True)
    labels = lax.all_gather(labels, "feat", tiled=True)
    weights = lax.all_gather(weights, "feat", tiled=True)
    if g["two_d"]:
        ids = lax.all_gather(ids, "row", tiled=True)
        vals = lax.all_gather(vals, "row", tiled=True)
        labels = lax.all_gather(labels, "row", tiled=True)
        weights = lax.all_gather(weights, "row", tiled=True)
    vals_c = vals.astype(cd)

    urows = None
    aux = caux
    ovf = None
    own = None
    if device_cap > 0:
        cids = ids
        extra = None
        if g["two_d"]:
            # Ownership masking before the sort — the FM step's 2-D
            # device-compact pattern (see _field_forward).
            loc, own = _fs._ownership_mask(g, ids)
            cids = jnp.where(own, loc, g["bucket_local"])
            extra = jnp.any(~own, axis=0).astype(jnp.int32)
        aux, ovf = _device_compact_aux_all(cids, device_cap, f_local,
                                           extra_segs=extra)
        urows, rows = _compact_gather_all(
            [vw[f] for f in range(f_local)], aux, cd, mask_overflow=True
        )
        if own is not None:
            rows = [r * own[:, f, None] for f, r in enumerate(rows)]
        uidx = None
    elif g["two_d"]:
        loc, own = _fs._ownership_mask(g, ids)
        gidx = jnp.clip(loc, 0, g["bucket_local"] - 1)
        rows = [
            r * own[:, f, None]
            for f, r in enumerate(
                _gather_all(lambda t, i: t[i], vw, gidx, cd))
        ]
        uidx = jnp.where(own, loc, g["bucket_local"])
    elif caux is not None:
        urows, rows = _compact_gather_all(
            [vw[f] for f in range(f_local)], caux, cd
        )
        uidx = None
    else:
        rows = _gather_all(lambda t, i: t[i], vw, ids, cd)
        uidx = ids

    b = vals.shape[0]
    # sel_loc[b, p, j, :] = v[id_p][target j] · x_p for this chip's
    # owned fields p; the target axis padded F → F_pad so the
    # all_to_all splits evenly (padding targets are zero columns).
    sel_loc = jnp.stack(
        [
            jnp.pad(
                r[:, : F * k].reshape(b, F, k) * vals_c[:, p, None, None],
                ((0, 0), (0, f_pad - F), (0, 0)),
            )
            for p, r in enumerate(rows)
        ],
        axis=1,
    )                                           # [B, f_local, F_pad, k]
    if g["two_d"]:
        # Complete each owned field's sel block across its row shards
        # (non-owned lanes contributed zeros). After this, sel_loc is
        # identical on every row shard, so everything downstream —
        # the a2a, pair/diag, the backward's dsel — runs replicated
        # over ``row`` by construction; only lin needs the 2-D psum.
        sel_loc = _psum_wire(sel_loc, "row", wire, cd)
    # selT[b, p, j, :] = sel[b, j, i_p] — every other chip's view of
    # this chip's fields as TARGETS, re-sharded in one collective. The
    # sel a2a is the FFM step's dominant ICI term (~F× the FM psum at
    # headline shapes — parallel/projection.py); ``wire``
    # (TrainConfig.collective_dtype) halves its bytes at bf16 precision.
    sel_wire = sel_loc.astype(wire) if wire is not None else sel_loc
    selT = jnp.swapaxes(
        lax.all_to_all(sel_wire, "feat", split_axis=2, concat_axis=1,
                       tiled=True),
        1, 2,
    ).astype(cd)                                # [B, f_local, F_pad, k]

    # Partial pairwise sum over owned i: Σ_j ⟨sel[i,j], sel[j,i]⟩ minus
    # the i==j diagonal; psum over feat completes Σ_{i≠j}.
    pair_p = jnp.sum(sel_loc * selT, axis=(1, 2, 3))
    feat0 = lax.axis_index("feat") * f_local
    diag_p = sum(
        jnp.sum(sel_loc[:, p, feat0 + p, :] ** 2, axis=-1)
        for p in range(f_local)
    )
    lin_p = (
        sum(r[:, F * k] * vals_c[:, p] for p, r in enumerate(rows))
        if spec.use_linear
        else jnp.zeros((b,), cd)
    )
    # pair/diag derive from the row-complete sel_loc (identical per row
    # shard) — psum over ``feat`` only; lin derives from the MASKED rows
    # (partial over row too) — psum over every score axis.
    pair = _psum_wire(pair_p - diag_p, "feat", wire, cd)
    scores = 0.5 * pair
    if spec.use_linear:
        scores = scores + _psum_wire(lin_p, g["score_axes"], wire, cd)
    if spec.use_bias:
        scores = scores + w0.astype(cd)
    return (scores, rows, sel_loc, selT, vals_c, uidx, urows, aux, ovf,
            labels, weights)


FIELD_FFM_MESH = Serves(
    COMPACT_LEVERS - {"host_dedup"} | {"collective_dtype"}, remedy=SGD_TABLES)


def _make_ffm_local_step(spec, config: TrainConfig, mesh):
    """Build the FFM sharded LOCAL step + layout facts (the FFM
    counterpart of :func:`_make_field_local_step`; shared by the
    per-step wrapper and the multi-step roll). Returns ``(local_step,
    host_compact)``."""
    from fm_spark_tpu.models.field_ffm import FieldFFMSpec

    if type(spec) is not FieldFFMSpec:
        raise ValueError("expected a FieldFFMSpec")
    refuse_unserved(config, FIELD_FFM_MESH, "the field-sharded FFM step",
                    spec.loss)
    wire = _collective_dtype(config)
    if set(mesh.axis_names) not in ({"feat"}, {"feat", "row"}):
        raise ValueError(
            "field-sharded FFM runs on a ('feat',) or ('feat', 'row') "
            "mesh (use make_field_mesh)"
        )
    g = _fs._mesh_geometry(spec, mesh)
    compact = config.compact_cap > 0
    device_cap = config.compact_cap if config.compact_device else 0
    host_compact = compact and not config.compact_device
    if host_compact and g["two_d"]:
        # Same structural limit as the FM step: a host aux built from
        # raw global ids cannot express row ownership.
        raise ValueError(
            "host-built compact_cap on the sharded FFM step requires a "
            "1-D ('feat',) mesh; use compact_device=True for 2-D "
            "(feat, row) meshes"
        )

    per_example_loss = losses_lib.loss_fn(spec.loss)
    cd = spec.cdtype
    k = spec.rank
    F = spec.num_fields
    f_local = g["f_local"]
    sr_base_key = _sr_base_key(config)
    lr_at = _lr_at(config)

    def local_step(params, step_idx, ids, vals, labels, weights,
                   caux=None):
        if host_compact and caux is None:
            raise ValueError(
                "compact sharded FFM step needs the batch's compact_aux "
                "operand (stacked [F_pad, ...], sharded over feat)"
            )
        vw = params["vw"]
        w0 = params["w0"]
        (scores, rows, sel_loc, selT, vals_c, uidx, urows, aux, ovf,
         labels, weights) = _ffm_field_forward(
            spec, g, vw, w0, ids, vals, labels, weights, caux=caux,
            device_cap=device_cap, wire=wire,
        )

        wsum = jnp.maximum(jnp.sum(weights), 1.0)

        def batch_loss(sc):
            return jnp.sum(per_example_loss(sc, labels) * weights) / wsum

        loss, dscores = jax.value_and_grad(batch_loss)(scores)
        lr = lr_at(step_idx)
        touched = weights > 0

        # ∂L/∂sel[b, i_p, j] = ds · sel[b, j, i_p] = ds · selT (zeroed
        # diagonal), then ∂L/∂v[id_p, j] = ∂sel · x_p — all local.
        # (2-D: selT is row-complete, so dsel is identical per row
        # shard; ownership lands at the WRITE via the sentinel/compact
        # aux, exactly the FM contract. The reg term uses the masked
        # rows — zero for non-owned lanes, whose writes drop anyway.)
        feat0 = lax.axis_index("feat") * f_local
        dsel = dscores[:, None, None, None] * selT
        own_col = jax.nn.one_hot(
            feat0 + jnp.arange(f_local), g["f_pad"], dtype=cd
        )                                        # [f_local, F_pad]
        dsel = dsel * (1.0 - own_col)[None, :, :, None]
        g_fulls = []
        for p in range(f_local):
            g_v = (
                dsel[:, p, :F, :] * vals_c[:, p, None, None]
            ).reshape(-1, F * k)
            if config.reg_factors:
                g_v = g_v + config.reg_factors * rows[p][:, : F * k] * touched[:, None]
            if spec.use_linear:
                g_l = dscores * vals_c[:, p]
                if config.reg_linear:
                    g_l = g_l + config.reg_linear * rows[p][:, F * k] * touched
            else:
                g_l = jnp.zeros_like(dscores)
            g_fulls.append(jnp.concatenate([g_v, g_l[:, None]], axis=1))
        # SR keys: one stream per (global field, row shard), like the
        # FM body — noise never correlates across chips sharing a field.
        field_offset = feat0
        if g["two_d"]:
            field_offset = field_offset + lax.axis_index("row") * g["f_pad"]
        if compact:
            new_slices = _compact_apply_all(
                [vw[f] for f in range(f_local)], g_fulls, urows, config,
                sr_base_key, step_idx, lr, aux,
                field_offset=field_offset,
            )
        else:
            new_slices = _apply_field_updates(
                [vw[f] for f in range(f_local)], uidx, g_fulls, rows,
                config, sr_base_key, step_idx, lr,
                field_offset=field_offset,
            )
        out = {"w0": w0, "vw": jnp.stack(new_slices, axis=0)}
        if spec.use_bias:
            out["w0"] = w0 - lr * (jnp.sum(dscores) + config.reg_bias * w0)
        if ovf is not None:
            loss = _fold_overflow(
                loss, lax.pmax(ovf, g["score_axes"]), config
            )
        return out, loss

    return local_step, host_compact


@declares(FIELD_FFM_MESH)
def make_field_ffm_sharded_body(spec, config: TrainConfig, mesh):
    """Unjitted field-sharded fused FFM step — config 4's multi-chip
    layout, on a 1-D ``(feat,)`` or 2-D ``(feat, row)`` mesh (row
    sharding of each field's bucket dimension — round 4, VERDICT r3
    #5). Same math as the single-chip
    :func:`fm_spark_tpu.sparse.make_field_ffm_sparse_sgd_body`
    (equivalence-tested); tables single-owner per field (and per bucket
    range on 2-D), one sel ``all_to_all`` — plus, 2-D, one sel ``psum``
    over ``row`` — instead of table movement. Supports the compact
    paths: host-built aux (single-process, 1-D) and the device-built
    aux (composes with 2-D meshes and multi-process)."""
    local_step, host_compact = _make_ffm_local_step(spec, config, mesh)
    if host_compact:
        return jax.shard_map(
            local_step,
            mesh=mesh,
            in_specs=(_fs.field_param_specs(mesh), P(),
                      *_fs.field_batch_specs(mesh),
                      (P("feat", None),) * 5),
            out_specs=(_fs.field_param_specs(mesh), P()),
            check_vma=False,
        )
    return jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(_fs.field_param_specs(mesh), P(), *_fs.field_batch_specs(mesh)),
        out_specs=(_fs.field_param_specs(mesh), P()),
        check_vma=False,
    )


@declares(FIELD_FFM_MESH)
def make_field_ffm_sharded_step(spec, config: TrainConfig, mesh):
    """Jitted field-sharded fused FFM step; params donated."""
    return jax.jit(
        make_field_ffm_sharded_body(spec, config, mesh),
        donate_argnums=(0,),
    )


def make_field_ffm_sharded_eval_step(spec, mesh):
    """Metrics-accumulation step on the field-sharded FFM layout —
    the shared forward (:func:`_ffm_field_forward`), then a replicated
    :func:`metrics.update_metrics` exactly like the FM eval step."""
    from fm_spark_tpu.models import base as model_base
    from fm_spark_tpu.models.field_ffm import FieldFFMSpec
    from fm_spark_tpu.utils import metrics as metrics_lib

    if type(spec) is not FieldFFMSpec:
        raise ValueError("expected a FieldFFMSpec")
    if set(mesh.axis_names) not in ({"feat"}, {"feat", "row"}):
        raise ValueError(
            "sharded FFM eval runs on a ('feat',) or ('feat', 'row') mesh"
        )
    per_example_loss = losses_lib.loss_fn(spec.loss)
    g = _fs._mesh_geometry(spec, mesh)
    mstate_specs = jax.tree_util.tree_map(
        lambda _: P(), jax.eval_shape(metrics_lib.init_metrics)
    )

    def local_eval(params, mstate, ids, vals, labels, weights):
        scores, _, _, _, _, _, _, _, _, labels, weights = (
            _ffm_field_forward(spec, g, params["vw"], params["w0"], ids,
                               vals, labels, weights)
        )
        per = per_example_loss(scores, labels)
        preds = model_base.predict_from_scores(spec, scores)
        return metrics_lib.update_metrics(
            mstate, scores, labels, per, weights, predictions=preds
        )

    return jax.jit(jax.shard_map(
        local_eval,
        mesh=mesh,
        in_specs=(_fs.field_param_specs(mesh), mstate_specs,
                  *_fs.field_batch_specs(mesh)),
        out_specs=mstate_specs,
        check_vma=False,
    ))


