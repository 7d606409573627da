"""Field-sharded fused sparse-SGD: the multi-chip layout of FieldFM.

Single-chip measurements (PERF.md) show the FieldFM hot path is bound by
per-index gather/scatter rate, not FLOPs or ICI. The scale-out that
multiplies that rate is sharding the *fields* over the mesh: with F
fields on n chips, each chip owns F/n sub-tables outright and performs
only ``B·F/n`` index ops per step — an 8× index-rate multiplier on a
v5e-8 (5 fields/chip at Criteo's 39).

Step anatomy (one compiled program, two collectives):

1. The host feeds each chip ``1/n`` of the batch (rows). One
   ``all_to_all`` over ``feat`` re-shards it from row-sharded to
   column(field)-sharded: ``[B/n, F_pad] → [B, F_pad/n]`` — the "batch
   all-gather" lever from PERF.md; ids+vals ≈ 8·B·F bytes cross ICI,
   the 10M-row tables never move. Labels/weights ride one small
   ``all_gather``.
2. Each chip gathers its fields' rows, forms partial interaction sums;
   one ``psum`` of ``([B,k], [B], [B])`` reconstructs exact scores on
   every chip (the linear-reduction identity, SURVEY.md §2).
3. Every chip computes the same ``dscores`` from replicated scores, then
   scatters updates into only its own tables — single-owner writes, so
   no cross-chip reduction of table gradients exists at all. Compare the
   reference, which tree-aggregates a full dense gradient every
   iteration (SURVEY.md §3.1).

Tables are uniquely owned per field over the ``feat`` axis. An optional
second mesh axis ``row`` shards each field's BUCKET dimension
(``make_field_mesh(n, n_row=r)``), scaling row capacity past per-field
bucket limits while keeping single-owner write semantics:

- Each ``(field, example)`` id is owned by exactly ONE row shard, so
  shard-local masked gathers (non-owned lanes zeroed) followed by a
  ``psum`` over BOTH axes reconstruct the exact partial sums — the same
  linear-reduction identity, now 2-D (SURVEY.md §7 step 5(b)).
- Updates scatter through an out-of-bounds sentinel index for non-owned
  lanes (XLA drop semantics), so each table row still has exactly one
  writer and no cross-chip gradient reduction exists.
- Smaller per-chip sub-tables also sit further under the measured
  gather/scatter size cliffs (PERF.md facts 2-3), so capacity scaling
  does not regress per-index cost.

Layout: per-field tables stacked into ``[F_pad, bucket, width]`` sharded
``P('feat')``; ``F_pad`` rounds F up to the mesh size so chips own equal
table counts. Padded fields carry zero tables and ``val=0`` batch
columns, keeping them exactly inert through forward, backward, and the
lazy-L2 decay. Math/update semantics are identical to the single-chip
:func:`fm_spark_tpu.sparse.make_field_sparse_sgd_body`; equivalence is
property-tested on the fake 8-device CPU mesh (tests/test_field_step.py).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from fm_spark_tpu.ops import losses as losses_lib
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
    _gather_fn,
    _gfull_grads,
    _lr_at,
    _psum_wire,
    _sr_base_key,
    declares,
    refuse_unserved,
)
from fm_spark_tpu.train import TrainConfig


def make_field_mesh(n_devices: int | None = None, devices=None,
                    n_row: int = 1):
    """Mesh for the field-sharded layout: 1-D ``(feat,)`` by default, or
    2-D ``(feat, row)`` with ``n_row`` shards of each field's bucket
    dimension (row capacity scale-out)."""
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    import numpy as np

    devices = np.asarray(devices)
    if n_row <= 1:
        return jax.sharding.Mesh(devices, ("feat",))
    if devices.size % n_row:
        raise ValueError(
            f"n_row={n_row} must divide the device count ({devices.size})"
        )
    return jax.sharding.Mesh(
        devices.reshape(devices.size // n_row, n_row), ("feat", "row")
    )


def padded_num_fields(num_fields: int, n_feat: int) -> int:
    return -(-num_fields // n_feat) * n_feat


def stack_field_params(spec, params, n_feat: int) -> dict:
    """Per-field table list → ``{"w0", "vw": [F_pad, bucket, width]}``."""
    if not spec.fused_linear:
        raise ValueError("field-sharded step requires fused_linear=True")
    f_pad = padded_num_fields(spec.num_fields, n_feat)
    tables = list(params["vw"])
    pad = f_pad - len(tables)
    if pad:
        tables += [jnp.zeros_like(tables[0])] * pad
    return {"w0": params["w0"], "vw": jnp.stack(tables, axis=0)}


def unstack_field_params(spec, stacked: dict) -> dict:
    """Inverse of :func:`stack_field_params` (drops padding fields)."""
    vw = stacked["vw"]
    return {
        "w0": stacked["w0"],
        "vw": [vw[f] for f in range(spec.num_fields)],
    }


def pad_field_batch(batch, num_fields: int, n_feat: int):
    """Zero-pad ``(ids, vals, labels, weights)`` to ``F_pad`` field slots."""
    import numpy as np

    ids, vals, labels, weights = batch
    f_pad = padded_num_fields(num_fields, n_feat)
    pad = f_pad - ids.shape[1]
    if pad:
        ids = np.concatenate(
            [ids, np.zeros((ids.shape[0], pad), ids.dtype)], axis=1
        )
        vals = np.concatenate(
            [vals, np.zeros((vals.shape[0], pad), vals.dtype)], axis=1
        )
    return ids, vals, labels, weights


# Batch enters example-sharded over the chips; the step's all_to_all turns
# it field-sharded on device. (1-D constants kept for direct callers; the
# mesh-aware functions below handle both layouts.)
BATCH_SPECS = (P("feat", None), P("feat", None), P("feat"), P("feat"))
PARAM_SPECS = {"w0": P(), "vw": P("feat", None, None)}


def field_param_specs(mesh) -> dict:
    """Param PartitionSpecs for a 1-D or 2-D field mesh: the stacked
    ``vw [F_pad, bucket, width]`` shards fields over ``feat`` and (2-D)
    the bucket dimension over ``row``."""
    if "row" in mesh.axis_names:
        return {"w0": P(), "vw": P("feat", "row", None)}
    return PARAM_SPECS


def field_batch_specs(mesh) -> tuple:
    """Batch PartitionSpecs: the example axis shards over every mesh
    axis (each chip is fed a distinct slice of the global batch)."""
    if "row" in mesh.axis_names:
        ax = ("feat", "row")
        return (P(ax, None), P(ax, None), P(ax), P(ax))
    return BATCH_SPECS


def shard_field_params(stacked: dict, mesh) -> dict:
    specs = field_param_specs(mesh)
    return {
        k: jax.device_put(v, NamedSharding(mesh, specs[k]))
        for k, v in stacked.items()
    }


def shard_field_batch(batch, mesh):
    """Place a host batch (already ``F_pad`` wide) example-sharded over
    the mesh. Each array goes to ``device_put`` AS IT IS, with the
    sharding: a NumPy array is cut into the devices' row runs on the
    host and each run sent to its own device. Never ``jnp.asarray(x)``
    first: that stages the WHOLE batch on chip 0 and the ``device_put``
    then re-shards it from there with a device program
    (``jit__multi_slice``) that queues behind chip 0's running step:
    228 ms of ``train/prep`` in a 349 ms step of ``fm_r64.train_4chip``
    (ledger, PR 31). The training loop's feed makes the shards apart and
    in parallel (:class:`FieldBatchFeed`); this is the one-call form for
    a batch that exists whole (evals, the elastic loop, tests)."""
    return tuple(
        jax.device_put(x, NamedSharding(mesh, s))
        for x, s in zip(batch, field_batch_specs(mesh))
    )


class FieldBatchFeed:
    """The mesh's ``place`` for the training loop's feed
    (``data.wrap_prefetch(batches, depth, place=...)``): batches come to
    the loop example-sharded over ``mesh``, each addressable device's
    shard made and sent by a worker of its own.

    ``feed(batch)`` takes a whole host batch ``(ids, vals, labels,
    weights)``: :func:`pad_field_batch` + :func:`shard_field_batch`.
    ``feed.from_rows(take, sel, weights)`` is what
    ``data.PlacedBatches`` calls when the source can hand the batch over
    as row numbers (``data.Batches``): ``sel`` is cut into one contiguous
    run per device, in the order the sharding lays rows out, and one
    worker per device gathers its run straight into ``[b, F_pad]``
    arrays (``take(run, F_pad)``), sends them to ITS device, and the
    global arrays are assembled from the pieces — the same bits on the
    same devices as the whole-batch form, with neither a whole host
    batch nor a second pass to pad it. The workers are as many as the
    sharding's addressable devices (a pool, started at the first batch;
    one device: the caller's own thread) and ``close()`` joins them.
    Single-process meshes only: across processes each feeds its own
    rows (:func:`shard_field_batch_local`).
    """

    def __init__(self, mesh, num_fields: int):
        self._mesh = mesh
        self._num_fields = num_fields
        self._n_feat = mesh.shape["feat"]
        self._f_pad = padded_num_fields(num_fields, self._n_feat)
        self._shardings = tuple(
            NamedSharding(mesh, s) for s in field_batch_specs(mesh))
        self._pool = None

    def __call__(self, batch):
        return shard_field_batch(
            pad_field_batch(batch, self._num_fields, self._n_feat),
            self._mesh)

    def from_rows(self, take, sel, weights):
        rows = self._shardings[2].addressable_devices_indices_map(
            (len(sel),))

        def shard(device):
            run = rows[device][0]
            return jax.device_put(
                (*take(sel[run], self._f_pad), weights[run]), device)

        if len(rows) > 1 and self._pool is None:
            from concurrent.futures import ThreadPoolExecutor

            self._pool = ThreadPoolExecutor(
                len(rows), thread_name_prefix="fm-spark-feed")
        each = self._pool.map if self._pool is not None else map
        parts = list(each(shard, rows))
        return tuple(
            jax.make_array_from_single_device_arrays(
                (len(sel), *parts[0][k].shape[1:]), sharding,
                [part[k] for part in parts])
            for k, sharding in enumerate(self._shardings)
        )

    def close(self) -> None:
        pool, self._pool = self._pool, None
        if pool is not None:
            pool.shutdown(wait=True, cancel_futures=True)


def shard_field_batch_local(batch, mesh):
    """Multi-host batch placement: each PROCESS supplies only ITS slice
    of the global batch (local rows = global_batch / process_count — the
    per-host input shard, SURVEY.md §4 "per-host input shards"), and the
    global array is assembled without ever replicating host data. The
    single-process :func:`shard_field_batch` device_puts the full batch
    instead (host data is already global there)."""
    import numpy as np

    return tuple(
        jax.make_array_from_process_local_data(
            NamedSharding(mesh, s), np.asarray(x)
        )
        for x, s in zip(batch, field_batch_specs(mesh))
    )


def _mesh_geometry(spec, mesh):
    """Shared layout constants + validity guards for the field-sharded
    train AND eval paths (single definition so the 2-D divisibility guard
    and padding math can never diverge between them)."""
    n_feat = mesh.shape["feat"]
    n_row = mesh.shape.get("row", 1)
    two_d = n_row > 1
    if two_d and spec.bucket % n_row:
        raise ValueError(
            f"bucket={spec.bucket} must divide evenly over n_row={n_row} "
            "row shards"
        )
    f_pad = padded_num_fields(spec.num_fields, n_feat)
    return dict(
        n_feat=n_feat, n_row=n_row, two_d=two_d,
        bucket_local=spec.bucket // n_row, f_pad=f_pad,
        f_local=f_pad // n_feat,
        score_axes=("feat", "row") if two_d else "feat",
    )


@dataclasses.dataclass(frozen=True)
class _Fwd:
    """:func:`_field_forward`'s result (named fields instead of the old
    positional 11-tuple — VERDICT r3: positional contracts break silently
    on extension). Traced values only; never crosses a jit boundary."""

    scores: object       # [B] replicated (or [B/n] local, score_shard)
    s: object            # [B, k] psum'd factor sums
    xvs: object          # f_local × [B, k] local xv terms
    xv_fulls: object     # f_local × [B, k+1] (gfull=True only, else None)
    rows: object         # f_local × [B, width] gathered rows
    vals_c: object       # [B, F_pad] compute-dtype vals (post re-shard)
    uidx: object         # single-owner scatter targets (None on compact)
    urows: object        # compact unique-row buffers (None on plain)
    labels: object       # [B] full-batch labels (post all_gather)
    weights: object      # [B] full-batch weights
    aux: object          # compact aux in effect (host or device-built)
    ovf: object          # device-compact overflow count (None otherwise)


def _score_block(g):
    """(chip linear index, chip count) over the score axes, feat-major /
    row-minor — the SAME order ``lax.all_gather`` over
    ``g["score_axes"]`` concatenates, so a sliced-then-gathered [B]
    vector reconstructs the global example order (equivalence-tested on
    the 2-D mesh in tests/test_score_sharded.py)."""
    idx = lax.axis_index("feat")
    nsh = g["n_feat"]
    if g["two_d"]:
        idx = idx * g["n_row"] + lax.axis_index("row")
        nsh = nsh * g["n_row"]
    return idx, nsh


def _ownership_mask(g, ids):
    """Localize global ids to THIS row shard's bucket range: returns
    ``(loc, own)`` — local ids and the ownership mask. The single
    definition of the 2-D ownership contract (FM and FFM forwards,
    plain and device-compact paths — the sentinel/clip handling at each
    call site differs, the contract must not)."""
    lo = lax.axis_index("row") * g["bucket_local"]
    loc = ids - lo
    own = (loc >= 0) & (loc < g["bucket_local"])
    return loc, own


def _field_forward(spec, g, gat, vw, w0, ids, vals, labels, weights,
                   caux=None, device_cap: int = 0, add_bias: bool = True,
                   gfull: bool = False, psum_dtype=None,
                   score_shard: bool = False):
    """The field-sharded forward, shared by the train body and the eval
    step: example-sharded → field-sharded re-shard (all_to_all over
    ``feat``; labels/weights ride all_gathers in the SAME collective
    order so the example permutation stays consistent), 2-D ownership-
    masked local gathers, and ONE psum group of the partial sums.

    ``caux`` (1-D mesh only) is the chip's LOCAL slice of the compact
    host-dedup aux (ops/scatter.compact_aux over the GLOBAL batch,
    stacked [F_pad, ...] and sharded over ``feat``): the all_to_all
    reconstructs each local field's full-B column in global host row
    order — exactly the order the host built the aux from — so the
    compact expansion applies per local field unchanged.

    ``device_cap`` > 0 selects the DEVICE-built compact aux instead
    (ops/scatter.device_compact_aux on each owned column, after the
    re-shard): no host aux operand, so it composes with multi-process
    feeds, and on a 2-D mesh each row shard compacts its ownership-
    masked ids (non-owned lanes collapse into one out-of-range segment
    whose writes drop — note that segment consumes one of the ``cap``
    slots). Exclusive with ``caux``.

    Returns an :class:`_Fwd` (see its field docs) — scores replicated
    across the mesh; the training body additionally consumes the locals
    for its analytic backward. ``gfull=True`` computes the full-width
    ``xv_fulls = rows·x`` products once and derives ``xvs`` (and the
    linear partial sum) from them — bitwise-identical forward values,
    and the backward can then build each g_full without a per-field
    concat (TrainConfig.gfull_fused).
    """
    cd = spec.cdtype
    k = spec.rank
    if caux is None:
        # The host-compact path never consumes per-lane ids (the aux
        # carries the gather/scatter targets), so its ids all_to_all is
        # skipped outright rather than left for XLA DCE to (maybe)
        # elide. The device-compact path needs the ids to build the aux.
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
    if device_cap > 0:
        own = None
        cids = ids
        extra = None
        if g["two_d"]:
            # Ownership masking BEFORE the sort: every non-owned lane
            # takes the out-of-range id ``bucket_local``, so all of them
            # collapse into the tail segment — its useg entry is OOB
            # (writes drop) and its expanded rows are zeroed below.
            # Each real segment is wholly owned by exactly one row shard
            # (ids in [lo, lo+bucket_local)), so owned segment sums are
            # complete without any cross-shard reduction. The sentinel
            # segment is discounted from overflow accounting (dropping
            # it is the point, not data loss).
            loc, own = _ownership_mask(g, ids)
            cids = jnp.where(own, loc, g["bucket_local"])
            extra = jnp.any(~own, axis=0).astype(jnp.int32)
        aux, ovf = _device_compact_aux_all(cids, device_cap, g["f_local"],
                                           extra_segs=extra)
        urows, rows = _compact_gather_all(
            [vw[f] for f in range(g["f_local"])], aux, cd,
            mask_overflow=True,
        )
        if own is not None:
            rows = [r * own[:, f, None] for f, r in enumerate(rows)]
        uidx = None
    elif g["two_d"]:
        # Each (field, example) id is owned by exactly one row shard:
        # gather locally where owned, zero elsewhere; the psum over both
        # axes reconstructs the exact sums. Non-owned update lanes go to
        # an out-of-bounds sentinel row (XLA scatter drop) — single-owner
        # writes.
        loc, own = _ownership_mask(g, ids)
        gidx = jnp.clip(loc, 0, g["bucket_local"] - 1)
        rows = [
            r * own[:, f, None]
            for f, r in enumerate(_gather_all(gat, vw, gidx, cd))
        ]
        uidx = jnp.where(own, loc, g["bucket_local"])
    elif caux is not None:
        urows, rows = _compact_gather_all(
            [vw[f] for f in range(g["f_local"])], caux, cd
        )
        uidx = None  # compact writes target the aux's cap lanes, not ids
    else:
        rows = _gather_all(gat, vw, ids, cd)
        uidx = ids
    xv_fulls = None
    if gfull:
        xv_fulls = [r * vals_c[:, f : f + 1] for f, r in enumerate(rows)]
        xvs = [x[:, :k] for x in xv_fulls]
    else:
        xvs = [r[:, :k] * vals_c[:, f : f + 1] for f, r in enumerate(rows)]
    s_p = sum(xvs)
    sq_p = sum(jnp.sum(x * x, axis=1) for x in xvs)
    if not spec.use_linear:
        lin_p = jnp.zeros((vals.shape[0],), cd)  # vals is post-all_to_all
    elif gfull:
        lin_p = sum(x[:, k] for x in xv_fulls)
    else:
        lin_p = sum(r[:, k] * vals_c[:, f] for f, r in enumerate(rows))
    # The scores collective: [B,k] + 2·[B] per step; tables never move.
    # ``psum_dtype`` (TrainConfig.collective_dtype) halves the wire
    # bytes of this — the projection model's dominant ICI term — at
    # bf16 wire precision; results come back in compute dtype.
    s = _psum_wire(s_p, g["score_axes"], psum_dtype, cd)
    sq = _psum_wire(sq_p, g["score_axes"], psum_dtype, cd)
    lin = _psum_wire(lin_p, g["score_axes"], psum_dtype, cd)
    if score_shard:
        # Score-sharded (TrainConfig.score_sharded): each chip reduces
        # the [B, k] score math for ITS example block only — the one
        # B-proportional term that does not otherwise shard
        # (projection.py). Per-example ops are elementwise, so the
        # sliced values are exactly the replicated computation's.
        # ``s`` stays fully replicated (the backward needs it for every
        # example); the caller all_gathers dscores.
        idx, nsh = _score_block(g)
        b_full = s.shape[0]
        if b_full % nsh:
            raise ValueError(
                f"score_sharded requires the global batch ({b_full}) "
                f"to divide by the mesh size ({nsh})"
            )
        bs = b_full // nsh
        s_red = lax.dynamic_slice_in_dim(s, idx * bs, bs)
        sq_red = lax.dynamic_slice_in_dim(sq, idx * bs, bs)
        lin_red = lax.dynamic_slice_in_dim(lin, idx * bs, bs)
    else:
        s_red, sq_red, lin_red = s, sq, lin
    scores = 0.5 * (jnp.sum(s_red * s_red, axis=1) - sq_red)
    if spec.use_linear:
        scores = scores + lin_red
    if spec.use_bias and add_bias:
        # DeepFM's caller folds the bias into its head loss instead
        # (add_bias=False) so the dense-side vjp sees it.
        scores = scores + w0.astype(cd)
    return _Fwd(scores=scores, s=s, xvs=xvs, xv_fulls=xv_fulls, rows=rows,
                vals_c=vals_c, uidx=uidx, urows=urows, labels=labels,
                weights=weights, aux=aux, ovf=ovf)


# The mesh steps take the COMPACT host aux alone (a full-batch one would
# train without its fast path), and on a 1-D mesh alone (below).
FIELD_FM_MESH = Serves(
    COMPACT_LEVERS - {"host_dedup"}
    | {"use_pallas", "gfull_fused", "collective_dtype", "score_sharded"},
    remedy=SGD_TABLES)


def _make_field_local_step(spec, config: TrainConfig, mesh):
    """Build the FM sharded LOCAL step (the per-shard function inside
    the shard_map) plus its layout facts. Shared by the per-step wrapper
    (:func:`make_field_sharded_sgd_body`) and the multi-step roll
    (:func:`make_field_sharded_multistep`) so the step math has one
    definition. Returns ``(local_step, host_compact)``."""
    from fm_spark_tpu.models.field_fm import FieldFMSpec

    if type(spec) is not FieldFMSpec:
        raise ValueError("expected a FieldFMSpec")
    if not spec.fused_linear:
        raise ValueError("field-sharded step requires fused_linear=True")
    refuse_unserved(config, FIELD_FM_MESH, "the field-sharded FM step",
                    spec.loss)
    if set(mesh.axis_names) not in ({"feat"}, {"feat", "row"}):
        raise ValueError(
            "field-sharded step runs on a ('feat',) or ('feat', 'row') "
            "mesh; see module docstring (use make_field_mesh)"
        )
    wire = _collective_dtype(config)
    g = _mesh_geometry(spec, mesh)
    compact = config.compact_cap > 0
    device_cap = config.compact_cap if config.compact_device else 0
    host_compact = compact and not config.compact_device
    if host_compact:
        # Compact HOST-dedup on the sharded step: supported on the 1-D
        # feat mesh — the aux is built from the GLOBAL batch and shards
        # field-wise (see _field_forward). The 2-D mesh's row-ownership
        # masking is incompatible with a host aux built from raw global
        # ids (a segment's owner depends on the row shard), and plain
        # full-B host_dedup is a measured loser — both rejected. The
        # DEVICE-built aux (config.compact_device) lifts both limits.
        if g["two_d"]:
            raise ValueError(
                "host-built compact_cap on the sharded step requires a "
                "1-D ('feat',) mesh; use compact_device=True for 2-D "
                "(feat, row) meshes"
            )

    sr_base_key = _sr_base_key(config)
    gat = _gather_fn(config)
    per_example_loss = losses_lib.loss_fn(spec.loss)
    cd = spec.cdtype
    k = spec.rank
    f_pad, f_local = g["f_pad"], g["f_local"]
    two_d = g["two_d"]
    lr_at = _lr_at(config)

    def local_step(params, step_idx, ids, vals, labels, weights,
                   caux=None):
        # Local blocks in: vw [f_local, bucket/n_row, width]; ids/vals
        # [B/n, F_pad]; labels/weights [B/n]; caux (host compact) the
        # [f_local, ...] aux slices. The shared forward (_field_forward)
        # re-shards, gathers, and psums; the backward below is
        # training-only.
        if host_compact and caux is None:
            raise ValueError(
                "compact sharded step needs the batch's compact_aux "
                "operand (stacked [F_pad, ...], sharded over feat)"
            )
        vw = params["vw"]
        w0 = params["w0"]
        fwd = _field_forward(
            spec, g, gat, vw, w0, ids, vals, labels, weights, caux=caux,
            device_cap=device_cap, gfull=config.gfull_fused,
            psum_dtype=wire, score_shard=config.score_sharded,
        )
        s, xvs, rows, vals_c = fwd.s, fwd.xvs, fwd.rows, fwd.vals_c
        uidx, urows, aux, ovf = fwd.uidx, fwd.urows, fwd.aux, fwd.ovf
        labels, weights = fwd.labels, fwd.weights

        # From here on every chip holds identical full-batch values
        # (score_sharded: scores/dscores are computed on this chip's
        # example block, then dscores is replicated by one tiny [B]
        # all_gather — per-example values identical to the replicated
        # computation; only the scalar loss reassociates).
        wsum = jnp.maximum(jnp.sum(weights), 1.0)

        if config.score_sharded:
            idx, nsh = _score_block(g)
            bs = labels.shape[0] // nsh
            labels_l = lax.dynamic_slice_in_dim(labels, idx * bs, bs)
            weights_l = lax.dynamic_slice_in_dim(weights, idx * bs, bs)

            def batch_loss(sc):
                return jnp.sum(
                    per_example_loss(sc, labels_l) * weights_l) / wsum

            loss_l, dscores_l = jax.value_and_grad(batch_loss)(fwd.scores)
            loss = lax.psum(loss_l, g["score_axes"])
            dscores = lax.all_gather(dscores_l, g["score_axes"],
                                     tiled=True)
        else:
            def batch_loss(sc):
                return jnp.sum(
                    per_example_loss(sc, labels) * weights) / wsum

            loss, dscores = jax.value_and_grad(batch_loss)(fwd.scores)
        lr = lr_at(step_idx)
        touched = weights > 0

        if config.gfull_fused:
            # Shared construction (sparse.py:_gfull_grads) — same
            # numerics as the single-chip body by definition. Non-owned
            # lanes still produce garbage that the sentinel index /
            # dropped segment discards.
            g_fulls = _gfull_grads(
                dscores, vals_c, s, fwd.xv_fulls, rows, touched, k, cd,
                spec.use_linear, config,
            )
        else:
            g_fulls = []
            for f in range(f_local):
                # s − xvs[f] is exactly s_{-f} for OWNED lanes (their xv
                # is in the psum); non-owned lanes produce garbage that
                # the sentinel index drops.
                g_v = dscores[:, None] * vals_c[:, f : f + 1] * (s - xvs[f])
                if config.reg_factors:
                    g_v = g_v + config.reg_factors * rows[f][:, :k] * touched[:, None]
                if spec.use_linear:
                    g_l = dscores * vals_c[:, f]
                    if config.reg_linear:
                        g_l = g_l + config.reg_linear * rows[f][:, k] * touched
                else:
                    g_l = jnp.zeros_like(dscores)
                g_fulls.append(jnp.concatenate([g_v, g_l[:, None]], axis=1))
        # SR keys: one stream per (global field, row shard) so noise never
        # correlates across the chips sharing a field.
        field_offset = lax.axis_index("feat") * f_local
        if two_d:
            field_offset = field_offset + lax.axis_index("row") * f_pad
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
        new_vw = jnp.stack(new_slices, axis=0)
        out = {"w0": w0, "vw": new_vw}
        if spec.use_bias:
            # dscores is replicated — a plain sum is the global bias grad.
            out["w0"] = w0 - lr * (jnp.sum(dscores) + config.reg_bias * w0)
        if ovf is not None:
            # Worst overflow anywhere on the mesh; the fold (policy
            # 'error') poisons the replicated loss so every host sees it.
            loss = _fold_overflow(
                loss, lax.pmax(ovf, g["score_axes"]), config
            )
        return out, loss

    return local_step, host_compact


@declares(FIELD_FM_MESH)
def make_field_sharded_sgd_body(spec, config: TrainConfig, mesh):
    """Unjitted ``(params, step_idx, ids, vals, labels, weights) →
    (params, loss)`` over stacked/sharded inputs; same semantics as the
    single-chip fused body."""
    local_step, host_compact = _make_field_local_step(spec, config, mesh)
    if host_compact:
        return jax.shard_map(
            local_step,
            mesh=mesh,
            in_specs=(field_param_specs(mesh), P(),
                      *field_batch_specs(mesh),
                      (P("feat", None),) * 5),
            out_specs=(field_param_specs(mesh), P()),
            check_vma=False,
        )
    return jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(field_param_specs(mesh), P(), *field_batch_specs(mesh)),
        out_specs=(field_param_specs(mesh), P()),
        check_vma=False,
    )


@declares(FIELD_FM_MESH)
def make_field_sharded_sgd_step(spec, config: TrainConfig, mesh):
    """Jitted field-sharded fused sparse-SGD step; params donated."""
    return jax.jit(
        make_field_sharded_sgd_body(spec, config, mesh), donate_argnums=(0,)
    )


def _check_sharded_multistep(config: TrainConfig, n: int):
    """Shared guards for the sharded rolls (single definition across
    the FM/FFM and DeepFM multistep factories): positive step count,
    and no host-built aux (its per-batch producer chain does not stack
    — compact_device composes with the roll instead)."""
    if n < 1:
        raise ValueError(f"steps per call must be >= 1, got {n}")
    if config.host_dedup or (
        config.compact_cap > 0 and not config.compact_device
    ):
        raise ValueError(
            "the sharded multistep does not take the host-built "
            "dedup/compact aux (per-batch producer chain); use "
            "compact_device=True"
        )


def stacked_field_batch_specs(mesh) -> tuple:
    """Batch PartitionSpecs for ``[m, ...]``-stacked batches (the
    sharded multi-step roll): the leading stack axis is replicated, the
    example axis shards over the mesh exactly as in
    :func:`field_batch_specs`."""
    return tuple(P(None, *tuple(sp)) for sp in field_batch_specs(mesh))


def shard_field_batch_stacked(stacked, mesh):
    """Device-place an ``[m, ...]``-stacked batch tuple
    (data/pipeline.StackedBatches over F_pad-padded batches) for
    :func:`make_field_sharded_multistep`. Host arrays go to their
    devices run by run, as in :func:`shard_field_batch`."""
    return tuple(
        jax.device_put(x, NamedSharding(mesh, sp))
        for x, sp in zip(stacked, stacked_field_batch_specs(mesh))
    )


def shard_field_batch_stacked_local(stacked, mesh):
    """Multi-host placement of an ``[m, ...]``-stacked batch: each
    PROCESS supplies only its row slice of every stacked step (the
    stacked form of :func:`shard_field_batch_local` — same leading-axis
    replication, example axis assembled across hosts without
    replication)."""
    import numpy as np

    return tuple(
        jax.make_array_from_process_local_data(
            NamedSharding(mesh, sp), np.asarray(x)
        )
        for x, sp in zip(stacked, stacked_field_batch_specs(mesh))
    )


def make_field_sharded_multistep(spec, config: TrainConfig, mesh, n: int):
    """Roll ``n`` FIELD-SHARDED fused steps into ONE compiled program —
    the multi-chip form of :func:`fm_spark_tpu.sparse.
    make_field_sparse_multistep` (round 4). The ``fori_loop`` runs
    INSIDE the shard_map, so per-call dispatch overhead — the
    projection model's ``t_fixed``, ~14% of a strong-scaled 8-chip
    step at the measured 2.5ms dispatch — is paid once per ``n`` steps;
    the collectives (all_to_all/psum/all_gather) repeat per iteration
    inside the single program.

    FM and FFM sharded bodies (pure SGD; no optax carry). The HOST-
    compact aux does not ride this roll (its producer chain is
    per-batch; use compact_device, which composes with everything) —
    rejected at construction. Returns ``mstep(params, step0, m, ids,
    vals, labels, weights) → (params, last_loss)`` over batches stacked
    on a leading ``[n, ...]`` axis (place with
    :func:`shard_field_batch_stacked`); ``m ≤ n`` dynamic, sticky −inf
    overflow semantics as in the single-chip roll.
    """
    from fm_spark_tpu.models.field_ffm import FieldFFMSpec

    _check_sharded_multistep(config, n)
    if isinstance(spec, FieldFFMSpec):
        local_step, _ = _make_ffm_local_step(spec, config, mesh)
    else:
        local_step, _ = _make_field_local_step(spec, config, mesh)

    def local_mstep(params, step0, m, ids, vals, labels, weights):
        def fbody(j, carry):
            p, prev = carry
            p, loss = local_step(p, step0 + j, ids[j], vals[j],
                                 labels[j], weights[j])
            # Sticky −inf, as in the single-chip roll.
            return p, jnp.where(jnp.isneginf(prev), prev, loss)

        return lax.fori_loop(0, m, fbody, (params, jnp.float32(0)))

    return jax.jit(
        jax.shard_map(
            local_mstep,
            mesh=mesh,
            in_specs=(field_param_specs(mesh), P(), P(),
                      *stacked_field_batch_specs(mesh)),
            out_specs=(field_param_specs(mesh), P()),
            check_vma=False,
        ),
        donate_argnums=(0,),
    )


def place_compact_aux(aux_padded, mesh):
    """Device-place an already-padded compact aux tuple for the sharded
    compact step (each [F_pad, ...] leaf sharded field-wise). Split from
    :func:`shard_compact_aux` so the CPU-side padding
    (:func:`stack_compact_aux`) can run in the prefetch producer thread
    while only this device_put stays on the consumer side."""
    sh = NamedSharding(mesh, P("feat", None))
    return tuple(jax.device_put(a, sh) for a in aux_padded)


def shard_compact_aux(aux, mesh, n_feat: int):
    """One-shot pad + device-place of a GLOBAL-batch compact aux tuple
    (:func:`fm_spark_tpu.ops.scatter.compact_aux`) for the sharded
    compact step."""
    return place_compact_aux(stack_compact_aux(aux, n_feat), mesh)


def stack_compact_aux(aux, n_feat: int):
    """Pad a GLOBAL-batch :func:`fm_spark_tpu.ops.scatter.compact_aux`
    tuple ([F, ...] arrays) to ``F_pad`` field slots for the sharded
    compact step. Padded fields get all-zero-id aux (1 segment holding
    every lane) — they write only into the zero padding tables, exactly
    like the plain path's padded columns. Place the result with
    :func:`place_compact_aux` (or use :func:`shard_compact_aux` for
    both halves at once)."""
    import numpy as np

    useg, segstart, segend, order, inv = (np.asarray(a) for a in aux)
    f, cap = useg.shape
    b = order.shape[1]
    f_pad = padded_num_fields(f, n_feat)
    pad = f_pad - f
    if not pad:
        return useg, segstart, segend, order, inv
    pu, ps, pe, po, pi = _pad_aux_blocks(pad, cap, b)
    return (
        np.concatenate([useg, pu]), np.concatenate([segstart, ps]),
        np.concatenate([segend, pe]), np.concatenate([order, po]),
        np.concatenate([inv, pi]),
    )


def _pad_aux_blocks(pad: int, cap: int, b: int):
    """The padded fields' aux blocks depend only on (pad, cap, b) —
    cached so the per-batch producer-thread call (stack_compact_aux via
    cli's MappedBatches) doesn't rebuild them every step."""
    import numpy as np

    cached = _PAD_AUX_CACHE.get((pad, cap, b))
    if cached is not None:
        return cached
    imax = np.iinfo(np.int32).max
    pu = np.zeros((pad, cap), np.int32)
    pu[:, 1:] = (imax - cap) + np.arange(1, cap, dtype=np.int32)
    ps = np.full((pad, cap), max(b - 1, 0), np.int32)
    pe = np.full((pad, cap), max(b - 1, 0), np.int32)
    ps[:, 0] = 0
    pe[:, 0] = max(b - 1, 0)
    po = np.ascontiguousarray(
        np.broadcast_to(np.arange(b, dtype=np.int32), (pad, b))
    )
    pi = np.zeros((pad, b), np.int32)
    blocks = (pu, ps, pe, po, pi)
    _PAD_AUX_CACHE.clear()  # one live shape per run is the norm
    _PAD_AUX_CACHE[(pad, cap, b)] = blocks
    return blocks


_PAD_AUX_CACHE: dict = {}


def make_field_sharded_eval_step(spec, mesh):
    """Metrics-accumulation step on the FIELD-SHARDED layout — periodic
    eval without gathering the multi-GB tables to the host (the default
    evaluator reconstructs canonical params per eval; at BASELINE.json:9
    scale that is ~3 GB of device→host traffic each time).

    Same forward as :func:`make_field_sharded_sgd_body` (all_to_all batch
    re-shard, masked local gathers on a 2-D mesh, one psum of partial
    sums), then a replicated :func:`metrics.update_metrics` — every chip
    sees the full psum'd score vector, so the metrics state stays
    replicated by construction. FieldFM; the DeepFM analog (replicated
    MLP head over the all_gathered ``h``) is
    :func:`make_field_deepfm_sharded_eval_step`.

    Returns ``estep(params, mstate, ids, vals, labels, weights) →
    mstate`` over stacked/sharded params and padded/sharded batches.
    """
    from fm_spark_tpu.models import base as model_base
    from fm_spark_tpu.models.field_fm import FieldFMSpec
    from fm_spark_tpu.utils import metrics as metrics_lib

    if type(spec) is not FieldFMSpec:
        raise ValueError("expected a FieldFMSpec")
    if not spec.fused_linear:
        raise ValueError("field-sharded eval requires fused_linear=True")
    per_example_loss = losses_lib.loss_fn(spec.loss)
    g = _mesh_geometry(spec, mesh)
    gat = lambda table, idx: table[idx]  # eval always takes the XLA gather

    def local_eval(params, mstate, ids, vals, labels, weights):
        fwd = _field_forward(
            spec, g, gat, params["vw"], params["w0"], ids, vals, labels,
            weights,
        )
        per = per_example_loss(fwd.scores, fwd.labels)
        preds = model_base.predict_from_scores(spec, fwd.scores)
        return metrics_lib.update_metrics(
            mstate, fwd.scores, fwd.labels, per, fwd.weights,
            predictions=preds
        )

    mstate_specs = jax.tree_util.tree_map(
        lambda _: P(), jax.eval_shape(metrics_lib.init_metrics)
    )
    return jax.jit(jax.shard_map(
        local_eval,
        mesh=mesh,
        in_specs=(field_param_specs(mesh), mstate_specs,
                  *field_batch_specs(mesh)),
        out_specs=mstate_specs,
        check_vma=False,
    ))


def evaluate_field_sharded(spec, mesh, params, batches, estep=None) -> dict:
    """Stream host batches through the sharded eval step → finalized
    metrics. ``params`` are the live stacked/sharded arrays; each batch
    is padded to the mesh's field multiple and sharded like training
    batches. Pass a prebuilt ``estep`` to avoid a re-trace per call."""
    from fm_spark_tpu.models.field_deepfm import FieldDeepFMSpec
    from fm_spark_tpu.models.field_ffm import FieldFFMSpec
    from fm_spark_tpu.utils import metrics as metrics_lib

    if estep is None:
        if type(spec) is FieldDeepFMSpec:
            estep = make_field_deepfm_sharded_eval_step(spec, mesh)
        elif type(spec) is FieldFFMSpec:
            estep = make_field_ffm_sharded_eval_step(spec, mesh)
        else:
            estep = make_field_sharded_eval_step(spec, mesh)
    n_feat = mesh.shape["feat"]
    pc = jax.process_count()
    if pc > 1:
        # Every host iterates the SAME eval stream; each feeds only its
        # row slice of each batch and the global array is assembled
        # across hosts (mirrors the training-side local placement).
        import numpy as np

        pid = jax.process_index()

        def place(b):
            rows = b[0].shape[0]
            if rows % pc:
                raise ValueError(
                    f"eval batch size {rows} must be divisible by the "
                    f"process count ({pc})"
                )
            lo = pid * (rows // pc)
            local = tuple(np.asarray(x)[lo: lo + rows // pc] for x in b)
            return shard_field_batch_local(local, mesh)
    else:
        place = lambda b: shard_field_batch(b, mesh)
    mstate = metrics_lib.init_metrics()
    for batch in batches:
        sb = place(pad_field_batch(tuple(batch), spec.num_fields, n_feat))
        mstate = estep(params, mstate, *sb)
    return {
        k: float(v) for k, v in metrics_lib.finalize_metrics(mstate).items()
    }




# ------------------------------------------------------------- family splits
# The DeepFM and FFM machinery live in sibling modules since round 4
# (this module had grown to carry three families); re-exported here so
# every existing import path (cli, tests, bench, __graft_entry__) keeps
# working unchanged. The sibling modules reference this module's layout
# helpers through the module object at call time, so the import cycle
# is benign.
from fm_spark_tpu.parallel.deepfm_step import (  # noqa: E402,F401
    _make_deepfm_sharded_one_step,
    field_deepfm_param_specs,
    make_field_deepfm_sharded_eval_step,
    make_field_deepfm_sharded_multistep,
    make_field_deepfm_sharded_step,
    shard_field_deepfm_params,
    stack_field_deepfm_params,
    unstack_field_deepfm_params,
)
from fm_spark_tpu.parallel.ffm_step import (  # noqa: E402,F401
    _ffm_field_forward,
    _make_ffm_local_step,
    make_field_ffm_sharded_body,
    make_field_ffm_sharded_eval_step,
    make_field_ffm_sharded_step,
)


# --------------------------------------------------------------------------
# AOT warm-start entries (see fm_spark_tpu/sparse.py's counterpart): the
# field-sharded fused steps lowered against abstract SHARDED shapes —
# compile (and persist, with utils/compile_cache enabled) before any
# table or batch is placed on the mesh.
# --------------------------------------------------------------------------


def lower_field_sharded_step(spec, config: TrainConfig, mesh,
                             batch_size: int, steps_per_call: int = 1):
    """Lower the field-sharded fused step (FM / FFM / DeepFM — the
    multi-chip CTR fast path) — or its ``steps_per_call`` roll —
    against abstract sharded shapes. Returns a ``jax.stages.Lowered``.

    Host-built compact aux configs are rejected (their aux rides each
    batch from the producer thread; precompiling would need a live
    batch) — ``compact_device`` is the composable form, and it lowers
    here like any other lever.
    """
    import functools

    from fm_spark_tpu.models.field_deepfm import FieldDeepFMSpec
    from fm_spark_tpu.models.field_ffm import FieldFFMSpec
    from fm_spark_tpu.parallel.deepfm_step import (
        field_deepfm_param_specs,
        make_field_deepfm_sharded_multistep,
        make_field_deepfm_sharded_step,
        stack_field_deepfm_params,
    )
    from fm_spark_tpu.parallel.ffm_step import make_field_ffm_sharded_step
    from fm_spark_tpu.parallel.step import (
        _sharded_abstract as _abstract_sharded_tree,
    )

    if steps_per_call < 1:
        raise ValueError(
            f"steps per call must be >= 1, got {steps_per_call}"
        )
    if config.host_dedup:
        raise ValueError(
            "the AOT entry cannot precompile a host-built aux step "
            "(the aux ships with each batch); use compact_device=True"
        )
    n = mesh.size
    if batch_size % n:
        raise ValueError(
            f"batch_size={batch_size} must divide by the mesh size ({n})"
        )
    n_feat = mesh.shape["feat"]
    is_deepfm = isinstance(spec, FieldDeepFMSpec)
    if is_deepfm and not spec.fm_interaction:
        raise ValueError(f"{type(spec).__name__} has no field-sharded step")
    stack = (stack_field_deepfm_params if is_deepfm
             else stack_field_params)
    stacked_struct = jax.eval_shape(
        lambda key: stack(spec, spec.init(key), n_feat),
        jax.random.key(0),
    )
    pspecs = (field_deepfm_param_specs(spec, mesh) if is_deepfm
              else field_param_specs(mesh))
    params_abs = _abstract_sharded_tree(stacked_struct, mesh, pspecs)
    B = batch_size
    f_pad = padded_num_fields(spec.num_fields, n_feat)
    sds = jax.ShapeDtypeStruct
    batch_struct = (
        sds((B, f_pad), jnp.int32), sds((B, f_pad), jnp.float32),
        sds((B,), jnp.float32), sds((B,), jnp.float32),
    )
    batch_abs = _abstract_sharded_tree(
        batch_struct, mesh, field_batch_specs(mesh)
    )
    i32 = sds((), jnp.int32)
    multi = steps_per_call > 1

    def stack_batch(abs_batch):
        return tuple(
            jax.ShapeDtypeStruct(
                (steps_per_call, *a.shape), a.dtype,
                sharding=NamedSharding(mesh, sp),
            )
            for a, sp in zip(abs_batch, stacked_field_batch_specs(mesh))
        )

    if is_deepfm:
        if multi:
            mstep = make_field_deepfm_sharded_multistep(
                spec, config, mesh, steps_per_call
            )
            opt_abs = jax.eval_shape(mstep.init_opt_state, params_abs)
            return mstep.lower(params_abs, opt_abs, i32, i32,
                               *stack_batch(batch_abs))
        step = make_field_deepfm_sharded_step(spec, config, mesh)
        opt_abs = jax.eval_shape(step.init_opt_state, params_abs)
        # The public wrapper is a plain function (it carries
        # init_opt_state); re-jit the underlying body for .lower().
        from fm_spark_tpu.parallel.deepfm_step import (
            _make_deepfm_sharded_one_step,
        )

        apply_one, _ = _make_deepfm_sharded_one_step(spec, config, mesh)
        jitted = functools.partial(jax.jit, donate_argnums=(0, 1))(
            apply_one
        )
        return jitted.lower(params_abs, opt_abs, i32, *batch_abs)

    if multi:
        mstep = make_field_sharded_multistep(spec, config, mesh,
                                             steps_per_call)
        return mstep.lower(params_abs, i32, i32,
                           *stack_batch(batch_abs))
    step = (
        make_field_ffm_sharded_step(spec, config, mesh)
        if isinstance(spec, FieldFFMSpec)
        else make_field_sharded_sgd_step(spec, config, mesh)
    )
    return step.lower(params_abs, i32, *batch_abs)


def precompile_field_sharded_step(spec, config: TrainConfig, mesh,
                                  batch_size: int,
                                  steps_per_call: int = 1):
    """Eagerly compile the field-sharded fused step — the multi-chip
    warm-start producer; returns the ``jax.stages.Compiled``."""
    return lower_field_sharded_step(
        spec, config, mesh, batch_size, steps_per_call
    ).compile()
