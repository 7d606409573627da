"""Per-coordinate adaptive optimizers for hashed-sparse CTR training.

Continuous CTR training (ROADMAP item 5 / ISSUE 13) is where plain SGD
stops being the reference answer: hashed feature frequencies span five
orders of magnitude, so a single global learning rate either burns the
head ids or never moves the tail. The standard fixes — per-coordinate
AdaGrad and FTRL-Proximal (McMahan et al., "Ad Click Prediction: a View
from the Trenches") — keep one or two scalar slots PER COORDINATE and
derive each coordinate's own step size from its accumulated gradient
history. This module provides both, in two forms that share one set of
update rules:

- **Dense optax form** (:func:`ftrl`): a ``GradientTransformation`` for
  the generic optax train step (strategy ``single``/``dp``/``row``) —
  ``train.make_optimizer`` routes ``TrainConfig.optimizer='ftrl'`` here,
  so ``cli train --optimizer ftrl`` works everywhere the dense step
  does, and the z/n slots ride checkpoints inside ``opt_state`` like
  any optax state. AdaGrad's dense form stays ``optax.adagrad`` (it
  predates this module).

- **Field-table form** (``sparse.make_field_ffm_adagrad_body``): the
  fused FieldFFM body under :func:`adagrad_rows`, as FFM's own paper
  trains it (Juan et al., RecSys 2016, Algorithm 1), on per-field
  tables of deployment size with slots from :func:`init_field_slots`.
  The FieldFM and FieldDeepFM bodies and the mesh steps still write
  their tables by plain SGD and refuse another rule by name.

- **Sparse row form** (:func:`make_sparse_adaptive_step`): the fused
  flat-FM analog of ``sparse.make_sparse_sgd_step``, riding the SAME
  dedup/scatter machinery (:func:`fm_spark_tpu.ops.scatter._dedup`'s
  segment sums + out-of-range-sentinel set-semantics writes): per-batch
  gradients are segment-summed per unique id, the touched rows AND
  their slot rows are gathered once, updated with the per-coordinate
  rule, and written back with one set per unique id — the slot tables
  never see a dense gradient. Dense parameter slots (the bias ``w0``)
  are deliberately EXCLUDED from the sparse slot set and keep plain
  SGD: one scalar does not need a frequency-adaptive schedule, and
  excluding it keeps the slot pytree exactly table-shaped.

Laziness contract: both rules are exactly lazy — a coordinate whose
batch gradient is zero is bit-unchanged (AdaGrad: ``n`` unchanged so
the step is 0; FTRL: ``z``/``n`` unchanged and the closed form
reproduces the stored weight, because :func:`ftrl_init_z` chooses the
initial ``z`` so the closed form equals the spec's init). The sparse
step therefore matches the dense transformation on every touched
coordinate and leaves untouched rows alone — pinned in
tests/test_optim.py.

FTRL has no use for the global ``lr_schedule``: its per-coordinate
``(beta + √n)/alpha`` IS the schedule (``alpha`` = the configured
learning rate), so the dense form ignores the schedule field rather
than mis-applying a second decay on top.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

__all__ = [
    "FtrlState",
    "adagrad_rows",
    "ftrl",
    "ftrl_init_z",
    "ftrl_rows",
    "init_adaptive_slots",
    "init_field_slots",
    "make_sparse_adaptive_step",
]

ADAPTIVE_OPTIMIZERS = ("ftrl", "adagrad")

#: AdaGrad's denominator floor (outside the sqrt — the McMahan paper's
#: form, NOT optax.adagrad's inside-the-sqrt initial accumulator).
ADAGRAD_EPS = 1e-8


# ------------------------------------------------------ per-row update rules


def adagrad_rows(rows, n, g, lr: float):
    """Per-coordinate AdaGrad on gathered rows.

    ``rows``/``n``/``g`` are [U, w] (or any matching shape): current
    weights, accumulated squared gradients, and this batch's summed
    gradient per coordinate. Returns ``(new_rows, new_n)`` in fp32.
    """
    g = g.astype(jnp.float32)
    n_new = n.astype(jnp.float32) + g * g
    step = lr * g / (jnp.sqrt(n_new) + ADAGRAD_EPS)
    return rows.astype(jnp.float32) - step, n_new


def ftrl_init_z(w0, alpha: float, beta: float):
    """The initial ``z`` that makes FTRL's closed form reproduce the
    spec's init (``n``=0, l1=0): ``w = -z·alpha/beta`` ⇒ ``z =
    -w·beta/alpha``. Without this, FTRL zeroes every coordinate on
    first touch — which kills FM factors outright (zero factors have
    zero interaction gradient and never recover)."""
    return -jnp.asarray(w0, jnp.float32) * (beta / alpha)


def ftrl_rows(rows, z, n, g, alpha: float, beta: float,
              l1: float, l2: float):
    """Per-coordinate FTRL-Proximal on gathered rows.

    The McMahan et al. update: ``σ = (√(n+g²) − √n)/α``, ``z += g −
    σ·w``, ``n += g²``, and the weight is the closed-form proximal
    solution of the accumulated problem. Returns ``(new_rows, new_z,
    new_n)`` in fp32. Exactly lazy: ``g = 0`` leaves all three
    unchanged (the closed form is a pure function of ``z``/``n``).
    """
    w = rows.astype(jnp.float32)
    g = g.astype(jnp.float32)
    z = z.astype(jnp.float32)
    n = n.astype(jnp.float32)
    n_new = n + g * g
    sigma = (jnp.sqrt(n_new) - jnp.sqrt(n)) / alpha
    z_new = z + g - sigma * w
    shrunk = jnp.sign(z_new) * jnp.maximum(jnp.abs(z_new) - l1, 0.0)
    denom = (beta + jnp.sqrt(n_new)) / alpha + l2
    return -shrunk / denom, z_new, n_new


# ------------------------------------------------------- dense (optax) form


class FtrlState(NamedTuple):
    """FTRL-Proximal per-coordinate slots (fp32, param-shaped)."""

    z: object
    n: object


def ftrl(alpha: float, beta: float = 1.0, l1: float = 0.0,
         l2: float = 0.0, l2_by_group: dict | None = None):
    """FTRL-Proximal as an optax ``GradientTransformation``.

    ``init`` seeds ``z`` from the incoming params via
    :func:`ftrl_init_z` so initialization survives the first touch;
    ``update`` returns ``new_w − w`` deltas (optax convention), cast to
    the gradient dtype. Per-coordinate slots are fp32 regardless of the
    param/compute dtype — slot precision is what the schedule is made
    of.

    L2 composition rule: the config's MLlib-style ``reg_*`` triple must
    NEVER be folded into the gradients FTRL sees — ``(g + λw)`` would
    corrupt the per-coordinate ``z``/``n`` statistics (the schedule
    itself). Instead ``l2_by_group`` maps top-level param groups
    (``w0``/``w``/``v``/``mlp`` — the :func:`~fm_spark_tpu.train
    ._group_reg` table) onto FTRL's own PROXIMAL l2 term, which is the
    rule's native, closed-form way of carrying L2; ``make_optimizer``
    routes the triple here and the dense train steps skip their
    gradient-side reg for FTRL. Unknown groups are an error — silently
    unregularized parameters are worse than a crash.
    """
    import optax

    if alpha <= 0:
        raise ValueError(f"ftrl needs alpha > 0, got {alpha}")

    def _l2_at(path) -> float:
        if l2_by_group is None:
            return l2
        top = path[0]
        key = str(getattr(top, "key", getattr(top, "idx", top)))
        if key not in l2_by_group:
            raise ValueError(
                f"no FTRL l2 group for param {key!r} "
                f"(know {sorted(l2_by_group)})")
        return float(l2_by_group[key]) + l2

    def init_fn(params):
        z = jax.tree_util.tree_map(
            lambda p: ftrl_init_z(p, alpha, beta), params)
        n = jax.tree_util.tree_map(
            lambda p: jnp.zeros(jnp.shape(p), jnp.float32), params)
        return FtrlState(z=z, n=n)

    def update_fn(updates, state, params=None):
        if params is None:
            raise ValueError("ftrl is a proximal rule; it needs params")

        # Three tree_maps re-running the rule per output; XLA CSEs the
        # shared subexpressions under jit, and it keeps the pytrees
        # honest (no tuple-leaf transpose tricks).
        def pick(i):
            return jax.tree_util.tree_map_with_path(
                lambda path, g, z, n, p: ftrl_rows(
                    p, z, n, g, alpha, beta, l1, _l2_at(path))[i],
                updates, state.z, state.n, params)

        deltas = jax.tree_util.tree_map_with_path(
            lambda path, g, z, n, p: (
                ftrl_rows(p, z, n, g, alpha, beta, l1,
                          _l2_at(path))[0]
                - p.astype(jnp.float32)).astype(g.dtype),
            updates, state.z, state.n, params)
        return deltas, FtrlState(z=pick(1), n=pick(2))

    return optax.GradientTransformation(init_fn, update_fn)


# ------------------------------------------------- sparse (scatter-path) form


def init_adaptive_slots(optimizer: str, spec, params) -> dict:
    """Slot pytree for :func:`make_sparse_adaptive_step` — one fp32
    table per SPARSE param table (``v``, and ``w`` when the spec uses
    the linear term); the dense ``w0`` slot is excluded by design.
    Checkpoint this dict as the step's ``opt_state`` — it rides
    save/restore like any other state tree."""
    if optimizer not in ADAPTIVE_OPTIMIZERS:
        raise ValueError(
            f"unknown adaptive optimizer {optimizer!r} "
            f"(know {ADAPTIVE_OPTIMIZERS})")
    slots: dict = {}
    tables = {"v": params["v"]}
    if spec.use_linear:
        tables["w"] = params["w"]
    for name, t in tables.items():
        if optimizer == "adagrad":
            slots[name] = {"n": jnp.zeros(t.shape, jnp.float32)}
        else:
            slots[name] = {
                "z": jnp.zeros(t.shape, jnp.float32),
                "n": jnp.zeros(t.shape, jnp.float32),
            }
    return slots


def init_field_slots(optimizer: str, params, keys,
                     init_accumulator: float = 0.0,
                     dense_keys=()) -> dict:
    """Slot pytree for the fused FIELD bodies (``sparse
    .make_field_ffm_adagrad_body``): ``{key: {"n": [tables]}}`` for
    every parameter key in ``keys`` (per-field table lists), one float32
    accumulator table per parameter table and of its shape, filled with
    ``init_accumulator`` (AdaGrad's ``G0``; the flat path's
    :func:`init_adaptive_slots` keeps 0). The bias has no slot, as
    there. ``dense_keys`` (``sparse.make_field_dcn_adagrad_body``, whose
    stacks take the tables' rule) adds ``{key: accumulators}`` for each:
    a float32 leaf, filled alike, for every leaf of ``params[key]``. The
    same tree is the step's ``opt_state`` and what a checkpoint stores
    beside the tables.

    One table at a time, each waited for before the next is queued, as
    ``FieldFFMSpec.init`` makes its tables: buffers are allocated as
    work is queued, so a queue that runs ahead of the device leaves the
    tables in another place every run, and where they lie sets a run's
    speed (PERF.md §6, PR 33)."""
    if optimizer != "adagrad":
        raise ValueError(
            f"field-table slots exist for 'adagrad', not {optimizer!r}")

    slots = {key: {"n": [
        jax.block_until_ready(
            jnp.full(t.shape, init_accumulator, jnp.float32))
        for t in params[key]]} for key in keys}
    for key in dense_keys:
        slots[key] = jax.tree.map(
            lambda leaf: jnp.full(leaf.shape, init_accumulator, jnp.float32),
            params[key])
    return slots


def seed_ftrl_slots(slots: dict, params, alpha: float,
                    beta: float) -> dict:
    """Re-seed FTRL ``z`` slots from the CURRENT param tables (fresh
    start only — restored slots already carry their history)."""
    out = dict(slots)
    for name in out:
        out[name] = dict(out[name],
                         z=ftrl_init_z(params[name], alpha, beta))
    return out


def make_sparse_adaptive_step(spec, config, *, beta: float = 1.0,
                              l1: float = 0.0, l2: float = 0.0):
    """Fused sparse per-coordinate-optimizer step for the flat FM
    family — ``sparse.make_sparse_sgd_step``'s adaptive sibling.

    Returns ``step(params, slots, ids, vals, labels, weights) →
    (params, slots, loss)`` with donated params/slots. The backward is
    the same analytic per-row rule as the SGD step; the write-back
    rides the dedup half of the scatter path: duplicate ids are
    segment-summed (``ops.scatter._dedup``) so each unique coordinate
    sees its TOTAL batch gradient exactly once — adaptive rules are
    read-modify-write and double-counting a duplicate id would double
    its schedule, not just its step — and both the row and its slot
    row(s) are written with one set-semantics scatter through the same
    out-of-range-sentinel mask the SGD dedup mode uses. ``w0`` (the
    dense slot) keeps plain constant-lr SGD.

    Regularization: ``l1``/``l2`` are FTRL's built-in proximal terms;
    the config's ``reg_*`` triple is rejected (two L2 paths silently
    composing would be worse than a crash).
    """
    import functools

    from fm_spark_tpu.models.fm import FMSpec
    from fm_spark_tpu.ops import losses as losses_lib
    from fm_spark_tpu.ops.scatter import _dedup

    if type(spec) is not FMSpec:
        raise ValueError(
            "the sparse adaptive step supports the flat FM family only "
            "(of the fused field families FieldFFM takes 'adagrad', "
            "sparse.make_field_ffm_adagrad_body; the others write by "
            "plain SGD)")
    from fm_spark_tpu.sparse import Serves, refuse_unserved

    # TieredTrainer builds THIS step over its hot-tier window with
    # embed_tier neutralized to 'off'; a bare 'require' here means the
    # caller skipped the tiered trainer.
    refuse_unserved(config, Serves(optimizers=ADAPTIVE_OPTIMIZERS),
                    "the flat-table sparse adaptive step", spec.loss)
    if config.reg_bias or config.reg_linear or config.reg_factors:
        raise ValueError(
            "the adaptive step rejects the reg_* triple: FTRL carries "
            "its own proximal l1/l2 and AdaGrad pairs with explicit "
            "weight decay, not lazy L2 — configure l1/l2 here instead")
    per_example_loss = losses_lib.loss_fn(spec.loss)
    cd = spec.cdtype
    alpha = float(config.learning_rate)
    is_ftrl = config.optimizer == "ftrl"

    def rule(rows, slot, g):
        if is_ftrl:
            new_rows, z_new, n_new = ftrl_rows(
                rows, slot["z"], slot["n"], g, alpha, beta, l1, l2)
            return new_rows, {"z": z_new, "n": n_new}
        new_rows, n_new = adagrad_rows(rows, slot["n"], g, alpha)
        return new_rows, {"n": n_new}

    def sparse_apply(table, slot, flat_ids, flat_g):
        """One table's dedup-scatter adaptive update: segment-sum the
        per-lane grads, gather + update + set-write the unique rows
        (non-run-start lanes route to the drop sentinel)."""
        n_rows = table.shape[0]
        sid, summed, run_start, _ = _dedup(flat_ids, flat_g)
        g_u = jnp.where(run_start[..., None] if summed.ndim > 1
                        else run_start, summed, 0.0)
        rows = table[sid].astype(jnp.float32)
        slot_rows = {k: s[sid] for k, s in slot.items()}
        new_rows, new_slot_rows = rule(rows, slot_rows, g_u)
        oob = jnp.where(run_start, sid, n_rows)
        table = table.at[oob].set(new_rows.astype(table.dtype),
                                  mode="drop")
        slot = {k: slot[k].at[oob].set(new_slot_rows[k], mode="drop")
                for k in slot}
        return table, slot

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, slots, ids, vals, labels, weights):
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
        # The reference's analytic per-row rule (BASELINE.json:5).
        g_rows = (dscores[:, None, None] * vals_c[..., None]
                  * (s[:, None, :] - xv))
        flat_ids = ids.reshape(-1)
        v, slots_v = sparse_apply(
            v, slots["v"], flat_ids,
            g_rows.reshape(-1, g_rows.shape[-1]).astype(jnp.float32))
        slots = dict(slots, v=slots_v)
        if spec.use_linear:
            g_w = (dscores[:, None] * vals_c).reshape(-1)
            w, slots_w = sparse_apply(w, slots["w"], flat_ids,
                                      g_w.astype(jnp.float32))
            slots = dict(slots, w=slots_w)
        if spec.use_bias:
            # Dense slot, deliberately excluded from the adaptive set:
            # plain constant-lr SGD on the scalar bias.
            w0 = w0 - alpha * jnp.sum(dscores)
        return {"w0": w0, "w": w, "v": v}, slots, loss

    return step
