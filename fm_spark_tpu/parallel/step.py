"""Sharded train/eval steps: psum gradient reduction and row-sharded tables.

The reference's per-iteration communication (SURVEY.md §3.1) is:
broadcast(weights) → executors compute per-partition gradient sums →
``treeAggregate`` reduce to the driver → driver applies the update. Here the
whole cycle is one compiled program over a ``(data, feat)`` mesh:

- ``dp``: each data-shard computes the gradient of its local batch slice;
  one ``lax.psum`` over ``data`` is the treeAggregate. Parameters are
  replicated and updated identically everywhere — no broadcast exists.
- ``row``: the (w, V) tables are row-sharded over ``feat``. Each shard
  computes masked partial sums (linear_p, s_p, sumsq_p) for the global ids
  that land in its rows; ``psum`` over ``feat`` reconstructs the exact
  scores (both terms are linear reductions over features — SURVEY.md §2).
  The backward pass then writes only shard-local rows: the 10M×64 table
  never moves over the interconnect, only [B, k] activations do.

  SCALE CAVEAT: ``row`` still materializes a dense per-shard gradient
  table each step (the generic optax update below) — measured at ~94k
  samples/sec/chip on CTR shapes (PERF.md headline table), ~8× below the
  fused path. It exists for exact optimizer parity (adam/adagrad, global
  L2) and as the FM-family generic strategy; the AT-SCALE path for CTR
  training is the field-sharded fused sparse step
  (``parallel/field_step.py``, strategy ``field_sparse``), which shards
  fields over the mesh and optionally row-shards buckets (2-D
  ``feat×row`` mesh, CLI ``--row-shards``) with in-place sparse updates.

The optimizer update runs under jit *outside* shard_map: with params placed
by :func:`shard_params`, XLA's SPMD partitioner keeps every elementwise
update local to the shard that owns the rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from fm_spark_tpu.ops import fm as fm_ops
from fm_spark_tpu.ops import losses as losses_lib
from fm_spark_tpu.train import TrainConfig, _group_reg, make_optimizer
from fm_spark_tpu.utils import metrics as metrics_lib

BATCH_SPECS = (P("data", None), P("data", None), P("data"), P("data"))


def _params_struct(spec):
    return jax.eval_shape(spec.init, jax.random.key(0))


def param_specs(spec, strategy: str):
    """PartitionSpec pytree for a model's params under a strategy."""
    struct = _params_struct(spec)
    if strategy == "dp":
        return jax.tree_util.tree_map(lambda _: P(), struct)
    if strategy == "row":
        if not _is_plain_fm(spec):
            raise ValueError(
                "row-sharded strategy supports the FM family only; "
                "use strategy='dp' for FFM/DeepFM"
            )
        return {"w0": P(), "w": P("feat"), "v": P("feat", None)}
    raise ValueError(f"unknown strategy {strategy!r}")


def _is_plain_fm(spec):
    from fm_spark_tpu.models.fm import FMSpec

    return type(spec) is FMSpec


def _check_divisibility(spec, mesh, strategy):
    if strategy == "row" and spec.num_features % mesh.shape["feat"]:
        raise ValueError(
            f"num_features={spec.num_features} must be divisible by the "
            f"feat mesh axis ({mesh.shape['feat']}); pad the hash space up"
        )


def shard_params(params, mesh, spec, strategy: str):
    """Place a param pytree onto the mesh per the strategy's specs."""
    specs = param_specs(spec, strategy)
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs
    )


def shard_batch(batch, mesh):
    """Place ``(ids, vals, labels, weights)`` sharded over the data axis."""
    return tuple(
        jax.device_put(jnp.asarray(x), NamedSharding(mesh, s))
        for x, s in zip(batch, BATCH_SPECS)
    )


def _local_scores_fn(spec, strategy: str, mesh):
    """Build ``scores(params, ids, vals)`` as seen by one device's block."""
    if strategy == "dp":
        return lambda p, ids, vals: spec.scores(p, ids, vals)

    rows_per = spec.num_features // mesh.shape["feat"]

    def scores(p, ids, vals):
        row_start = lax.axis_index("feat") * rows_per
        w = p["w"] if spec.use_linear else jnp.zeros_like(p["w"])
        lin_p, s_p, sq_p = fm_ops.fm_partial_terms(
            w, p["v"], ids, vals, row_start, rows_per, spec.cdtype
        )
        lin = lax.psum(lin_p, "feat")
        s = lax.psum(s_p, "feat")
        sq = lax.psum(sq_p, "feat")
        w0 = p["w0"] if spec.use_bias else jnp.zeros((), jnp.float32)
        return fm_ops.fm_scores_from_partials(w0, lin, s, sq, spec.cdtype)

    return scores


def _make_grad_fn(spec, mesh, strategy: str):
    """shard_map'd ``(params, batch) → (grads, loss)`` with psum reduction.

    The ``row`` path never differentiates *through* a collective (the
    transpose of ``psum`` under ``check_vma=False`` re-sums replicated
    cotangents over ``feat``, inflating table gradients by the axis size).
    Instead: one explicit ``jax.vjp`` over the shard-local partial-sum map,
    with the score cotangents derived locally — mathematically exact because
    scores are an affine function of each shard's partials:

        scores = w0 + Σ_f lin_f + ½(‖Σ_f s_f‖² − Σ_f sq_f)
        ⇒ ∂L/∂lin_f = ∂L/∂scores;  ∂L/∂s_f = ∂L/∂scores · s;
          ∂L/∂sq_f = −½ ∂L/∂scores     (s = the full psum'd [B,k] sum)
    """
    per_example_loss = losses_lib.loss_fn(spec.loss)
    pspecs = param_specs(spec, strategy)

    def _loss_and_dscores(scores, labels, weights, wsum):
        def f(sc):
            per = per_example_loss(sc, labels) * weights
            return jnp.sum(per) / jnp.maximum(wsum, 1.0)

        return jax.value_and_grad(f)(scores)

    if strategy == "dp":

        def grads_and_loss(params, ids, vals, labels, weights):
            wsum = lax.psum(jnp.sum(weights), "data")

            def local_loss(p):
                scores = spec.scores(p, ids, vals)
                per = per_example_loss(scores, labels) * weights
                return jnp.sum(per) / jnp.maximum(wsum, 1.0)

            loss, grads = jax.value_and_grad(local_loss)(params)
            # The treeAggregate: one psum over the batch axis.
            grads = lax.psum(grads, "data")
            loss = lax.psum(loss, "data")
            return grads, loss

    else:
        rows_per = spec.num_features // mesh.shape["feat"]

        def grads_and_loss(params, ids, vals, labels, weights):
            row_start = lax.axis_index("feat") * rows_per
            w_in = params["w"] if spec.use_linear else jnp.zeros_like(params["w"])

            def partial_fn(w, v):
                return fm_ops.fm_partial_terms(
                    w, v, ids, vals, row_start, rows_per, spec.cdtype
                )

            (lin_p, s_p, sq_p), vjp = jax.vjp(partial_fn, w_in, params["v"])
            lin = lax.psum(lin_p, "feat")
            s = lax.psum(s_p, "feat")
            sq = lax.psum(sq_p, "feat")
            w0 = params["w0"] if spec.use_bias else jnp.zeros((), jnp.float32)
            scores = fm_ops.fm_scores_from_partials(w0, lin, s, sq, spec.cdtype)
            wsum = lax.psum(jnp.sum(weights), "data")
            loss, dscores = _loss_and_dscores(scores, labels, weights, wsum)
            g_w, g_v = vjp((dscores, dscores[:, None] * s, -0.5 * dscores))
            g_w0 = jnp.sum(dscores) if spec.use_bias else jnp.zeros((), jnp.float32)
            if not spec.use_linear:
                g_w = jnp.zeros_like(g_w)
            grads = {"w0": g_w0.astype(jnp.float32), "w": g_w, "v": g_v}
            grads = lax.psum(grads, "data")
            loss = lax.psum(loss, "data")
            return grads, loss

    return jax.shard_map(
        grads_and_loss,
        mesh=mesh,
        in_specs=(pspecs, *BATCH_SPECS),
        out_specs=(pspecs, P()),
        check_vma=False,
    )


def make_parallel_train_step(
    spec, config: TrainConfig, mesh, strategy: str = "dp", optimizer=None
):
    """Build the jitted multi-device train step.

    Returns ``step(params, opt_state, ids, vals, labels, weights) →
    (params, opt_state, {loss, grad_norm})``. Inputs must be placed with
    :func:`shard_params` / :func:`shard_batch`.
    """
    from fm_spark_tpu.sparse import OPTAX_OPTIMIZERS, Serves, refuse_unserved

    # Grad psums here feed the optimizer DIRECTLY (no later fp32
    # re-derivation), a different precision contract from the fused
    # steps' activation collectives: collective_dtype is not served.
    refuse_unserved(config, Serves(optimizers=OPTAX_OPTIMIZERS),
                    "the dense optax parallel step", spec.loss)
    _check_divisibility(spec, mesh, strategy)
    optimizer = optimizer or make_optimizer(config)
    add_reg = _group_reg(config)
    grad_fn = _make_grad_fn(spec, mesh, strategy)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, ids, vals, labels, weights):
        grads, loss = grad_fn(params, ids, vals, labels, weights)
        grads = add_reg(grads, params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, {
            "loss": loss,
            "grad_norm": optax.global_norm(grads),
        }

    return step


def make_parallel_eval_step(spec, mesh, strategy: str = "dp"):
    """Jitted sharded metrics accumulation; state is replicated."""
    _check_divisibility(spec, mesh, strategy)
    per_example_loss = losses_lib.loss_fn(spec.loss)
    local_scores = _local_scores_fn(spec, strategy, mesh)
    pspecs = param_specs(spec, strategy)
    mspecs = jax.tree_util.tree_map(
        lambda _: P(), metrics_lib.init_metrics()
    )

    def delta(params, ids, vals, labels, weights):
        scores = local_scores(params, ids, vals)
        per = per_example_loss(scores, labels)
        d = metrics_lib.update_metrics(
            metrics_lib.init_metrics(), scores, labels, per, weights
        )
        # Metric fields are plain sums → psum over the batch axis only
        # (every feat replica computed identical values).
        return lax.psum(d, "data")

    delta_fn = jax.shard_map(
        delta,
        mesh=mesh,
        in_specs=(pspecs, *BATCH_SPECS),
        out_specs=mspecs,
        check_vma=False,
    )

    @jax.jit
    def step(params, mstate, ids, vals, labels, weights):
        d = delta_fn(params, ids, vals, labels, weights)
        return jax.tree_util.tree_map(jnp.add, mstate, d)

    return step


# --------------------------------------------------------------------------
# AOT warm-start entries (see fm_spark_tpu/sparse.py's counterpart for
# the rationale): lower + compile the dense parallel step against
# abstract SHARDED shapes, so the executable exists — and, with
# utils/compile_cache enabled, persists — before any array is placed on
# the mesh.
# --------------------------------------------------------------------------


def _sharded_abstract(struct, mesh, specs):
    """ShapeDtypeStructs carrying the NamedShardings the real call will
    use — lowering without them would compile a differently-partitioned
    program and the warm cache would never be hit."""
    return jax.tree_util.tree_map(
        lambda s, sp: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, sp)
        ),
        struct, specs,
    )


def _abstract_opt_state(optimizer, params_abs, mesh, pspecs):
    """Abstract optimizer state with shardings matched to the params.

    optax slot buffers (adam/adagrad moments) mirror a param leaf's
    shape exactly, and the update runs under jit where SPMD keeps each
    slot co-located with its rows — so shape-matching against the param
    specs reproduces the placement ``optimizer.init(sharded_params)``
    produces. Scalars (counts) and unmatched leaves are replicated.
    """
    shape_to_spec = {}
    for leaf, sp in zip(
        jax.tree_util.tree_leaves(params_abs),
        jax.tree_util.tree_leaves(pspecs, is_leaf=lambda x: isinstance(x, P)),
    ):
        shape_to_spec.setdefault(leaf.shape, sp)
    struct = jax.eval_shape(optimizer.init, params_abs)
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype,
            sharding=NamedSharding(
                mesh, shape_to_spec.get(s.shape, P())
            ),
        ),
        struct,
    )


def lower_parallel_train_step(spec, config: TrainConfig, mesh,
                              strategy: str = "dp", *,
                              batch_size: int, nnz: int | None = None,
                              optimizer=None):
    """Lower the dp/row mesh step against abstract sharded shapes.

    ``nnz`` is the batch's per-example id count (defaults to
    ``spec.num_fields`` when the model has one). Returns a
    ``jax.stages.Lowered``; ``.compile()`` yields the executable."""
    nnz = nnz if nnz is not None else getattr(spec, "num_fields", None)
    if not nnz:
        raise ValueError(
            "nnz (ids per example) is required for a model without "
            "num_fields"
        )
    if batch_size % mesh.shape["data"]:
        raise ValueError(
            f"batch_size={batch_size} must divide by the data mesh "
            f"axis ({mesh.shape['data']})"
        )
    optimizer = optimizer or make_optimizer(config)
    step = make_parallel_train_step(spec, config, mesh, strategy,
                                    optimizer)
    pspecs = param_specs(spec, strategy)
    params_abs = _sharded_abstract(_params_struct(spec), mesh, pspecs)
    opt_abs = _abstract_opt_state(optimizer, params_abs, mesh, pspecs)
    B = batch_size
    sds = jax.ShapeDtypeStruct
    batch_struct = (
        sds((B, nnz), jnp.int32), sds((B, nnz), jnp.float32),
        sds((B,), jnp.float32), sds((B,), jnp.float32),
    )
    batch_abs = _sharded_abstract(batch_struct, mesh, BATCH_SPECS)
    return step.lower(params_abs, opt_abs, *batch_abs)


def precompile_parallel_train_step(spec, config: TrainConfig, mesh,
                                   strategy: str = "dp", *,
                                   batch_size: int,
                                   nnz: int | None = None,
                                   optimizer=None):
    """Eagerly compile the dp/row mesh step (the warm-start producer for
    the dense strategies); returns the ``jax.stages.Compiled``."""
    return lower_parallel_train_step(
        spec, config, mesh, strategy,
        batch_size=batch_size, nnz=nnz, optimizer=optimizer,
    ).compile()
