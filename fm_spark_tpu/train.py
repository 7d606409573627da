"""FMTrainer: the jit-compiled on-device training loop.

This replaces the reference's L4/L5 (SURVEY.md §1, §3.1):
``FMWithSGD.run`` → ``GradientDescent.runMiniBatchSGD`` with one Spark job
per SGD iteration (broadcast weights → sample → treeAggregate gradients →
driver update). Here the entire step — forward, backward, regularization,
optimizer update — is ONE compiled XLA program with parameters resident on
device; the host only feeds batches and reads metrics. The reference's
update rule is preserved as the default:

    weights ← weights − (stepSize/√iter) · (grad + reg · weights)

with the ``regParam`` triple applied per group (bias / linear / factors),
matching MLlib's ``Updater`` semantics (SURVEY.md §0.2, §3.1).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Iterable

import jax
import jax.numpy as jnp
import optax

from fm_spark_tpu import obs
from fm_spark_tpu.ops import losses as losses_lib
from fm_spark_tpu.resilience import faults, watchdog
from fm_spark_tpu.resilience.divergence import DivergenceDetected
from fm_spark_tpu.utils import metrics as metrics_lib
from fm_spark_tpu.utils.logging import MetricsLogger


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Training hyperparameters (reference ``train()`` args + rebuild knobs)."""

    num_steps: int = 100                   # numIterations
    batch_size: int = 1024
    learning_rate: float = 0.1             # stepSize
    lr_schedule: str = "inv_sqrt"          # stepSize/√iter | 'constant'
    optimizer: str = "sgd"                 # 'sgd' | 'adam' | 'adagrad' |
                                           # 'ftrl' (per-coordinate
                                           # FTRL-Proximal, optim/)
    # AdaGrad's G0 on the tables of a fused field body (sparse
    # .make_field_ffm_adagrad_body): what every accumulator starts at.
    # Juan et al.'s Algorithm 1 starts at 1 against per-example
    # gradients; against this program's batch-MEAN gradients that is
    # 1/B² (configs: avazu_ffm_r16_adagrad). The flat and dense AdaGrad
    # forms keep their own starts.
    adagrad_init_accumulator: float = 0.0
    reg_bias: float = 0.0                  # regParam triple (r0, r1, r2)
    reg_linear: float = 0.0
    reg_factors: float = 0.0
    seed: int = 0
    log_every: int = 100
    eval_every: int = 0                    # 0 = only at the end
    metrics_path: str | None = None
    # Sparse-row write strategy for the fused FieldFM steps (ops/scatter.py):
    # 'scatter_add' | 'dedup' | 'dedup_sr'. dedup_sr is the bf16-storage
    # quality fix (stochastic rounding needs deduped set-semantics).
    sparse_update: str = "scatter_add"
    # Route the fused steps' row gather/update through the Pallas
    # pipelined-DMA kernels (ops/pallas_fm.py) instead of XLA
    # gather/scatter. The update side dedups in-batch first (the kernel's
    # read-modify-write needs unique ids); dedup_sr keeps its XLA
    # set-semantics write-back. Off-TPU backends run the kernels in
    # interpret mode (correctness only — the A/B belongs on a real chip,
    # PERF.md "Pallas" lever).
    use_pallas: bool = False
    # Host-assisted dedup (PERF.md round-3 lever): the prefetch thread
    # precomputes each batch's per-field sort/segment maps
    # (ops/scatter.dedup_aux) and ships them with the batch, so the
    # device never sorts and the scatter writes each unique id once.
    # Requires a dedup sparse_update mode; the fused FieldFM step then
    # takes a trailing ``aux`` operand.
    host_dedup: bool = False
    # COMPACT host-dedup (round-2 on-chip finding: XLA charges scatter
    # per LANE, dropped or not, so masking duplicates can't win — only
    # fewer lanes can). When > 0, the host aux compacts each field's
    # unique ids into this static capacity (ops/scatter.compact_aux) and
    # the device touches the big tables with ``compact_cap`` lanes
    # instead of B: unique rows gathered once, per-lane rows expanded
    # from the [cap, w] buffer, segment sums via one cumsum (no B-lane
    # scatter), one unique+sorted write per id. Must bound the per-field
    # per-batch unique-id count (the aux builder raises otherwise).
    # Requires host_dedup=True (or compact_device) and a dedup
    # sparse_update mode.
    compact_cap: int = 0
    # Build the compact aux ON DEVICE inside the step (one stable
    # argsort + cap-lane scatters per field — ops/scatter.
    # device_compact_aux) instead of shipping a host-built aux with the
    # batch. This is the scale-out form of the compact lever: it
    # composes with 2-D (feat, row) meshes and multi-process feeds
    # (each chip compacts only the F/n columns it owns after the
    # all_to_all), where the host aux structurally cannot. Single-chip
    # it trades the 47MB/batch aux transfer + host sort for F on-device
    # sorts — measure per attachment (bench.py sweep). Exclusive with
    # host_dedup; requires compact_cap > 0 and a dedup sparse_update.
    compact_device: bool = False
    # What happens when a field's per-batch unique-id count exceeds
    # compact_cap:
    #  'error' — host aux: raise before the step (the r2 behavior);
    #            device aux: poison the loss to −inf (unreachable
    #            naturally — losses are non-negative), which the training
    #            loop's periodic loss fetch turns into a hard error.
    #  'drop'  — device aux only: ids past the cap-th unique (the
    #            largest ids) behave as absent features for that batch —
    #            bounded, documented degradation instead of a crash.
    #  'split' — host aux only: the pipeline splits the offending batch
    #            into halves (zero-weight padded) until every field
    #            fits — exact semantics, more (smaller) steps.
    compact_overflow: str = "error"
    # Build each field's fused row update g_full as ONE elementwise
    # expression ``ds·x·(s1 − mask·xv_full) + rv·rows·touched`` (with
    # ``s1 = [s, 1]`` built once) instead of per-field
    # ``concat([g_v, g_l])`` — eliminates F × [B, k+1] concat copy
    # passes if XLA was not fusing them into the update's reorder
    # gather (PERF.md round-4 lever). Same arithmetic; results pinned
    # to a ULP-tight bound in tests/test_gfull.py (XLA contraction may
    # differ). FieldFM fused-linear bodies only. Off by default until
    # the on-chip A/B decides (bench.py --gfull-fused).
    gfull_fused: bool = False
    # Wire format for the field-sharded steps' ACTIVATION collectives
    # ('float32' | 'bfloat16'): the (s, sq, lin) score psum group (the
    # dominant ~60MB/chip/step ICI term at headline shapes —
    # parallel/projection.py), DeepFM's h psum/all_gather, and FFM's sel
    # all_to_all. 'bfloat16' halves those ICI bytes; reductions
    # accumulate in bf16 on the wire and results are cast back to the
    # compute dtype on arrival. Batch re-shard collectives (ids/vals/
    # labels/weights) and table writes are NOT affected — this is a
    # wire-precision knob, not a storage one. Quality envelope measured
    # by bench_quality.py (budget row); sharded-step factories only
    # (single-chip programs have no collectives — rejected there).
    collective_dtype: str = "float32"
    # Shard the [B, k] score + dscores math over EXAMPLES on the
    # field-sharded FM step: each chip reduces scores for its B/n
    # example block and one tiny [B] all_gather replicates dscores for
    # the backward. Per-example ops are elementwise, so dscores are
    # EXACTLY the replicated computation's values (equivalence-tested);
    # only the scalar loss reassociates. This removes the projection
    # model's only non-shardable B-proportional term — the binding
    # constraint on weak scaling (parallel/projection.py). Requires the
    # global batch to divide by the mesh size; FM sharded step only.
    score_sharded: bool = False
    # Example-shard the DEEP HEAD on the field-sharded DeepFM step (the
    # h-analog of score_sharded — VERDICT r4 #4): instead of
    # all_gather-ing ``h`` ([B, F_pad·k] — the step's dominant ICI term,
    # ~623MB/chip/step bf16 at headline shapes) and running the MLP
    # replicated on every chip, ONE all_to_all re-shards h by EXAMPLES
    # ([B/n, F_pad·k] per chip, ~n× fewer wire bytes), each chip runs
    # the MLP forward/backward on its B/n slice (deep FLOPs divide by n
    # instead of being replicated), a [B]-scalar all_gather replicates
    # the deep scores, the deep pullback returns through the reverse
    # all_to_all, and the MLP grads complete with one small psum over
    # ``feat``. Numerics: per-example deep scores are the replicated
    # computation's values up to matmul row-blocking; the MLP grad
    # reassociates across chips (psum) — equivalence-tested to tight
    # tolerance. Requires the global batch to divide by the feat mesh
    # extent; field-sharded DeepFM step only (rejected elsewhere).
    deep_sharded: bool = False
    # Compute the compact update's per-segment sums with the Pallas
    # sorted-run kernel (ops/pallas_segsum.py) instead of the blocked
    # two-level prefix: one streaming read of the sorted deltas + a
    # VMEM-resident [cap, w] accumulator — no [B, w] prefix
    # materialization (the round-4 "next levers" candidate, VERDICT r4
    # #2a; upside ≈ the remaining half of the blocked-prefix cost).
    # Same values up to fp32 reassociation; interpret mode off-TPU;
    # off by default until the on-chip A/B (bench.py sweep) prices it.
    # Requires compact_cap > 0 (it has nothing to compute otherwise).
    segtotal_pallas: bool = False
    # FFM only: compute the field-aware interaction and its backward in
    # per-owner-field blocks instead of materializing the [B, F, F, k]
    # ``sel``/``dsel`` tensors (the config-4 step's dominant HBM
    # traffic — PERF.md: bf16 compute buffers alone, which halve
    # exactly these, measured +23%). Same math, so values agree with
    # the default body up to fp reassociation of the pair sums; the
    # FORWARD's largest live tensor drops from [B, F, F, k] to
    # [B, F, k]. The backward's per-field gradient set (F × [B, F·k],
    # the same total bytes as the default body's dv) remains live until
    # the table updates — only the sel/dsel materialization is
    # eliminated. Off by default until the on-chip A/B (bench.py
    # --model ffm sweep) prices it.
    sel_blocked: bool = False
    # Fused Pallas embedding path (ops/pallas_fused.py; ROADMAP item 4):
    #  'off'     — the XLA reference path (default).
    #  'auto'    — use the fused kernel family that serves this
    #              (spec, config, backend) and fall back to XLA when
    #              none does — queryably (sparse.fused_embed_plan
    #              returns the reason; bench/cli surface it), the
    #              attachment-without-Pallas degrade mode.
    #  'require' — hard-fail (ops.PallasUnavailable) when no family
    #              serves, for tests/benches that must price the kernel.
    # Families: the FieldFM COMPACT backward (g_full built on-chip from
    # sorted scalar streams + the VMEM-resident urows block, fused with
    # the segment totals — the per-field [B, w] gradient set never
    # touches HBM; subsumes gfull_fused + segtotal_pallas for that
    # stage) and the sel-blocked FieldFFM interaction forward/backward
    # (tile-resident sel/dsel). fp32 results are bit-exact against the
    # reference bodies (tests/test_pallas_fused.py); priced per kernel
    # by bench_kernels.py and through the bench.py sweep legs.
    fused_embed: str = "off"
    # Tiered embedding store (fm_spark_tpu/embed; ROADMAP item 2):
    #  'off'     — tables fully HBM-resident (default).
    #  'auto'    — tier when the tiered flat-FM trainer serves this
    #              (spec, config, strategy) — embed.tier_plan returns
    #              the verdict and the reason — else fall back to the
    #              in-HBM path, SAYING so (cli surfaces the reason).
    #  'require' — hard-fail when the tiered trainer cannot serve
    #              (fused field families, sharded strategies, non-sparse
    #              optimizers) — same discipline as fused_embed.
    # The hot tier holds ``hot_rows`` HBM rows managed as buckets of
    # ``embed_bucket_rows`` contiguous rows (the residency/eviction/
    # prefetch unit); all planes — v, w, and the FTRL/AdaGrad z/n slot
    # tables — share one residency map. Misses that block the step are
    # counted and timed (embed/stall_ms), never hidden.
    embed_tier: str = "off"
    hot_rows: int = 0
    embed_bucket_rows: int = 512


def _group_reg(config: TrainConfig):
    """Per-group L2 added to the gradient, like MLlib's squared-L2 Updater.

    Groups: w0 → reg_bias, w → reg_linear, v/mlp → reg_factors. The fused
    ``vw`` tables of FieldFMSpec get a per-COLUMN vector (factor columns →
    reg_factors, the last linear column → reg_linear). Unknown groups are
    an error — silently unregularized parameters are worse than a crash.

    FTRL is the exception (ISSUE 13): its L2 is PROXIMAL, carried by
    the transform's own closed form (``make_optimizer`` routes the
    triple into ``optim.ftrl(l2_by_group=...)``) — folding ``λw`` into
    the gradients here would corrupt the per-coordinate z/n schedule
    statistics, so this returns the identity for ``optimizer='ftrl'``.
    """
    import numpy as np

    if config.optimizer == "ftrl":
        return lambda grads, params: grads

    known = {
        "w0": config.reg_bias,
        "w": config.reg_linear,
        "v": config.reg_factors,
        "mlp": config.reg_factors,
    }

    def add_reg(grads, params):
        def one(path, g, p):
            top = path[0]
            key = str(getattr(top, "key", getattr(top, "idx", top)))
            if key == "vw":
                if config.reg_factors == 0.0 and config.reg_linear == 0.0:
                    return g
                r = np.full((p.shape[-1],), config.reg_factors, np.float32)
                r[-1] = config.reg_linear
                return g + jnp.asarray(r) * p.astype(g.dtype)
            if key not in known:
                raise ValueError(f"no regularization group for param {key!r}")
            r = known[key]
            return g if r == 0.0 else g + r * p.astype(g.dtype)

        return jax.tree_util.tree_map_with_path(one, grads, params)

    return add_reg


def make_optimizer(config: TrainConfig) -> optax.GradientTransformation:
    if config.optimizer == "ftrl":
        # Per-coordinate FTRL-Proximal (optim/, ISSUE 13): its
        # (beta + sqrt(n))/alpha term IS the schedule, per coordinate,
        # so the global lr_schedule deliberately does not apply. The
        # reg_* triple routes into FTRL's PROXIMAL l2 per group —
        # never into the gradients (_group_reg is identity for ftrl):
        # (g + λw)² folded into n would corrupt the schedule itself.
        from fm_spark_tpu import optim

        return optim.ftrl(
            alpha=config.learning_rate,
            l2_by_group={"w0": config.reg_bias,
                         "w": config.reg_linear,
                         "v": config.reg_factors,
                         "mlp": config.reg_factors})
    if config.lr_schedule == "inv_sqrt":
        # iteration is 1-based in the reference: lr_i = stepSize / sqrt(i).
        schedule = lambda count: config.learning_rate / jnp.sqrt(count + 1.0)
    elif config.lr_schedule == "constant":
        schedule = config.learning_rate
    else:
        raise ValueError(f"unknown lr_schedule {config.lr_schedule!r}")
    if config.optimizer == "sgd":
        return optax.sgd(schedule)
    if config.optimizer == "adam":
        return optax.adam(schedule)
    if config.optimizer == "adagrad":
        return optax.adagrad(schedule)
    raise ValueError(f"unknown optimizer {config.optimizer!r}")


def make_train_step(spec, config: TrainConfig, optimizer=None):
    """Build the jit-compiled single-device train step.

    Returns ``step(params, opt_state, ids, vals, labels, weights) →
    (params, opt_state, metrics_dict)`` with donated params/opt_state.
    """
    from fm_spark_tpu.sparse import OPTAX_OPTIMIZERS, Serves, refuse_unserved

    refuse_unserved(config, Serves(optimizers=OPTAX_OPTIMIZERS),
                    "the dense single-device train step", spec.loss)
    optimizer = optimizer or make_optimizer(config)
    per_example_loss = losses_lib.loss_fn(spec.loss)
    add_reg = _group_reg(config)

    def step(params, opt_state, ids, vals, labels, weights):
        def loss_f(p):
            scores = spec.scores(p, ids, vals)
            per = per_example_loss(scores, labels) * weights
            return jnp.sum(per) / jnp.maximum(jnp.sum(weights), 1.0)

        loss, grads = jax.value_and_grad(loss_f)(params)
        grads = add_reg(grads, params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, {
            "loss": loss,
            "grad_norm": optax.global_norm(grads),
        }

    return jax.jit(step, donate_argnums=(0, 1))


def make_eval_step(spec):
    """Build the jit-compiled metrics-accumulation step.

    RMSE is computed from the model's actual PREDICTIONS (regression clip
    applied, matching ``FMModel.predict``), while AUC/logloss use the raw
    scores.
    """
    from fm_spark_tpu.models import base as model_base

    per_example_loss = losses_lib.loss_fn(spec.loss)

    def step(params, mstate, ids, vals, labels, weights):
        scores = spec.scores(params, ids, vals)
        per = per_example_loss(scores, labels)
        preds = model_base.predict_from_scores(spec, scores)
        return metrics_lib.update_metrics(
            mstate, scores, labels, per, weights, predictions=preds
        )

    return jax.jit(step)


def evaluate_params(spec, params, batches, max_batches: int | None = None,
                    step=None) -> dict:
    """Stream ``(ids, vals, labels, weights)`` batches → finalized metrics.

    Shared by :meth:`FMTrainer.evaluate` and :func:`fm_spark_tpu.compat
    .evaluate`. Pass a precompiled ``step`` (from :func:`make_eval_step`)
    to avoid a re-trace per call — periodic in-training eval does.
    """
    if step is None:
        step = make_eval_step(spec)
    mstate = metrics_lib.init_metrics()
    for i, (ids, vals, labels, weights) in enumerate(batches):
        if max_batches is not None and i >= max_batches:
            break
        mstate = step(
            params, mstate, jnp.asarray(ids), jnp.asarray(vals),
            jnp.asarray(labels), jnp.asarray(weights),
        )
    return {k: float(v) for k, v in metrics_lib.finalize_metrics(mstate).items()}


class FMTrainer:
    """End-to-end trainer: the rebuild's ``FMWithSGD`` equivalent.

    Usage::

        trainer = FMTrainer(spec, TrainConfig(num_steps=1000, ...))
        params = trainer.fit(train_batches)
        metrics = trainer.evaluate(eval_batches)
    """

    def __init__(self, spec, config: TrainConfig, n_chips: int = 1):
        # Warm start for any library user of the trainer: the
        # persistent XLA compilation cache is on without a flag
        # (utils/compile_cache says where it lives).
        from fm_spark_tpu.utils import compile_cache

        compile_cache.enable()
        self.spec = spec
        self.config = config
        self.optimizer = make_optimizer(config)
        self._train_step = make_train_step(spec, config, self.optimizer)
        self._eval_step = make_eval_step(spec)
        self.params = spec.init(jax.random.key(config.seed))
        self.opt_state = self.optimizer.init(self.params)
        self.step_count = 0
        self.logger = MetricsLogger(path=config.metrics_path, n_chips=n_chips)
        self.loss_history: list[float] = []
        self.last_eval: dict | None = None  # most recent in-fit eval metrics

    def fit(self, batches: Iterable, num_steps: int | None = None,
            checkpointer=None, preemption_guard=None, eval_batches=None,
            prefetch: int = 0, supervisor=None, elastic=None,
            divergence_guard=None):
        """Run the training loop; ``batches`` yields (ids, vals, labels, w).

        With a :class:`fm_spark_tpu.checkpoint.Checkpointer`, training
        state (params, optimizer state, step, pipeline cursor) is saved on
        the checkpointer's cadence, the run resumes from the latest saved
        step automatically, and a ``PreemptionGuard`` (if given) turns
        SIGTERM into an orderly flush-and-return (SURVEY.md §5). The
        pipeline-cursor slot carries whatever ``batches.state()``
        returns — for the streaming ingest source
        (:class:`fm_spark_tpu.data.StreamBatches`) that is the
        ``(epoch, shard, byte_offset, records)`` cursor plus the
        quarantine counters, so a kill-and-resume run consumes every
        record exactly once and its dead-letter accounting continues
        instead of resetting; a run whose guard quarantined anything
        logs a final ``bad_records`` metrics line.

        ``eval_batches`` (a zero-arg callable returning a finite batch
        iterable, e.g. ``lambda: iterate_once(*te, bs)``) enables periodic
        held-out evaluation every ``config.eval_every`` steps; metrics are
        logged with an ``eval_`` prefix.

        ``prefetch > 0`` wraps ``batches`` in a background
        :class:`~fm_spark_tpu.data.Prefetcher` AFTER checkpoint resume
        (the producer reads ahead immediately, so it must see the
        restored cursor), overlapping host batch assembly with device
        compute.

        ``supervisor`` (a :class:`fm_spark_tpu.resilience.Supervisor`,
        requires ``checkpointer``) turns a mid-run DEVICE LOSS from a
        crash into a degradation: the loss is journaled, the supervisor
        probes the attachment and backs off (circuit-breaking after its
        threshold of consecutive losses), device state is rebuilt fresh,
        and the run resumes from the latest committed checkpoint with
        the pipeline cursor restored — so the resumed loss curve is the
        uninterrupted one (the same continuity contract as
        kill-and-resume, tests/test_checkpoint.py). Non-device errors
        propagate unchanged.

        ``elastic`` (a :class:`fm_spark_tpu.resilience.ElasticController`,
        requires ``supervisor``) upgrades the supervisor's terminal
        verdict: when the breaker opens on a PERMANENT fault (N
        identical consecutive device losses — a dead attachment, not a
        flap), the controller sheds capacity instead of dying — the
        shrink is journaled, per-chip metrics re-normalize to the
        surviving chip count, the breaker re-arms, and the run resumes
        from the last good checkpoint. Mixed-mode circuit opens (a
        genuinely thrashing attachment) still raise.

        ``divergence_guard`` (a :class:`fm_spark_tpu.resilience
        .divergence.DivergenceGuard`, requires ``checkpointer``) watches
        every step's loss — NaN/Inf, or a configurable spike over the
        trailing median — and on detection rolls back to the last good
        checkpoint and resumes with a reduced step budget (stop just
        before the diverging step), so a numeric blowup costs one
        checkpoint window instead of the run. Costs one device→host
        loss fetch per step while enabled.
        """
        total = num_steps if num_steps is not None else self.config.num_steps
        log_every = max(self.config.log_every, 1)
        if supervisor is not None and checkpointer is None:
            raise ValueError(
                "supervised training needs a checkpointer: device-loss "
                "recovery without committed state to resume from would "
                "silently restart the run from scratch"
            )
        if elastic is not None and supervisor is None:
            raise ValueError(
                "elastic degraded mode needs a supervisor: the shrink "
                "trigger is the supervisor's permanent-fault verdict"
            )
        if divergence_guard is not None and checkpointer is None:
            raise ValueError(
                "divergence-guard training needs a checkpointer: "
                "rollback without committed good state to restore would "
                "silently restart the run from scratch"
            )
        if checkpointer is not None:
            if not (hasattr(batches, "state") and hasattr(batches, "restore")):
                raise ValueError(
                    "checkpointed training needs a resumable batch source "
                    "with state()/restore() (e.g. data.Batches); a plain "
                    "iterator would silently replay data after resume"
                )

        def save(force=False):
            if checkpointer is None:
                return
            if not force and not checkpointer.due(self.step_count):
                return  # skip snapshot construction off-cadence
            # Snapshot mutable fields: async saves serialize in a background
            # thread while the loop keeps appending to loss_history.
            args = (self.step_count, self.params, self.opt_state,
                    batches.state(), {"loss_history": list(self.loss_history)})
            if force:
                checkpointer.save(*args, force=True)
                checkpointer.wait()
            else:
                checkpointer.save(*args)
            if supervisor is not None:
                # A committed post-recovery checkpoint IS real progress:
                # close the breaker so it counts CONSECUTIVE losses, not
                # lifetime ones — a long run whose attachment flaps once
                # a day must never accumulate toward CircuitOpen.
                supervisor.note_success("train")

        from fm_spark_tpu.data import wrap_prefetch

        source = batches
        # A recovery retry with NO committed checkpoint yet must rewind
        # the batch source to its pre-run cursor — resume_or_init only
        # restores a cursor a checkpoint recorded, and replaying from
        # mid-stream would silently skip the already-consumed window.
        initial_cursor = (source.state()
                          if checkpointer is not None
                          and hasattr(source, "state") else None)
        need_rebuild = False
        while True:
            try:
                if need_rebuild:
                    # Rebuild EVERYTHING that lived on the dead device —
                    # params/opt state (also donated, so host handles
                    # are stale either way) and the jitted steps. This
                    # runs INSIDE the supervised try: a rebuild against
                    # a still-dead attachment raises another device-loss
                    # error, which cycles back through recover() and is
                    # bounded by the circuit breaker instead of escaping
                    # uncaught.
                    checkpointer.reopen()
                    if (initial_cursor is not None
                            and checkpointer.latest_step() is None):
                        source.restore(initial_cursor)
                    self.params = self.spec.init(
                        jax.random.key(self.config.seed))
                    self.opt_state = self.optimizer.init(self.params)
                    self.step_count = 0
                    self.loss_history = []
                    self._train_step = make_train_step(
                        self.spec, self.config, self.optimizer)
                    self._eval_step = make_eval_step(self.spec)
                    need_rebuild = False
                start = 0
                if checkpointer is not None:
                    from fm_spark_tpu import checkpoint as ckpt_lib

                    # With a checkpointer, num_steps is a GLOBAL step
                    # target: a resumed run continues toward it (and a
                    # finished run is a no-op). Without one, fit() runs
                    # num_steps more steps.
                    start = ckpt_lib.resume_or_init(self, checkpointer,
                                                    batches=source)
                batches, close_prefetch = wrap_prefetch(source, prefetch)
                try:
                    result = self._fit_loop(batches, start, total,
                                            log_every, checkpointer,
                                            preemption_guard,
                                            eval_batches, save,
                                            divergence_guard)
                    if supervisor is not None:
                        supervisor.note_success("train")
                    ingest_guard = getattr(source, "guard", None)
                    if ingest_guard is not None and ingest_guard.n_bad:
                        # Quarantined-record accounting is part of the
                        # run's record (the ISSUE 5 dirty-data
                        # contract): one summary metrics line; the
                        # per-record detail lives in the dead-letter
                        # journal.
                        self.logger.log(self.step_count,
                                        bad_records=ingest_guard.n_bad,
                                        good_records=ingest_guard.n_ok)
                    return result
                finally:
                    close_prefetch()
            except DivergenceDetected as e:
                # Rollback: resume from the last good checkpoint with a
                # REDUCED budget (stop before the diverging step —
                # deterministic replay would re-diverge identically).
                # note_rollback re-raises when its budget is spent.
                restored = (checkpointer.last_good_step()
                            if hasattr(checkpointer, "last_good_step")
                            else checkpointer.latest_step()) or 0
                total = min(total, divergence_guard.note_rollback(
                    e, restored))
                # Full rebuild: the poisoned params were donated into
                # the step and must never survive the rollback; the
                # resume path then restores the verified state.
                need_rebuild = True
            except Exception as e:  # noqa: BLE001 — classified below
                from fm_spark_tpu.resilience import is_device_loss

                if supervisor is None or not is_device_loss(e):
                    raise
                # Device loss: journal + probe + bounded backoff (raises
                # CircuitOpen after the supervisor's threshold of
                # consecutive losses), then loop back to rebuild device
                # state and resume from the latest committed checkpoint.
                import time as _time

                from fm_spark_tpu.resilience.supervisor import CircuitOpen

                t_recover = _time.perf_counter()
                try:
                    supervisor.recover("train", e)
                except CircuitOpen:
                    # Terminal verdict — unless the failure run is
                    # PERMANENT (identical losses: dead capacity, not a
                    # thrashing attachment) and the elastic controller
                    # can still shed chips: shrink, re-normalize the
                    # per-chip metrics, re-arm the breaker, resume from
                    # the last good checkpoint on the smaller gang.
                    if (elastic is None or not supervisor.permanent()
                            or not elastic.can_shrink()):
                        raise
                    prev_chips = elastic.n_chips
                    elastic.shrink("train")
                    # Re-normalize per-chip metrics ONLY if the logger
                    # was tracking the controller's fleet view — a
                    # single-chip trainer (n_chips=1) paired with a
                    # fleet-wide controller must not start dividing its
                    # one-device rate by the surviving fleet size.
                    if self.logger._n_chips == prev_chips:
                        self.logger.set_n_chips(elastic.n_chips)
                    supervisor.reset("train")
                need_rebuild = True
                # Recovery wall-clock (probe + backoff) must not deflate
                # the next throughput window — same contract as the
                # periodic-eval pause. (The rebuild itself is timed into
                # the next window's pause only via this call on a repeat
                # failure; its cost is one init + re-jit.)
                self.logger.add_pause(_time.perf_counter() - t_recover)

    def _fit_loop(self, batches, start, total, log_every, checkpointer,
                  preemption_guard, eval_batches, save,
                  divergence_guard=None):
        it = iter(batches)
        steps_since_log = 0
        # Telemetry (ISSUE 7): latched ONCE so an un-observed process
        # pays a single attribute check per step (the ≤1% disabled-path
        # contract, tests/test_obs_overhead.py). The first step's wall
        # time is recorded separately with the compile-cache hit/miss
        # delta (the PR-1 hooks) — the compile-vs-execute split — and
        # excluded from the steady-state step-time histogram.
        obs_on = obs.enabled()
        hist_step = obs.histogram("step_time_ms") if obs_on else None
        first_step_pending = obs_on
        cc0 = None
        if obs_on:
            from fm_spark_tpu.utils import compile_cache

            cc0 = compile_cache.cache_stats()
        # Window spans are emitted RETROACTIVELY at each log boundary
        # (one record per window, never an open span held across
        # iterations — an exception mid-window must not leak a span
        # onto the thread's parent stack). Step time is observed as
        # the WINDOW mean, measured after the boundary's loss fetch —
        # the d2h fence — because the jitted step returns at dispatch
        # time: per-step host timing would record enqueue latency, not
        # device step time, on an async backend.
        win_ts, win_t0, win_steps = time.time(), time.perf_counter(), 0
        # Watchdog exemption for the FIRST loop step of every
        # _fit_loop entry (fresh start AND each post-recovery
        # re-entry): that step carries the jit compile, whose wall
        # time is budgeted nowhere near a steady step's — arming the
        # step_window deadline over it would misclassify a healthy
        # cold start as a hang. (The obs plane fences the same step
        # out of its histograms for the same reason.)
        import contextlib

        first_loop_call = True
        for step_i in range(start, total):
            if preemption_guard is not None and preemption_guard.should_stop:
                save(force=True)
                return self.params
            # One step's host-observable window — the fault point, the
            # batch fetch (a stalled producer hangs HERE), and the step
            # dispatch — runs under the ``step_window`` deadline
            # watchdog (ISSUE 10); a single is-None/False check each
            # when no fault plan / watchdog is active.
            wd_ctx = (contextlib.nullcontext() if first_loop_call
                      else watchdog.phase("step_window"))
            first_loop_call = False
            with wd_ctx:
                faults.inject("train_step")
                try:
                    ids, vals, labels, weights = next(it)
                except StopIteration:
                    raise ValueError(
                        f"batch iterable exhausted after {step_i} of "
                        f"{total} steps; pass an epoch-cycling iterator "
                        "(data.Batches) or lower num_steps"
                    ) from None
                t_step0 = (time.perf_counter() if first_step_pending
                           else 0.0)
                self.params, self.opt_state, m = self._train_step(
                    self.params, self.opt_state,
                    jnp.asarray(ids), jnp.asarray(vals),
                    jnp.asarray(labels), jnp.asarray(weights),
                )
            if obs_on:
                if first_step_pending:
                    first_step_pending = False
                    # Fence THIS step only: the compile-vs-execute
                    # split wants the real first-step wall time, and
                    # one d2h on the compile step is free next to the
                    # compile itself.
                    jax.block_until_ready(m)  # fmlint: disable=jax-host-sync -- deliberate first-step-only fence: the compile-vs-execute split needs real first-step wall time
                    dt_ms = (time.perf_counter() - t_step0) * 1e3
                    from fm_spark_tpu.utils import compile_cache

                    cc1 = compile_cache.cache_stats()
                    obs.histogram("train.first_step_ms").observe(dt_ms)
                    obs.event("compile_split",
                              first_step_ms=round(dt_ms, 3),
                              cache_hits=cc1["hits"] - cc0["hits"],
                              fresh_compiles=(cc1["misses"]
                                              - cc0["misses"]))
                    # Steady-state windows must not amortize the
                    # compile step: restart the window after it.
                    win_ts, win_t0, win_steps = (time.time(),
                                                 time.perf_counter(), 0)
                else:
                    win_steps += 1
            self.step_count += 1
            steps_since_log += 1
            if divergence_guard is not None:
                # One device→host sync per step — the opt-in price of
                # catching the blowup BEFORE its state can be logged,
                # evaluated, or reach a checkpoint snapshot below.
                divergence_guard.check(self.step_count, float(m["loss"]))  # fmlint: disable=jax-host-sync -- opt-in per-step sync: the guard must see the loss before it can checkpoint/log
            if self.step_count % log_every == 0 or step_i == total - 1:
                loss = float(m["loss"])  # fmlint: disable=jax-host-sync -- the PR-7 window fence: the log-boundary loss fetch IS the measurement boundary
                self.loss_history.append(loss)
                self.logger.log(
                    self.step_count,
                    samples=steps_since_log * len(labels),
                    loss=loss,
                    grad_norm=float(m["grad_norm"]),  # fmlint: disable=jax-host-sync -- log-boundary fetch, already behind the window fence above
                )
                if obs_on:
                    # float(m["loss"]) above was the d2h fence: every
                    # dispatched step in the window has executed, so
                    # the window mean is honest device step time.
                    win_dur = time.perf_counter() - win_t0
                    if win_steps:
                        win_mean_ms = win_dur * 1e3 / win_steps
                        hist_step.observe(win_mean_ms)
                        # Live introspection (ISSUE 14): a window mean
                        # past the trailing p99 fires a rate-limited
                        # deep capture while the slow program is still
                        # resident; one None check when unarmed.
                        obs.introspect.observe_step_time(win_mean_ms)
                    # steps=win_steps, not steps_since_log: the first
                    # window's timer restarts after the compile step,
                    # so the span must count only the steps its
                    # duration actually covers.
                    obs.emit_span("train/steps", win_ts, win_dur,
                                  steps=win_steps,
                                  step=self.step_count, loss=loss)
                    # Device-memory watermark once per log window
                    # (ISSUE 9): the HBM peak / live-buffer gauges ride
                    # the metrics snapshots so a run's memory profile
                    # is recorded next to its step rate. Per-window,
                    # not per-step — live_arrays() walks every buffer.
                    obs.device_memory_snapshot()
                    win_ts, win_t0, win_steps = (time.time(),
                                                 time.perf_counter(), 0)
                steps_since_log = 0
            if eval_batches is not None and (
                (self.config.eval_every > 0
                 and self.step_count % self.config.eval_every == 0)
                or step_i == total - 1  # always evaluate the final model
            ):
                t_eval = time.perf_counter()
                with obs.span("train/eval", step=self.step_count) as sp:
                    em = self.evaluate(eval_batches())
                    sp.set(**{f"eval_{k}": round(float(v), 6)
                              for k, v in em.items()})
                self.last_eval = em
                self.logger.log(
                    self.step_count,
                    **{f"eval_{k}": v for k, v in em.items()},
                )
                # Eval wall-clock must not deflate the next training
                # throughput window — nor inflate the step-time
                # histogram's current window.
                pause = time.perf_counter() - t_eval
                self.logger.add_pause(pause)
                if obs_on:
                    win_t0 += pause
            save()
        save(force=True)
        return self.params

    def evaluate(self, batches: Iterable, max_batches: int | None = None) -> dict:
        """Stream eval batches through the on-device accumulators, using
        the eval step compiled once at construction (no re-trace per
        periodic in-training eval)."""
        return evaluate_params(
            self.spec, self.params, batches, max_batches,
            step=self._eval_step,
        )
