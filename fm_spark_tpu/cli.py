"""Command-line entry points: train / eval / predict / preprocess.

Parity target is the lineage's example driver ``main()`` (SURVEY.md §2
row 8, §5 "Config / flag system"): parse args, load data, train/test
split, train, report AUC/logloss. Instead of positional spark-submit args
this exposes the registered benchmark configs (:mod:`fm_spark_tpu.configs`)
with flag overrides::

    python -m fm_spark_tpu.cli list-configs
    python -m fm_spark_tpu.cli train --config movielens_fm_r8 \
        --data u.data --model-out /tmp/model
    python -m fm_spark_tpu.cli train --config criteo1tb_fm_r64 \
        --synthetic 100000 --steps 50
    python -m fm_spark_tpu.cli eval  --model /tmp/model --data u.data
    python -m fm_spark_tpu.cli predict --model /tmp/model --data u.data \
        --out preds.csv
    python -m fm_spark_tpu.cli preprocess --config criteo_kaggle_fm_r32 \
        --input day0.tsv --out-dir /data/packed

Training strategies (``--strategy`` overrides the config default):
``single`` (one-device FMTrainer), ``field_sparse`` (the fused sparse-SGD
fast path for field-partitioned FM), ``dp``/``row`` (mesh-parallel psum
steps over all visible devices).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

import numpy as np

from fm_spark_tpu import parallel, sparse
from fm_spark_tpu.cli_levers import (
    _add_lever_args,
    _lever_overrides,
    check_levers_any,
)


# ----------------------------------------------------------------- data


from fm_spark_tpu.data.packed import field_local as _field_local


def _is_packed_dir(path) -> bool:
    import os

    return bool(path) and os.path.isdir(path)


def _ingest_guard(args, windowed: bool = True):
    """Build the per-record error policy from the dirty-data flags
    (``--data-policy`` / ``--quarantine-dir`` / ``--max-bad-frac``);
    the defaults reproduce the pre-hardening strict behavior (first bad
    record raises, now with ``path:lineno`` context). Shared by the
    in-memory text loaders (per-line ``on_error`` callbacks +
    whole-load breaker — they pass ``windowed=False`` because their
    good count arrives in one post-parse bulk, which the trailing
    window would misread as a 100%-bad burst) and the streaming ingest
    path (ISSUE 5)."""
    from fm_spark_tpu.data.stream import RecordGuard

    policy = getattr(args, "data_policy", "strict")
    qdir = getattr(args, "quarantine_dir", None)
    frac = getattr(args, "max_bad_frac", None)
    if policy == "quarantine" and not qdir:
        # ISSUE 7 consolidation: without an explicit quarantine dir the
        # dead-letter journal joins the run's other telemetry under the
        # per-run obs directory.
        from fm_spark_tpu import obs

        qdir = obs.run_dir()
    if policy == "quarantine" and not qdir:
        raise SystemExit(
            "--data-policy quarantine needs --quarantine-dir or an "
            "active --obs-dir (the dead-letter journal has to land "
            "somewhere)"
        )
    return RecordGuard(policy=policy, quarantine_dir=qdir,
                       max_bad_frac=1.0 if frac is None else frac,
                       windowed=windowed)


def _synthetic_local(ids, shaped):
    """A synthetic draw's ids made field-local for ``shaped`` (a config
    or a spec: ``bucket``, and ``hots`` where its columns are bags). A
    slot's bucket range is its own (``field_local``) unless bags share
    their column's: then the id within the range, whichever slot."""
    if getattr(shaped, "hots", ()):
        return ids % np.int32(shaped.bucket)
    return _field_local(ids, shaped.bucket)


def load_dataset(cfg, args) -> tuple:
    """Return ``(ids, vals, labels, num_features)`` per the config's dataset.

    ``--synthetic N`` works for every config (planted-FM CTR data shaped
    like the config); otherwise ``--data`` is interpreted by dataset kind:
    movielens → ratings file, criteo/avazu → a raw text file (parsed
    in-memory; packed dirs stream via :class:`StreamingBatches` in
    ``train`` instead of loading here), libsvm → text.
    """
    from fm_spark_tpu import data as data_lib

    if args.synthetic:
        n = args.synthetic
        if cfg.bucket > 0:
            num_features = cfg.num_features
            ids, vals, labels = data_lib.synthetic_ctr(
                n, num_features, cfg.num_fields, seed=cfg.seed,
                dense_fields=cfg.dense_fields, hots=cfg.hots or None,
            )
        else:  # dense-id dataset stand-in (movielens-like shapes)
            num_features = 4096
            ids, vals, labels = data_lib.synthetic_ctr(
                n, num_features, cfg.num_fields, seed=cfg.seed
            )
        if cfg.field_local_ids:
            ids = _synthetic_local(ids, cfg)
        return ids, vals, labels, num_features

    if not args.data:
        raise SystemExit("need --data PATH or --synthetic N")

    if cfg.dataset == "movielens":
        from fm_spark_tpu.data import movielens

        (ids, vals, labels), meta = movielens.load_ratings(
            args.data, task=cfg.task
        )
        return ids, vals, labels, meta["num_features"]

    if cfg.dataset in ("criteo", "avazu"):
        if _is_packed_dir(args.data):
            raise SystemExit(
                "packed dirs are streamed, not loaded whole; this path "
                "handles text files (bug: caller should use StreamingBatches)"
            )
        # Small raw text file: parse in memory. The per-line error
        # callback routes malformed rows through the active policy
        # (strict raise with path:lineno / quarantine + dead-letter);
        # the whole-load breaker then vets the overall bad fraction.
        mod = __import__(
            f"fm_spark_tpu.data.{cfg.dataset}", fromlist=["parse_lines"]
        )
        with open(args.data, "rb") as f:
            lines = f.read().splitlines()
        header_off = 0
        if cfg.dataset == "avazu" and lines and lines[0].startswith(b"id,"):
            lines = lines[1:]
            header_off = 1
        guard = _ingest_guard(args, windowed=False)
        parse_args = dict(per_field=True, on_error=guard.on_error,
                          path=args.data, start_lineno=1 + header_off)
        if cfg.dense_fields:
            # The integer columns as values, not as hashed bins.
            if cfg.dense_fields != getattr(mod, "NUM_INT", None):
                raise SystemExit(
                    f"config {cfg.name!r} reads {cfg.dense_fields} dense "
                    f"columns of a {cfg.dataset!r} file; only criteo's 13 "
                    "integer columns load as values")
            ids, vals, labels = mod.parse_lines_dense(
                lines, cfg.bucket, **parse_args)
        else:
            ids, labels = mod.parse_lines(lines, cfg.bucket, **parse_args)
            vals = np.ones(ids.shape, np.float32)
        guard.ok_many(len(labels))
        guard.check_overall()
        # parse_lines yields int8 labels (the packed on-disk dtype); every
        # other loader hands float32 to the jitted steps — match it, or the
        # step recompiles against a second signature.
        labels = labels.astype(np.float32)
        if cfg.field_local_ids:
            ids = _field_local(ids, cfg.bucket)
        return ids, vals, labels, cfg.num_features

    if cfg.dataset == "libsvm":
        guard = _ingest_guard(args, windowed=False)
        ids, vals, labels = data_lib.load_libsvm(
            args.data, on_error=guard.on_error
        )
        guard.ok_many(labels.shape[0])
        guard.check_overall()
        return ids, vals, labels, int(ids.max()) + 1 if ids.size else 1

    raise SystemExit(f"don't know how to load dataset kind {cfg.dataset!r}")


def iter_packed_once(ds, batch_size: int, bucket: int = 0, row_range=None):
    """One ordered, finite, fixed-shape pass over a packed dataset —
    the streaming analog of :func:`fm_spark_tpu.data.iterate_once` for
    evaluation/prediction (final partial batch zero-padded, weight 0)."""
    lo, hi = row_range if row_range is not None else (0, len(ds))
    for start in range(lo, hi, batch_size):
        end = min(start + batch_size, hi)
        ids, vals, labels = ds.assemble(np.s_[start:end], bucket=bucket)
        b = end - start
        pad = batch_size - b
        weights = np.ones((b,), np.float32)
        if pad:
            ids = np.concatenate([ids, np.zeros((pad,) + ids.shape[1:],
                                                ids.dtype)])
            vals = np.concatenate([vals, np.zeros((pad,) + vals.shape[1:],
                                                  vals.dtype)])
            labels = np.concatenate([labels, np.zeros((pad,), labels.dtype)])
            weights = np.concatenate([weights, np.zeros((pad,), np.float32)])
        yield ids, vals, labels, weights


class StreamingBatches:
    """Resumable batch source over a packed dir, with optional conversion
    of per-field-offset global ids to field-local ids (FieldFM layout).

    Wraps :class:`fm_spark_tpu.data.PackedBatches` — memory-mapped,
    chunk-shuffled, never materializes the dataset (a Criteo-1TB packed
    dir is hundreds of GB; whole-array loading would OOM the host).
    """

    def __init__(self, packed, bucket: int = 0):
        self._inner = packed
        self._bucket = bucket

    def next_batch(self):
        ids, vals, labels, weights = next(self._inner)
        if self._bucket:
            ids = _field_local(ids, self._bucket)
        return ids, vals, labels, weights

    def __iter__(self):
        return self

    def __next__(self):
        return self.next_batch()

    def state(self) -> dict:
        return self._inner.state()

    def restore(self, state: dict) -> None:
        self._inner.restore(state)


# ----------------------------------------------------------------- train


def _resume(checkpointer, params, opt_state, batches,
            layout: str = "canonical"):
    """Restore (params, opt_state, start_step) from the latest checkpoint.

    ``layout`` names what THIS run will save ("canonical" per-field host
    trees, or "sharded" live mesh arrays — cli --ckpt-sharded); the
    checkpoint's recorded layout must match, both ways, or the user gets
    an actionable message instead of an orbax tree-structure traceback.
    For ``layout="sharded"`` the examples are the freshly sharded arrays
    and orbax restores each shard to its owner.
    """
    if checkpointer is None:
        return params, opt_state, 0
    hint = (
        "add --ckpt-sharded to resume it (or point --checkpoint-dir at "
        "a fresh directory)"
        if layout == "canonical"
        else "drop --ckpt-sharded to resume it (or point "
        "--checkpoint-dir at a fresh directory)"
    )
    from fm_spark_tpu import obs

    try:
        with obs.interval("setup/resume", layout=layout):
            restored = checkpointer.restore(params, opt_state)
    except Exception as e:
        raise SystemExit(
            f"could not restore the checkpoint as {layout}-layout — the "
            "directory likely holds the other layout (then: " + hint +
            "), or a sharded checkpoint is being resumed onto a "
            f"different device count / mesh: {e}"
        ) from e
    if restored is None:
        return params, opt_state, 0
    stored = (restored.get("extra") or {}).get("layout") or "canonical"
    if stored != layout:
        raise SystemExit(
            f"checkpoint at this directory is {stored}-layout but this "
            f"run saves {layout}-layout; " + hint
        )
    if restored["pipeline"] is not None:
        batches.restore(restored["pipeline"])
    return restored["params"], restored["opt_state"], restored["step"]


def _periodic_evaluator(spec, tconfig, eval_source, logger, evaluate=None):
    """Shared periodic-eval hook for the non-FMTrainer loops: returns
    ``maybe_eval(step, params_thunk)``, a no-op unless ``eval_every`` is
    set; eval wall-clock is excluded from the throughput window.
    ``evaluate`` overrides the default canonical-params evaluator (the
    field-sharded loop passes one that scores on the live sharded arrays
    — no table gather)."""
    if eval_source is None or tconfig.eval_every <= 0:
        return lambda step, params, window=1: None
    import time as _time

    if evaluate is None:
        from fm_spark_tpu.train import evaluate_params, make_eval_step

        estep = make_eval_step(spec)  # compiled once, reused every eval
        evaluate = lambda params_thunk: evaluate_params(
            spec, params_thunk(), eval_source(), step=estep
        )

    def maybe_eval(step, params_thunk, window=1):
        # Windowed cadence: fire iff a multiple of eval_every falls in
        # (step - window, step]. window=1 is the classic modulo; multi-
        # step loops pass their stride so off-aligned steps still fire.
        every = tconfig.eval_every
        if (step // every) <= ((step - window) // every):
            return
        t0 = _time.perf_counter()
        em = evaluate(params_thunk)
        logger.log(step, **{f"eval_{k}": v for k, v in em.items()})
        logger.add_pause(_time.perf_counter() - t0)

    return maybe_eval


@dataclasses.dataclass(frozen=True)
class _FieldCap:
    """One row of the field_sparse CAPABILITY TABLE: which step factory
    serves a model family in each layout (the levers it serves are its
    own declaration, ``sparse.serves_of``), and what the loop around it
    can do. An unsupported request hard-fails (no silent fallback)."""

    single_step: callable            # (spec, tconfig) -> step
    sharded_step: callable | None    # (spec, tconfig, mesh) -> step
    carries_opt: bool                # dense state rides the step (DeepFM's
                                     # optax state; accumulators of the
                                     # dense leaves under a table rule)
    table_rules: tuple               # table optimizers besides 'sgd' whose
                                     # slot tables ride the one-chip step
    sharded_2d: bool                 # 2-D (feat, row) mesh (--row-shards)
    sharded_multiproc: bool          # multi-process pseudo-cluster / pods
    multistep_single: bool           # --steps-per-call fori roll (1 chip)
    multistep_sharded: bool          # --steps-per-call on the sharded step


_FIELD_CAPS = {
    "FieldFMSpec": _FieldCap(
        single_step=sparse.make_field_sparse_sgd_step,
        sharded_step=parallel.make_field_sharded_sgd_step,
        carries_opt=False, table_rules=(),
        sharded_2d=True, sharded_multiproc=True,
        multistep_single=True, multistep_sharded=True,
    ),
    "FieldFFMSpec": _FieldCap(
        single_step=sparse.make_field_ffm_step,
        sharded_step=parallel.make_field_ffm_sharded_step,
        carries_opt=False, table_rules=("adagrad",),
        sharded_2d=True, sharded_multiproc=True,
        multistep_single=True, multistep_sharded=True,
    ),
    "FieldDeepFMSpec": _FieldCap(
        single_step=sparse.make_field_deepfm_sparse_step,
        sharded_step=parallel.make_field_deepfm_sharded_step,
        carries_opt=True, table_rules=(),
        sharded_2d=True, sharded_multiproc=True,
        multistep_single=True, multistep_sharded=True,
    ),
    # DeepFM's one-chip body with the CIN as its head: one chip, one step
    # a call (no mesh step computes a CIN; the roll is not held by tests).
    "FieldXDeepFMSpec": _FieldCap(
        single_step=sparse.make_field_deepfm_sparse_step, sharded_step=None,
        carries_opt=True, table_rules=(),
        sharded_2d=False, sharded_multiproc=False,
        multistep_single=False, multistep_sharded=False,
    ),
    # One chip, one step a call: no mesh step takes a real-valued column
    # or a replicated bottom stack yet (ROADMAP Reach).
    "FieldDLRMSpec": _FieldCap(
        single_step=sparse.make_field_dlrm_sparse_step, sharded_step=None,
        carries_opt=True, table_rules=(),
        sharded_2d=False, sharded_multiproc=False,
        multistep_single=False, multistep_sharded=False,
    ),
    # One chip, one step a call, AdaGrad on every parameter: table slots
    # AND the dense leaves' accumulators ride the step (no SGD body
    # pools a bag; no mesh step, no roll).
    "FieldDCNSpec": _FieldCap(
        single_step=sparse.make_field_dcn_adagrad_step, sharded_step=None,
        carries_opt=True, table_rules=("adagrad",),
        sharded_2d=False, sharded_multiproc=False,
        multistep_single=False, multistep_sharded=False,
    ),
}


def check_row_scale(strategy: str, num_features: int) -> str | None:
    """The ≥1M-feature ``row``-strategy guardrail (VERDICT r5 next-round
    #8). ``row`` materializes a dense per-shard gradient table every
    step (parallel/step.py SCALE CAVEAT) — measured ~8× below the fused
    ``field_sparse`` path at CTR scale — so meeting a production-sized
    table with it is almost always a mistake, not a choice. Returns the
    warning text, or None when the combination is fine."""
    if strategy != "row" or num_features < 1_000_000:
        return None
    return (
        f"strategy 'row' with {num_features:,} features materializes a "
        "dense per-shard gradient table every step — measured ~8x below "
        "the fused sparse path at CTR scale (parallel/step.py SCALE "
        "CAVEAT). Use --strategy field_sparse for tables this size, or "
        "pass --force to run 'row' anyway (exact optimizer parity is "
        "its one remaining use)."
    )


def _make_overflow_guard(tconfig):
    """Sticky overflow detection for the device-compact 'error' policy.

    ``_fold_overflow`` poisons the STEP loss to −inf (unreachable by any
    shipped loss — they are non-negative — so a genuinely diverging
    run's +inf is never mistaken for a cap overflow). A single step's
    loss is NOT a sufficient detector though: an overflow at step i
    followed by clean steps would go unseen at the next boundary, and a
    checkpoint would snapshot the drop-corrupted tables (ADVICE r3 +
    round-4 review). So the training loop calls ``note_loss`` on EVERY
    step's loss, maintaining a device-side RUNNING MIN — one fused
    ``jnp.minimum``, no device→host sync — and the boundary calls
    (``check_poison`` before every checkpoint save; ``fetch_loss`` at
    log cadence) read that: −inf is sticky from the first poisoned step
    onward. Returns ``(note_loss, check_poison, fetch_loss)``; all are
    no-ops/plain-float when the policy is inactive.
    """
    import math as _math

    import jax.numpy as jnp

    guard_active = (tconfig.compact_device
                    and tconfig.compact_overflow == "error")
    poison_box = {"v": jnp.float32(jnp.inf) if guard_active else None}

    def note_loss(loss):
        if guard_active:
            # fmin, not minimum: a later NaN loss (genuine divergence)
            # must not launder the −inf sentinel into NaN and slip past
            # the isinf check.
            poison_box["v"] = jnp.fmin(poison_box["v"], loss)

    def check_poison():
        if guard_active:
            pv = float(poison_box["v"])
            if _math.isinf(pv) and pv < 0:
                raise SystemExit(
                    "compact_cap overflow: a field's per-batch "
                    "unique-id count exceeded --compact-cap "
                    f"{tconfig.compact_cap} at some step since the "
                    "last clean checkpoint (loss poisoned to −inf by "
                    "the 'error' policy; the running-min detector is "
                    "sticky). Raise --compact-cap, or pick "
                    "--compact-overflow drop; restart from the last "
                    "checkpoint."
                )

    def fetch_loss(loss) -> float:
        check_poison()
        return float(loss)

    return note_loss, check_poison, fetch_loss


def _validate_field_caps(spec, tconfig, cap, n, pc, sharded,
                         row_shards, steps_per_call, ckpt_sharded):
    """The field_sparse guard block: every request a family's steps
    cannot serve hard-fails against the capability row (_FIELD_CAPS) and
    the declaration of the factory the loop builds — never a silent
    fallback. Returns ``(compact_sharded, multi)``.
    Split out of _fit_field_sparse (VERDICT r3: the loop function was
    accreting validation, placement, resume, and the loop)."""
    if row_shards < 1:
        raise SystemExit(f"--row-shards must be >= 1, got {row_shards}")
    if sharded and cap.sharded_step is None:
        raise SystemExit(
            f"{type(spec).__name__} has no field-sharded step: it trains "
            f"on one chip (found {n} devices)"
        )
    if row_shards > 1 and not (sharded and cap.sharded_2d):
        # Never silently ignore an explicit sharding request.
        raise SystemExit(
            f"--row-shards={row_shards} needs multiple devices and a "
            f"model family with a 2-D (feat, row) sharded step "
            f"(found {n} device(s), {type(spec).__name__})"
        )
    if ckpt_sharded and not sharded:
        raise SystemExit(
            "--ckpt-sharded applies to multi-device field-sharded runs "
            f"(found {n} device(s)); the default canonical layout "
            "already serves single-chip runs"
        )
    if pc > 1 and not cap.sharded_multiproc:
        raise SystemExit(
            f"multi-process training is not supported for "
            f"{type(spec).__name__}"
        )
    if steps_per_call < 1:
        raise SystemExit(
            f"--steps-per-call must be >= 1, got {steps_per_call}"
        )
    multi = steps_per_call > 1
    if tconfig.optimizer in cap.table_rules and (sharded or multi):
        # The slot tables ride the one-chip step alone: the mesh steps
        # and the fori roll write their tables by plain SGD.
        raise SystemExit(
            f"--optimizer {tconfig.optimizer} on the tables of "
            f"{type(spec).__name__} runs on one chip, one step a call "
            f"(found {n} device(s), --steps-per-call {steps_per_call}); "
            "the field-sharded steps and the multistep roll implement "
            "plain SGD only"
        )
    compact_sharded = (
        tconfig.host_dedup and tconfig.compact_cap > 0 and sharded
    )
    if multi:
        if sharded:
            # The SHARDED roll (round 4): the fori rides inside the
            # shard_map for FM/FFM, and in the outer jit around it for
            # DeepFM (the optax carry). No host-built aux (its
            # per-batch producer chain does not stack — compact_device
            # composes instead); multi-process rides
            # shard_field_batch_stacked_local (pseudo-cluster phase 7).
            if not cap.multistep_sharded:
                raise SystemExit(
                    "--steps-per-call > 1 on multiple devices is not "
                    f"supported for {type(spec).__name__}"
                )
            if compact_sharded:
                raise SystemExit(
                    "--steps-per-call > 1 does not take the host-built "
                    "compact aux; use --compact-device"
                )

        elif not cap.multistep_single:
            raise SystemExit(
                "--steps-per-call > 1 is not supported for "
                f"{type(spec).__name__} on a single device"
            )
    # The levers: what the factory the loop builds declares it serves.
    factory = cap.sharded_step if sharded else cap.single_step
    layout = "field-sharded" if sharded else "single-chip"
    try:
        sparse.refuse_unserved(
            tconfig, sparse.serves_of(factory, spec, tconfig),
            f"the {layout} {type(spec).__name__} step (found {n} "
            "device(s))", spec.loss)
    except ValueError as refused:
        raise SystemExit(str(refused)) from None
    if compact_sharded and (row_shards > 1 or pc > 1):
        # The HOST-built aux needs some host to hold every field's full
        # global column (excludes multi-process) and raw global ids
        # (excludes 2-D row ownership). The device-built aux has neither
        # constraint.
        raise SystemExit(
            "host-built --compact-cap on multiple chips requires a 1-D "
            "field mesh (no --row-shards) and a single process; add "
            "--compact-device to build the aux in-step, which composes "
            "with both"
        )
    if sharded:
        if tconfig.batch_size % n:
            raise SystemExit(
                f"batch_size={tconfig.batch_size} must be divisible by "
                f"the device count ({n}) for the field-sharded strategy"
            )
        if n % row_shards:
            raise SystemExit(
                f"--row-shards={row_shards} must divide the device "
                f"count ({n})"
            )

    return compact_sharded, multi


def _place_field_state(spec, tconfig, cap, canonical, opt0, n, pc,
                       sharded, row_shards, compact_sharded,
                       devices=None):
    """Step construction + parameter/batch placement for the
    field_sparse loop, from the capability row: single-chip or
    field-sharded (1-D/2-D mesh, single- or multi-process), with the
    uniform ``(params, opt, i, *b) → (params, opt, loss)`` step shape.
    Returns ``(step, params, opt, prep, to_canonical, mesh)`` —
    ``mesh`` is None single-chip. A step that carries table slots
    (``cap.table_rules``; :func:`_hold_slots` places them) returns a
    fourth value, its ``stats``. Split out of _fit_field_sparse
    (VERDICT r3)."""
    import jax
    import jax.numpy as jnp

    is_deepfm = cap.carries_opt
    slots = tconfig.optimizer in cap.table_rules
    mesh = None

    def adapt(step_pl):
        """Lift a ``(params, i, *b) → (params, loss)`` step into the
        uniform ``(params, opt, i, *b) → (params, opt, loss)`` shape."""
        def wrapped(params, opt, i, *b):
            params, loss = step_pl(params, i, *b)
            return params, opt, loss
        return wrapped

    host = lambda b: jax.tree_util.tree_map(jnp.asarray, tuple(b))

    if sharded:
        from fm_spark_tpu.parallel import (
            FieldBatchFeed, make_field_mesh, pad_field_batch,
            shard_field_deepfm_params, shard_field_params,
            stack_field_deepfm_params, stack_field_params,
            unstack_field_deepfm_params, unstack_field_params,
        )

        n_feat = n // row_shards
        mesh = make_field_mesh(n, n_row=row_shards, devices=devices)
        if pc > 1:
            from fm_spark_tpu.parallel import shard_field_batch_local

            # Each process feeds only its local slice of the global
            # batch; the global array is assembled across hosts.
            prep = lambda b: shard_field_batch_local(
                pad_field_batch(b, spec.num_fields, n_feat), mesh
            )
            # device_get cannot fetch non-addressable shards; the gather
            # crosses processes (DCN) — used only for canonical
            # checkpoints/final export (--ckpt-sharded avoids it).
            from jax.experimental import multihost_utils

            fetch = lambda p: multihost_utils.process_allgather(
                p, tiled=True
            )
        else:
            # Whole host batches through prep(b); from a loader that can
            # hand rows over, each chip's shard made and sent apart.
            prep = FieldBatchFeed(mesh, spec.num_fields)
            fetch = jax.device_get
        if is_deepfm:
            step = cap.sharded_step(spec, tconfig, mesh)
            params = shard_field_deepfm_params(
                stack_field_deepfm_params(spec, canonical, n_feat), mesh
            )
            opt = jax.device_put(opt0)
            to_canonical = lambda p: unstack_field_deepfm_params(
                spec, fetch(p)
            )
        else:
            step = adapt(cap.sharded_step(spec, tconfig, mesh))
            params = shard_field_params(
                stack_field_params(spec, canonical, n_feat), mesh
            )
            opt = opt0
            to_canonical = lambda p: unstack_field_params(
                spec, fetch(p)
            )
        if compact_sharded:
            # DedupAuxBatches (installed below) appends the compact aux;
            # the F_pad padding (stack_compact_aux) rides the producer
            # thread via the MappedBatches wrapper installed alongside
            # it, so prep only device-places it field-wise with the
            # padded batch.
            from fm_spark_tpu.parallel import place_compact_aux

            _data_prep = prep
            prep = lambda b: (
                *_data_prep(b[:4]), place_compact_aux(b[4], mesh),
            )
    else:
        from fm_spark_tpu.models import rows

        built = cap.single_step(spec, tconfig)
        step = built if is_deepfm or slots else adapt(built)
        # The loop holds its tables in the form models/rows.py chooses
        # for a holder that writes (lane-padded where the chip would
        # otherwise transpose each in and out of a step), formed once
        # here, each canonical table let go as its held one arrives.
        # What leaves the loop — evals, checkpoints, the returned model
        # — is canonical again (the identity where nothing changed form).
        params, shapes, _ = rows.hold(canonical, sparse.FUSED_TABLE_KEYS,
                                      writes=True, consume=True)
        to_canonical = lambda p, release=False: rows.canonical(
            p, shapes, release
        )
        opt, prep = opt0, host

    return step, params, opt, prep, to_canonical, mesh


def _tree_bytes(tree) -> int:
    """The bytes of a tree's arrays by their shapes (no device asked)."""
    import jax

    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree.leaves(tree))


def _hold_slots(slots0):
    """The one-chip loop's table slots (``optim.init_field_slots``'s
    tree, or a checkpoint's) placed as its tables are: each slot table
    in the form ``models/rows.py`` gives its table, by the same walk,
    the canonical one let go as its held one arrives. Returns ``(slots,
    to_host)``; ``to_host(slots)`` is the canonical tree as NumPy, one
    table at a time, so that a checkpoint never stands a second copy of
    the slots beside the first on the device."""
    import jax
    import numpy as np

    from fm_spark_tpu import obs
    from fm_spark_tpu.models import rows

    slots, shapes, held = rows.hold(slots0, sparse.FUSED_TABLE_KEYS,
                                    writes=True, consume=True)
    obs.gauge("train/slot_table_bytes").set(held["resident_table_bytes"])
    to_host = lambda o: jax.tree.map(
        lambda shape, leaf: np.asarray(rows.canonical(leaf, shape)),
        shapes, o)
    return slots, to_host


def _fit_field_sparse(spec, tconfig, batches, logger, checkpointer=None,
                      eval_source=None, prefetch: int = 0,
                      row_shards: int = 1, steps_per_call: int = 1,
                      ckpt_sharded: bool = False, devices=None,
                      place_in_loop: bool = False, setup_done=None):
    """Training loop on the fused sparse steps (the CTR fast path).

    On one device this is the single-chip fused step; with multiple
    devices the field-sharded layout (parallel/field_step.py) is used —
    tables partitioned over chips, all_to_all batch re-shard inside the
    step. FieldDeepFM additionally carries optax state for its dense
    head (MLP + bias); pure-SGD models carry an empty dict so the loop
    and checkpoints have one shape.

    ``steps_per_call > 1`` (single-chip FM/FFM) rolls that many steps
    into one compiled ``fori_loop`` program over host-stacked batches —
    bench.py's dispatch amortization for the production loop (PERF.md
    fact 1). Logging/eval/checkpoint cadence rounds to call boundaries.

    ``ckpt_sharded`` (multi-device field-sharded runs) checkpoints the
    STACKED SHARDED arrays directly — orbax writes each shard from its
    owning process, no full-table host gather per save. Sharded
    checkpoints resume only onto the same mesh layout; the default
    canonical (per-field-list) layout remains the topology-portable
    format.

    ``devices`` (elastic degraded mode) pins the loop to an explicit
    device subset: the mesh is built from exactly these devices and the
    canonical checkpoint re-places onto them at resume — how the
    elastic retry wrapper continues a run on the surviving half of a
    shrunk fleet.

    Batches are placed by the FEED (``wrap_prefetch(..., place=prep)``:
    in the prefetcher's thread, each chip's shard made and sent apart on
    a mesh), so the loop takes them from the queue already on the
    chips. ``place_in_loop`` keeps ``prep`` on the loop's own thread
    instead: the elastic wrapper's, whose mesh shrinks under it — a
    queued batch placed on a lost chip is worse than a slow one.

    What comes before the loop leaves hot intervals, disjoint and in
    this order: ``setup/init`` (``spec.init`` and the optimizer's state,
    with the waits ``spec.init`` makes itself and no other),
    ``setup/resume`` (only where a checkpoint is read; after
    ``setup/place`` under ``ckpt_sharded``), ``setup/place``
    (:func:`_place_field_state` and :func:`_hold_slots`) and
    ``setup/step_build`` (everything from there to the loop: the
    placement report, evaluators, rolled steps, the feed). ``setup_done``,
    where given, is called the instant before the loop's first step:
    ``cmd_train`` closes its ``setup/run`` there.
    """
    import jax
    import jax.numpy as jnp

    from fm_spark_tpu import obs

    n = len(devices) if devices is not None else jax.device_count()
    pc = jax.process_count()
    cap = _FIELD_CAPS.get(type(spec).__name__)
    if cap is None:
        raise SystemExit(
            f"field_sparse strategy has no capability row for "
            f"{type(spec).__name__}"
        )
    sharded = n > 1
    is_deepfm = cap.carries_opt
    slots = tconfig.optimizer in cap.table_rules

    # ---- validation + placement (helpers above) -----------------------
    compact_sharded, multi = _validate_field_caps(
        spec, tconfig, cap, n, pc, sharded, row_shards, steps_per_call,
        ckpt_sharded,
    )

    if tconfig.fused_embed == "auto" and not sharded:
        # The 'auto' lever's fallback is silent in the step's OUTPUTS
        # but never in its provenance (ISSUE 8): surface which fused
        # Pallas family serves this run — or why the XLA path runs
        # instead — before any compile happens.
        family, reason = sparse.fused_embed_plan(spec, tconfig)
        print(
            (f"fused-embed: serving kernel family {family!r}"
             if family else
             f"fused-embed: XLA fallback ({reason})"),
            file=sys.stderr,
        )

    # ---- state init ---------------------------------------------------
    with obs.interval("setup/init") as phase:
        canonical = spec.init(jax.random.key(tconfig.seed))
        opt0 = {}
        if is_deepfm and not slots:
            from fm_spark_tpu.train import make_optimizer

            # Dense-head optimizer state only (structure is device-count
            # independent, so checkpoints resume on any mesh).
            opt0 = make_optimizer(tconfig).init(
                {k: canonical[k] for k in spec.dense_keys}
            )
        elif slots:
            # Table-sized state: only its shapes until a checkpoint has
            # had its say (a restore reads into them), so that a resume
            # never holds fresh slots beside the restored ones. A family
            # whose dense leaves take the tables' rule keeps their
            # accumulators in the same tree.
            import functools

            from fm_spark_tpu import optim

            init_slots = functools.partial(
                optim.init_field_slots, tconfig.optimizer,
                keys=sparse.FUSED_TABLE_KEYS,
                init_accumulator=tconfig.adagrad_init_accumulator,
                dense_keys=spec.dense_keys if is_deepfm else ())
            opt0 = jax.eval_shape(init_slots, canonical)
        n_tables = len(jax.tree.leaves(canonical["vw"]))
        phase.set(tables=n_tables, bytes=_tree_bytes(canonical))
    start = 0
    if not ckpt_sharded:
        # Default: checkpoints use the canonical per-field-list layout so
        # a run can resume on a different device count. (Sharded resume
        # happens AFTER params are placed on the mesh, below.)
        canonical, opt0, start = _resume(checkpointer, canonical, opt0,
                                         batches)
    opt_canonical = (jax.device_get if is_deepfm and not slots
                     else (lambda o: {}))
    with obs.interval("setup/place", tables=n_tables) as phase:
        if slots and isinstance(jax.tree.leaves(opt0)[0],
                                jax.ShapeDtypeStruct):
            opt0 = init_slots(canonical)
        step, params, opt, prep, to_canonical, mesh = _place_field_state(
            spec, tconfig, cap, canonical, opt0, n, pc, sharded, row_shards,
            compact_sharded, devices=devices,
        )
        if slots:
            opt, opt_canonical = _hold_slots(opt)
        # A spec's tables share one shape, so one says how all are held.
        held, made = (jax.tree.leaves(tree["vw"])[0].shape
                      for tree in (params, canonical))
        phase.set(bytes=_tree_bytes((params, opt)),
                  form=("stacked" if sharded else
                        "padded" if held != made else "as_is"))

    if ckpt_sharded:
        params, opt, start = _resume(checkpointer, params, opt, batches,
                                     layout="sharded")
    t_build = time.perf_counter()
    # Where the tables landed, and what each device's memory looked like
    # once they had (chip_smoke.py checks both on the chip).
    from fm_spark_tpu.utils import device as device_lib

    placed = device_lib.placement(params)
    print(json.dumps({"placement": placed}), flush=True)
    if (not slots and tconfig.sparse_update == "scatter_add"
            and not tconfig.use_pallas):
        # The SGD bodies' default write: a field's owner takes the whole
        # batch's lanes (one chip, or a mesh after its all-to-all) and
        # ops/scatter decides from them and the table's shape.
        from fm_spark_tpu.ops import scatter as scatter_lib

        obs.gauge("train/update_lanes_per_field").set(
            scatter_lib.update_lanes(
                tconfig.batch_size,
                jax.tree.leaves(params["vw"])[0].shape[-2:]))
    if getattr(spec, "dense_fields", 0):
        # A model with dense input columns: how many of the batch's slots
        # are values.
        obs.gauge("train/dense_fields").set(spec.dense_fields)
    if hasattr(spec, "mxu_flops_per_step"):
        # The model's own count of a step's matrix products.
        obs.gauge("train/mxu_flops_per_step").set(
            spec.mxu_flops_per_step(tconfig.batch_size))
    if getattr(spec, "cin_layers", ()):
        # A CIN: the Hadamard products a step forms, and the operations
        # of the products it runs (under mxu_flops_per_step's count where
        # the last layer pools first).
        obs.gauge("train/cin_outer_elems_per_step").set(
            spec.cin_outer_elems_per_step(tconfig.batch_size))
        obs.gauge("train/cin_product_flops_per_step").set(
            spec.cin_product_flops_per_step(tconfig.batch_size))
    if getattr(spec, "hots", ()):
        # A model whose columns are bags: the ids an example carries and
        # the lanes a step's pooled gather and write move.
        obs.gauge("train/ids_per_example").set(spec.ids_per_example)
        obs.gauge("train/pool_lanes_per_step").set(
            spec.ids_per_example * tconfig.batch_size)
    obs.event("table_layout", table_layouts=placed["table_layouts"],
              table_device_bytes=placed["table_device_bytes"])

    sharded_eval = None
    if (sharded and eval_source is not None and tconfig.eval_every > 0):
        # Periodic eval on the live sharded arrays — the multi-GB tables
        # never leave the mesh. evaluate_field_sharded dispatches the
        # family-specific eval step (FM / FFM / DeepFM); build it once
        # here so every eval reuses the compiled program.
        from fm_spark_tpu.models.field_deepfm import FieldDeepFMSpec
        from fm_spark_tpu.models.field_ffm import FieldFFMSpec
        from fm_spark_tpu.parallel import (
            evaluate_field_sharded,
            make_field_deepfm_sharded_eval_step,
            make_field_ffm_sharded_eval_step,
            make_field_sharded_eval_step,
        )

        if isinstance(spec, FieldDeepFMSpec):
            _sh_estep = make_field_deepfm_sharded_eval_step(
                spec, mesh, deep_sharded=tconfig.deep_sharded
            )
        elif isinstance(spec, FieldFFMSpec):
            _sh_estep = make_field_ffm_sharded_eval_step(spec, mesh)
        else:
            _sh_estep = make_field_sharded_eval_step(spec, mesh)
        sharded_eval = lambda _thunk: evaluate_field_sharded(
            spec, mesh, params, eval_source(), estep=_sh_estep
        )
    maybe_eval = _periodic_evaluator(spec, tconfig, eval_source, logger,
                                     evaluate=sharded_eval)
    log_every = max(tconfig.log_every, 1)
    since = 0
    from fm_spark_tpu.data import wrap_prefetch

    def pipe_state():
        """Pipeline cursor for checkpoints. Multi-host: strip the
        per-process row range (lo/hi) — each host re-derives its own on
        resume and restores only the common (epoch, index) cursor, which
        stays in lockstep across hosts."""
        st = batches.state()
        if jax.process_count() > 1 and isinstance(st, dict):
            st = {k: v for k, v in st.items() if k not in ("lo", "hi")}
        return st

    note_loss, check_poison, fetch_loss = _make_overflow_guard(tconfig)

    # What a checkpoint stores: canonical host trees (topology-portable,
    # the default) or the live sharded arrays (--ckpt-sharded; orbax
    # writes each shard from its owner, no host gather).
    if ckpt_sharded:
        ckpt_params = lambda: params
        ckpt_opt = lambda: opt
        ckpt_extra = {"layout": "sharded"}
    else:
        ckpt_params = lambda: to_canonical(params)
        ckpt_opt = lambda: opt_canonical(opt)
        ckpt_extra = None
    if tconfig.host_dedup:
        # BEFORE the prefetcher: the per-field argsorts run in the
        # producer thread, off the device critical path.
        from fm_spark_tpu.data import DedupAuxBatches

        batches = DedupAuxBatches(
            batches, cap=tconfig.compact_cap,
            overflow=("split" if tconfig.compact_overflow == "split"
                      else "error"),
        )
        if compact_sharded:
            # F_pad-padding of the aux also belongs in the producer.
            # compact_sharded guarantees row_shards == 1 (validated
            # above), so the feat extent is the full device count.
            from fm_spark_tpu.data import MappedBatches
            from fm_spark_tpu.parallel import stack_compact_aux

            batches = MappedBatches(
                batches,
                lambda b: (*b[:4], stack_compact_aux(b[4], n)),
            )
    if multi:
        from fm_spark_tpu.data import StackedBatches

        if sharded:
            # Pad each batch to F_pad in the producer; ONE compiled
            # program rolls the m sharded steps, amortizing per-call
            # dispatch exactly like the single-chip roll.
            from fm_spark_tpu.data import MappedBatches
            from fm_spark_tpu.parallel import (
                make_field_deepfm_sharded_multistep,
                make_field_sharded_multistep,
                pad_field_batch,
                shard_field_batch_stacked,
            )

            n_feat = n // row_shards
            batches = MappedBatches(
                batches,
                lambda b: pad_field_batch(b, spec.num_fields, n_feat),
            )
            if is_deepfm:
                mstep = make_field_deepfm_sharded_multistep(
                    spec, tconfig, mesh, steps_per_call)
            else:
                mstep = make_field_sharded_multistep(spec, tconfig,
                                                     mesh,
                                                     steps_per_call)
            if pc > 1:
                # Each process stacks its LOCAL row slices; the global
                # stacked arrays assemble across hosts.
                from fm_spark_tpu.parallel import (
                    shard_field_batch_stacked_local,
                )

                prep = lambda sb: shard_field_batch_stacked_local(
                    sb, mesh)
            else:
                prep = lambda sb: shard_field_batch_stacked(sb, mesh)
        elif is_deepfm:
            mstep = sparse.make_field_deepfm_multistep(spec, tconfig,
                                                       steps_per_call)
        else:
            mstep = sparse.make_field_sparse_multistep(spec, tconfig,
                                                       steps_per_call)
        # Stacking runs in the prefetch producer thread. `total` bounds
        # source consumption so the tail stack pads instead of reading
        # batches that would never train (exact-resume cursor).
        batches = StackedBatches(batches, steps_per_call,
                                 total=tconfig.num_steps - start)
    from fm_spark_tpu.resilience import faults

    if place_in_loop:
        batches, close_prefetch = wrap_prefetch(batches, prefetch)
    else:
        batches, close_prefetch = wrap_prefetch(batches, prefetch,
                                                place=prep)
        prep = lambda placed: placed
    obs.record_interval("setup/step_build", t_build, time.perf_counter())
    if setup_done is not None:
        setup_done()
    # The loop's hot intervals (obs.interval: always-live ring, profiler
    # annotation, trace.jsonl). Per iteration a parent ``train/step``
    # and inside it next_batch (the wait on the prefetch queue), prep
    # (what placement is left on this thread: taking the tuple, unless
    # place_in_loop — the feed's own share is feed/place), dispatch
    # (the jitted call returning) and, at log cadence, loss_fetch (the
    # fence: the host waiting for the device). The rest of train/step
    # is its self time.
    try:
        if multi:
            i = start
            while i < tconfig.num_steps:
                m = min(steps_per_call, tconfig.num_steps - i)
                with obs.interval("train/step", step=i, steps=m):
                    # Deterministic mid-run device loss for the elastic
                    # shrink tests (resilience/faults.py); a single
                    # is-None check when no fault plan is active.
                    faults.inject("train_step")
                    with obs.interval("train/next_batch", step=i):
                        stacked = batches.next_batch()
                    with obs.interval("train/prep", step=i):
                        placed = prep(stacked)
                    with obs.interval("train/dispatch", step=i):
                        if is_deepfm:
                            params, opt, loss = mstep(
                                params, opt, jnp.int32(i), jnp.int32(m),
                                *placed)
                        else:
                            params, loss = mstep(params, jnp.int32(i),
                                                 jnp.int32(m), *placed)
                    note_loss(loss)
                    i += m
                    since += m * stacked[2].shape[1]
                    # Windowed cadences: a multiple of the interval
                    # inside (i-m, i] fires, so stride-advanced (and
                    # off-aligned resumed) counters never silently skip.
                    if (i // log_every) > ((i - m) // log_every) or (
                        i >= tconfig.num_steps
                    ):
                        with obs.interval("train/loss_fetch", step=i - m):
                            loss_now = fetch_loss(loss)
                        logger.log(i, samples=since, loss=loss_now)
                        since = 0
                    maybe_eval(i, lambda: to_canonical(params), window=m)
                    if (checkpointer is not None
                            and checkpointer.due_window(i, m)):
                        check_poison()
                        # Same layout contract as the per-step loop:
                        # --ckpt-sharded saves the live sharded arrays
                        # (no host gather) and records the layout for
                        # resume.
                        checkpointer.save(i, ckpt_params(), ckpt_opt(),
                                          pipe_state(), extra=ckpt_extra)
        else:
            for i in range(start, tconfig.num_steps):
                with obs.interval("train/step", step=i):
                    faults.inject("train_step")
                    with obs.interval("train/next_batch", step=i):
                        batch = batches.next_batch()
                    with obs.interval("train/prep", step=i):
                        placed = prep(batch)
                    with obs.interval("train/dispatch", step=i):
                        params, opt, loss, *stats = step(
                            params, opt, jnp.int32(i), *placed)
                    note_loss(loss)
                    since += len(batch[2])
                    if ((i + 1) % log_every == 0
                            or i == tconfig.num_steps - 1):
                        with obs.interval("train/loss_fetch", step=i):
                            loss_now = fetch_loss(loss)
                            # What the step reported (a slot-carrying
                            # step's unique rows, a CIN's pooled maps):
                            # device values of the step just fenced, no
                            # second wait.
                            counted = {k: np.asarray(v).tolist()
                                       for st in stats
                                       for k, v in st.items()}
                        logger.log(i + 1, samples=since, loss=loss_now,
                                   **counted)
                        since = 0
                    maybe_eval(i + 1, lambda: to_canonical(params))
                    if checkpointer is not None and checkpointer.due(i + 1):
                        check_poison()
                        checkpointer.save(i + 1, ckpt_params(), ckpt_opt(),
                                          pipe_state(), extra=ckpt_extra)
        if checkpointer is not None:
            if start < tconfig.num_steps:
                check_poison()
            checkpointer.save(tconfig.num_steps, ckpt_params(), ckpt_opt(),
                              pipe_state(), extra=ckpt_extra,
                              force=True)
            checkpointer.wait()
    finally:
        close_prefetch()
    print(json.dumps({"memory_after_fit": device_lib.memory()}),
          flush=True)
    if sharded:
        return to_canonical(params)
    # Nothing reads the loop's own tables after this: each padded one
    # goes as its canonical one arrives (never two generations).
    return to_canonical(params, release=True)


def _fit_field_sparse_elastic(spec, tconfig, batches, checkpointer,
                              eval_source, prefetch, row_shards,
                              steps_per_call, max_shrinks,
                              journal, metrics_path, supervisor=None):
    """Elastic degraded-mode wrapper around :func:`_fit_field_sparse`
    (the tentpole of ISSUE 4): a mid-run device loss is journaled and
    retried by the supervisor (probe + bounded backoff); when the
    breaker opens on a PERMANENT fault — N identical consecutive losses,
    the dead-attachment signature — the elastic controller halves the
    device set, the mesh is rebuilt from the survivors, the last good
    checkpoint re-places onto the smaller mesh (the canonical layout is
    topology-portable by construction), per-chip metrics re-normalize
    to the surviving chip count, and training continues 8→4→2→1 instead
    of dying. Mixed-mode circuit opens and non-device errors propagate
    unchanged.
    """
    import jax

    from fm_spark_tpu.resilience import (
        BackoffPolicy,
        CircuitOpen,
        ElasticController,
        Supervisor,
        is_device_loss,
    )
    from fm_spark_tpu.utils.logging import MetricsLogger

    if supervisor is None:
        supervisor = Supervisor(
            policy=BackoffPolicy(initial=1.0, multiplier=2.0,
                                 max_delay=15.0),
            journal=journal, breaker_threshold=3,
        )
    elastic = ElasticController(max_shrinks=max_shrinks, journal=journal)
    devices = None  # full fleet until the first shrink
    # A retry with NO committed checkpoint yet must rewind the batch
    # source to its pre-run cursor — _resume only restores a cursor a
    # checkpoint recorded, and replaying from mid-stream would silently
    # skip the already-consumed window.
    initial_cursor = batches.state() if hasattr(batches, "state") else None
    logger = MetricsLogger(path=metrics_path, n_chips=jax.device_count())
    # Committed progress between two losses means the attachment came
    # BACK — the breaker counts CONSECUTIVE losses, so a long run that
    # flaps once an hour must never accumulate toward a permanent
    # verdict (the same note_success contract FMTrainer.fit wires into
    # its save cadence).
    step_at_last_failure = None
    while True:
        try:
            params = _fit_field_sparse(
                spec, tconfig, batches, logger, checkpointer,
                eval_source=eval_source, prefetch=prefetch,
                row_shards=row_shards, steps_per_call=steps_per_call,
                devices=devices, place_in_loop=True,
            )
            supervisor.note_success("train")
            if elastic.degraded and journal is not None:
                journal.emit("degraded_complete", **elastic.summary())
            return params, elastic
        except Exception as e:  # noqa: BLE001 — classified below
            if not is_device_loss(e):
                raise
            # An async save may be wedged on dead buffers; committed
            # checkpoints on disk are all the resume needs.
            checkpointer.reopen()
            committed = checkpointer.latest_step()
            if (step_at_last_failure is not None and committed is not None
                    and committed > step_at_last_failure):
                supervisor.note_success("train")
            step_at_last_failure = committed
            try:
                supervisor.recover("train", e)
            except CircuitOpen:
                if not supervisor.permanent() or not elastic.can_shrink():
                    raise
                devices = elastic.shrink("train")
                if tconfig.batch_size % len(devices):
                    raise SystemExit(
                        f"elastic shrink reached {len(devices)} device(s) "
                        f"but batch_size={tconfig.batch_size} does not "
                        "divide by it; pick a batch divisible by every "
                        "shrink step (halving from the initial mesh) or "
                        "lower --max-shrinks"
                    ) from e
                logger.set_n_chips(len(devices))
                supervisor.reset("train")
            if (initial_cursor is not None
                    and checkpointer.latest_step() is None):
                batches.restore(initial_cursor)


def _fit_parallel(spec, tconfig, batches, strategy, logger, checkpointer=None,
                  eval_source=None, prefetch: int = 0):
    """Training loop on the mesh-parallel psum step (dp / row)."""
    import jax

    from fm_spark_tpu.parallel import (
        make_mesh, make_parallel_train_step, shard_batch, shard_params,
    )
    from fm_spark_tpu.train import make_optimizer

    n = jax.device_count()
    n_feat = 1
    if strategy == "row":
        # Use as many feat shards as divide the table; rest goes to data.
        for cand in range(min(n, 8), 0, -1):
            if n % cand == 0 and spec.num_features % cand == 0:
                n_feat = cand
                break
    mesh = make_mesh(n // n_feat, n_feat)
    step = make_parallel_train_step(spec, tconfig, mesh, strategy)
    params = shard_params(
        spec.init(jax.random.key(tconfig.seed)), mesh, spec, strategy
    )
    opt_state = make_optimizer(tconfig).init(params)
    params, opt_state, start = _resume(checkpointer, params, opt_state, batches)
    # Eval streams through the single-device step on gathered params —
    # rare relative to training, so clarity wins over sharded eval here.
    maybe_eval = _periodic_evaluator(
        spec, tconfig, eval_source, logger
    )
    log_every = max(tconfig.log_every, 1)
    since = 0
    from fm_spark_tpu.data import wrap_prefetch

    batches, close_prefetch = wrap_prefetch(batches, prefetch)
    try:
        for i in range(start, tconfig.num_steps):
            batch = shard_batch(batches.next_batch(), mesh)
            params, opt_state, m = step(params, opt_state, *batch)
            since += batch[2].shape[0]
            if (i + 1) % log_every == 0 or i == tconfig.num_steps - 1:
                logger.log(i + 1, samples=since, loss=float(m["loss"]),
                           grad_norm=float(m["grad_norm"]))
                since = 0
            maybe_eval(i + 1, lambda: jax.device_get(params))
            if checkpointer is not None:
                checkpointer.maybe_save(i + 1, params, opt_state,
                                        batches.state())
        if checkpointer is not None:
            checkpointer.save(tconfig.num_steps, params, opt_state,
                              batches.state(), force=True)
            checkpointer.wait()
    finally:
        close_prefetch()
    return params


def _maybe_init_distributed(args) -> None:
    """``--distributed``: run ``jax.distributed.initialize`` BEFORE the
    first backend touch, so multi-host training needs no hand-written
    launcher around the CLI.

    On a Cloud TPU pod slice the bare flag suffices (jax auto-detects
    coordinator/process topology from the TPU metadata); elsewhere pass
    the explicit triple. The three explicit flags require each other —
    a partial triple would silently fall back to auto-detection on the
    wrong cluster, so it hard-fails instead. The multi-process training
    semantics themselves (field-sharded step, per-host batch placement,
    cross-host checkpoint layout) are the ones exercised by the
    2-process pseudo-cluster (tests/multihost_worker.py); this hook
    only removes the external-initializer requirement.
    """
    if not args.distributed:
        if (args.coordinator is not None or args.num_processes is not None
                or args.process_id is not None):
            raise SystemExit(
                "--coordinator/--num-processes/--process-id require "
                "--distributed"
            )
        return
    explicit = (args.coordinator, args.num_processes, args.process_id)
    if any(x is not None for x in explicit) and None in explicit:
        raise SystemExit(
            "--coordinator, --num-processes and --process-id must be "
            "given together (a partial triple would auto-detect against "
            "the wrong cluster)"
        )
    import jax

    if args.coordinator is not None:
        jax.distributed.initialize(
            coordinator_address=args.coordinator,
            num_processes=args.num_processes,
            process_id=args.process_id,
        )
    else:
        jax.distributed.initialize()


def _online_days(args, cfg):
    """Assemble the time-ordered day list for ``train --online``:
    ``--synthetic N`` split into ``--online-days`` slices (with the
    ``--drift-inject`` label-flip drill lever), or ``--data d0,d1,...``
    — one raw-text shard per day, parsed in memory per dataset kind."""
    from fm_spark_tpu import data as data_lib
    from fm_spark_tpu import online

    if args.synthetic:
        num_features = cfg.num_features if cfg.bucket > 0 else 4096
        ids, vals, labels = data_lib.synthetic_ctr(
            args.synthetic, num_features, cfg.num_fields, seed=cfg.seed)
        days = online.split_days(ids, vals, labels, args.online_days)
        if args.drift_inject is not None:
            days = online.flip_labels(days, args.drift_inject)
        return days, num_features
    if not args.data or "," not in args.data:
        raise SystemExit(
            "--online needs time-ordered days: --data d0,d1,... (one "
            "shard per day) or --synthetic N with --online-days")
    if args.drift_inject is not None:
        raise SystemExit("--drift-inject is the synthetic drill lever; "
                         "real day shards carry their own drift")
    paths = [p for p in args.data.split(",") if p]
    days = []
    if cfg.dataset in ("criteo", "avazu"):
        mod = __import__(f"fm_spark_tpu.data.{cfg.dataset}",
                         fromlist=["parse_lines"])
        for path in paths:
            with open(path, "rb") as f:
                lines = f.read().splitlines()
            if cfg.dataset == "avazu" and lines and \
                    lines[0].startswith(b"id,"):
                lines = lines[1:]
            guard = _ingest_guard(args, windowed=False)
            ids, labels = mod.parse_lines(
                lines, cfg.bucket, per_field=True,
                on_error=guard.on_error, path=path, start_lineno=1)
            guard.ok_many(len(labels))
            guard.check_overall()
            days.append((ids, np.ones(ids.shape, np.float32),
                         labels.astype(np.float32)))
        return days, cfg.num_features
    if cfg.dataset == "libsvm":
        from fm_spark_tpu.data import load_libsvm

        num_features = 0
        for path in paths:
            guard = _ingest_guard(args, windowed=False)
            ids, vals, labels = load_libsvm(path,
                                            on_error=guard.on_error)
            guard.ok_many(labels.shape[0])
            guard.check_overall()
            num_features = max(num_features,
                               int(ids.max()) + 1 if ids.size else 1)
            days.append((ids, vals, labels))
        return days, num_features
    raise SystemExit(
        f"--online day shards support criteo/avazu/libsvm text "
        f"(config {cfg.name!r} is dataset {cfg.dataset!r}); use "
        "--synthetic N for a config-free run")


def _run_online_cmd(args, cfg, tconfig) -> int:
    """``train --online``: the continuous-learning protocol (ISSUE 13)
    — see :mod:`fm_spark_tpu.online` for the loop itself."""
    from fm_spark_tpu import obs, online
    from fm_spark_tpu.checkpoint import Checkpointer
    from fm_spark_tpu.train import FMTrainer
    from fm_spark_tpu.utils.logging import EventLog

    if cfg.strategy != "single" or not args.checkpoint_dir:
        raise SystemExit(
            "--online requires strategy 'single' and --checkpoint-dir "
            "(day-granular rollback restores demoted generations from "
            f"the chain; config {cfg.name!r} resolves to strategy "
            f"{cfg.strategy!r})")
    if cfg.task != "classification":
        raise SystemExit("--online watches eval AUC; config "
                         f"{cfg.name!r} is task {cfg.task!r}")
    days, num_features = _online_days(args, cfg)
    spec = cfg.spec(num_features if cfg.bucket <= 0 else None)

    import os as _os

    _os.makedirs(args.checkpoint_dir, exist_ok=True)
    journal = EventLog(_os.path.join(args.checkpoint_dir,
                                     "health.jsonl"),
                       mirror_to_flight=True)
    checkpointer = Checkpointer(args.checkpoint_dir,
                                save_every=args.checkpoint_every,
                                journal=journal)
    trainer = FMTrainer(spec, tconfig)
    sentry = online.drift_guard(
        drop_factor=args.drift_drop_factor,
        max_rollbacks=args.drift_max_rollbacks, journal=journal)
    ledger = leg = fingerprint = run_id = None
    if args.quality_ledger:
        from fm_spark_tpu.obs.ledger import (
            PerfLedger,
            measurement_fingerprint,
            runtime_versions,
        )
        from fm_spark_tpu.utils import device as device_lib

        dev = device_lib.describe()
        ledger = PerfLedger(args.quality_ledger)
        leg = f"{online.QUALITY_LEG_PREFIX}{cfg.name}/{tconfig.optimizer}"
        fingerprint = measurement_fingerprint(
            variant=leg, model=cfg.model, batch=tconfig.batch_size,
            rank=cfg.rank,
            extra={"optimizer": tconfig.optimizer,
                   "lr": tconfig.learning_rate},
            device_kind=dev["kind"], n_chips=dev["count"],
            **runtime_versions())
        run_id = obs.run_id() or obs.new_run_id()
    try:
        summary = online.run_online(
            trainer, days, checkpointer, sentry=sentry,
            journal=journal, ledger=ledger, leg=leg,
            fingerprint=fingerprint, run_id=run_id)
    finally:
        checkpointer.close()
        journal.close()
    print(json.dumps({"online": summary}))
    if args.model_out:
        from fm_spark_tpu import models as models_lib

        models_lib.save_model(args.model_out, spec, trainer.params)
        print(json.dumps({"saved": args.model_out}))
    return 0


def _start_metrics_endpoint(args) -> None:
    """``--metrics-port`` (ISSUE 14): serve the live registry over
    stdlib HTTP (``/metrics`` Prometheus text + ``/healthz`` JSON) so a
    long-running loop is inspectable without touching the process. The
    bound port is echoed as a JSON line (port 0 = OS-assigned — how
    tests and co-located daemons avoid collisions); the server rides a
    daemon thread and is stopped in ``main``'s finally."""
    port = getattr(args, "metrics_port", None)
    if port is None:
        return
    from fm_spark_tpu.obs import export as obs_export

    srv = obs_export.start_metrics_server(port)
    print(json.dumps({"metrics_port": srv.port,
                      "metrics_url": srv.url,
                      "endpoints": ["/metrics", "/healthz"]}),
          flush=True)


def _announce_device(cache_dir: str) -> None:
    """The first JSON line of ``train`` and ``serve``: the device every
    later rate in the stream came from (initialises the backend)."""
    from fm_spark_tpu.utils import device as device_lib

    print(json.dumps({"device": device_lib.describe(),
                      "compile_cache": cache_dir}), flush=True)


def cmd_train(args) -> int:
    # Set-up is one hot interval, ``setup/run``: from here to the instant
    # before the loop's first step (``setup_done`` below), with the
    # phases that take the time as intervals inside it.
    t_entry = time.perf_counter()

    from fm_spark_tpu import configs as configs_lib
    from fm_spark_tpu import models
    from fm_spark_tpu import obs
    from fm_spark_tpu.data import Batches, train_test_split
    from fm_spark_tpu.train import FMTrainer, evaluate_params
    from fm_spark_tpu.utils import compile_cache
    from fm_spark_tpu.utils.logging import MetricsLogger

    # Warm start: the persistent compilation cache goes on BEFORE any
    # jit compile, so a second run of the same config skips every XLA
    # compilation (utils/compile_cache says where it lives).
    cache_dir = compile_cache.enable()
    _maybe_init_distributed(args)
    _announce_device(cache_dir)

    # Telemetry plane (ISSUE 7): on by default — every stream this run
    # emits (spans, metrics snapshots, the flight-recorder window, any
    # dead-letter journal) lands under <obs-dir>/<run_id>/.
    _obs_dir = getattr(args, "obs_dir", None)
    if _obs_dir and _obs_dir.lower() != "none":
        import os as _os_obs

        from fm_spark_tpu import obs
        from fm_spark_tpu.obs import introspect as _introspect

        _obs_run = obs.new_run_id()
        obs.configure(_os_obs.path.join(_obs_dir, _obs_run),
                      run_id=_obs_run, install_signals=True)
        # Deep-capture engine (ISSUE 14): anomaly triggers (sentinel
        # regressions, watchdog near-misses, step-time spikes) arm
        # bounded capture bundles under this run's obs dir.
        _introspect.configure(obs.run_dir(), run_id=_obs_run)
        print(json.dumps({"run_id": _obs_run, "obs_dir": obs.run_dir()}),
              flush=True)
    _start_metrics_endpoint(args)

    batch_size = args.batch_size
    if args.batch_per_chip is not None:
        if batch_size is not None:
            raise SystemExit(
                "--batch-per-chip and --batch-size are exclusive "
                "(weak scaling derives the global batch from the mesh)"
            )
        import jax as _jax0

        batch_size = args.batch_per_chip * _jax0.device_count()
    cfg = configs_lib.get_config(
        args.config,
        num_steps=args.steps, batch_size=batch_size,
        learning_rate=args.lr, strategy=args.strategy, seed=args.seed,
        optimizer=args.optimizer, loss=args.loss,
        sparse_update=args.sparse_update,
        param_dtype=args.param_dtype,
        compute_dtype=args.compute_dtype,
        use_pallas=True if args.use_pallas else None,
    )
    tconfig = cfg.train_config(
        log_every=args.log_every, metrics_path=args.metrics,
        eval_every=args.eval_every,
        **_lever_overrides(args),
    )
    msg = check_levers_any(tconfig)
    if msg:
        raise SystemExit(msg)

    import jax as _jax

    pc = _jax.process_count()
    if pc > 1:
        # Only the multi-chip field-sharded loop has cross-host parameter
        # semantics (collectives inside the step + local batch placement);
        # every other loop would silently train a DIFFERENT model per
        # host on its data shard. Family support comes from the
        # capability table (_FIELD_CAPS.sharded_multiproc).
        if cfg.strategy != "field_sparse":
            raise SystemExit(
                f"multi-process training supports strategy "
                f"'field_sparse' only; config {cfg.name!r} resolves to "
                f"strategy {cfg.strategy!r}"
            )
        if tconfig.batch_size % pc:
            raise SystemExit(
                f"batch_size={tconfig.batch_size} must be divisible by "
                f"the process count ({pc})"
            )

    if cfg.dense_fields and (args.online or (args.data and (
            "," in args.data or _is_packed_dir(args.data)))):
        # Dense columns are values; the packed format and the streaming
        # parsers hold hashed ids alone, and the online loop draws its own.
        raise SystemExit(
            f"config {cfg.name!r} reads {cfg.dense_fields} dense columns as "
            "values: it trains from --synthetic N or one raw text file "
            "(--data FILE), not from a packed dir, a shard list or --online")
    if cfg.hots and (args.online or args.data):
        # No file format of this repo holds a bag of ids a column yet.
        raise SystemExit(
            f"config {cfg.name!r} reads {sum(cfg.hots)} ids an example in "
            f"{len(cfg.hots)} bags: it trains from --synthetic N; the "
            "Criteo text and packed formats hold one id a column")
    if args.online:
        # Continuous learning (ISSUE 13): its own day-granular loop —
        # time-ordered train/eval, drift sentry, coordinated rollback.
        if pc > 1:
            raise SystemExit("--online is single-process")
        return _run_online_cmd(args, cfg, tconfig)

    te = None
    te_packed = None
    if cfg.dataset in ("criteo", "avazu") and _is_packed_dir(args.data):
        # Large preprocessed data: stream from the memory-mapped packed
        # dir. --test-fraction holds out the file's TAIL rows — a random
        # split iff the packed dir was shuffled (preprocess shuffles by
        # default; with --no-shuffle this is a TEMPORAL tail split, e.g.
        # the last Criteo day, and held-out metrics are not comparable to
        # a random-split baseline).
        from fm_spark_tpu.data import PackedBatches, PackedDataset

        spec = cfg.spec()
        ds = PackedDataset(args.data)
        cut = (
            max(1, int(len(ds) * (1.0 - args.test_fraction)))
            if args.test_fraction > 0 else len(ds)
        )
        bucket = cfg.bucket if cfg.field_local_ids else 0
        if pc > 1:
            # Multi-host ingestion: each process streams ITS contiguous
            # slice of the train rows and feeds batch_size/pc rows per
            # step (the Spark partitions-per-executor analog); equal
            # slices keep the hosts' epoch cursors in lockstep.
            per = cut // pc
            pid = _jax.process_index()
            row_range = (pid * per, (pid + 1) * per)
            local_bs = tconfig.batch_size // pc
        else:
            row_range = (0, cut)
            local_bs = tconfig.batch_size
        # bucket pushed into PackedBatches: the field-local conversion
        # fuses into the (native) row gather instead of a second pass,
        # and PackedBatches speaks the batch-source protocol directly.
        batches = PackedBatches(ds, local_bs, seed=cfg.seed,
                                row_range=row_range, bucket=bucket)
        if cut < len(ds):
            te_packed = (ds, (cut, len(ds)), bucket)
    elif (cfg.dataset in ("criteo", "avazu") and args.data
          and "," in args.data):
        # Multi-shard raw-text streaming (ISSUE 5): --data takes a
        # comma-separated ordered shard list; the bounded-memory
        # ShardReader + RecordGuard ingest trains straight off dirty,
        # larger-than-RAM text with an exactly-once checkpointable
        # cursor — no preprocess step, no whole-file materialization.
        import os as _os

        from fm_spark_tpu.data import MappedBatches
        from fm_spark_tpu.data.stream import (
            ShardReader,
            StreamBatches,
            line_parser,
        )

        paths = [p for p in args.data.split(",") if p]
        missing = [p for p in paths if not _os.path.isfile(p)]
        if missing:
            raise SystemExit(
                f"missing shard file(s): {', '.join(missing)}"
            )
        if args.test_fraction > 0:
            raise SystemExit(
                "streaming text ingest (--data with a comma-separated "
                "shard list) holds out no eval split; pass "
                "--test-fraction 0, or preprocess to a packed dir for "
                "held-out metrics"
            )
        if pc > 1:
            raise SystemExit(
                "streaming text ingest is single-process; preprocess "
                "to a packed dir for multi-host runs"
            )
        spec = cfg.spec()
        # Headers are skipped by MATCH, not position: a split(1)-sharded
        # headered CSV carries the header in shard 0 only, and dropping
        # line 1 of every shard would eat one real record per shard.
        reader = ShardReader(paths,
                             header_prefix=(b"id," if cfg.dataset ==
                                            "avazu" else None))
        if args.native_ingest:
            # Native-rate ingest (ISSUE 6): C++ chunk parse with the
            # exactly-once cursor and quarantine semantics preserved
            # bit-identically; falls back to the per-line Python path
            # automatically when the library cannot be built or the config
            # is outside the native contract.
            from fm_spark_tpu.data.native_stream import (
                NativeStreamBatches,
                make_stream_batches,
                native_stream_unsupported_reason,
            )

            batches = make_stream_batches(
                reader, cfg.dataset, tconfig.batch_size,
                max_nnz=cfg.num_fields, guard=_ingest_guard(args),
                num_features=cfg.num_features, bucket=cfg.bucket,
                native_ingest="auto",
            )
            if not isinstance(batches, NativeStreamBatches):
                print(
                    "cli: --native-ingest fell back to the pure-Python "
                    "streaming parser: "
                    + str(native_stream_unsupported_reason(
                        cfg.dataset, cfg.num_fields, cfg.bucket)),
                    file=sys.stderr,
                )
        else:
            batches = StreamBatches(
                reader, line_parser(cfg.dataset, cfg.bucket),
                tconfig.batch_size, max_nnz=cfg.num_fields,
                guard=_ingest_guard(args), num_features=cfg.num_features,
            )
        if cfg.field_local_ids:
            # Producer-thread id conversion, same placement as the
            # packed StreamingBatches path; the guard surfaces through
            # the wrapper's pass-through property.
            batches = MappedBatches(
                batches,
                lambda b: (_field_local(b[0], cfg.bucket), *b[1:]),
            )
    else:
        with obs.interval("setup/data") as phase:
            ids, vals, labels, num_features = load_dataset(cfg, args)
            phase.set(rows=len(labels))
        spec = cfg.spec(num_features if cfg.bucket <= 0 else None)
        (tr, te) = (
            train_test_split(ids, vals, labels, args.test_fraction,
                             seed=cfg.seed)
            if args.test_fraction > 0
            else ((ids, vals, labels), None)
        )
        if pc > 1:
            # Strided per-process split (keeps label mix); local batch =
            # global / processes, matching the per-host input shard the
            # field-sharded step's make_array placement expects.
            pid = _jax.process_index()
            tr = tuple(a[pid::pc] for a in tr)
            batches = Batches(*tr, tconfig.batch_size // pc, seed=cfg.seed)
        else:
            batches = Batches(*tr, tconfig.batch_size, seed=cfg.seed)

    import contextlib

    checkpointer = None
    health_journal = None
    if args.checkpoint_dir:
        from fm_spark_tpu.checkpoint import Checkpointer

        if args.supervise or args.elastic or args.divergence_guard is not None:
            import os as _os0

            from fm_spark_tpu.utils.logging import EventLog

            _os0.makedirs(args.checkpoint_dir, exist_ok=True)
            # The journal stays WITH the checkpoint chain (one chain
            # dir can serve many runs; its narrative must not split
            # per-run), but every event is mirrored into the flight
            # ring so the run's fault timeline, flight_dump.json, and
            # obs_report carry the retry story too.
            health_journal = EventLog(
                _os0.path.join(args.checkpoint_dir, "health.jsonl"),
                mirror_to_flight=True,
            )
        checkpointer = Checkpointer(
            args.checkpoint_dir, save_every=args.checkpoint_every,
            journal=health_journal,
            verify="commit" if args.ckpt_sharded else "checksum",
        )

    profile_ctx = (
        _jax.profiler.trace(args.profile) if args.profile
        else contextlib.nullcontext()
    )
    strategy = cfg.strategy
    warn = check_row_scale(strategy, spec.num_features)
    if warn:
        if not args.force:
            raise SystemExit(warn)
        print(f"warning: {warn}", file=sys.stderr)
    supervisor = None
    if args.supervise:
        # Device-fault supervision (resilience/): single-strategy FMTrainer
        # only — the field-sharded loops keep their own failure semantics
        # — and recovery without committed state to resume from would
        # silently restart training, so the checkpointer is required.
        if strategy != "single" or not args.checkpoint_dir:
            raise SystemExit(
                "--supervise requires strategy 'single' and "
                "--checkpoint-dir (device-loss recovery resumes from "
                f"committed checkpoints; config {cfg.name!r} resolves "
                f"to strategy {strategy!r})"
            )
        from fm_spark_tpu.resilience import Supervisor

        supervisor = Supervisor(journal=health_journal)
    elastic = None
    if args.elastic:
        # Elastic degraded mode (ISSUE 4): permanent device loss sheds
        # capacity instead of killing the run. Resume-on-a-smaller-mesh
        # rides the topology-portable CANONICAL checkpoint layout, so
        # the mesh-pinned --ckpt-sharded layout is out; multi-process
        # shrink would need a coordinated re-init across hosts.
        if not args.checkpoint_dir:
            raise SystemExit(
                "--elastic requires --checkpoint-dir (degraded-mode "
                "resume restores the last good checkpoint onto the "
                "shrunk mesh)"
            )
        if strategy not in ("single", "field_sparse"):
            raise SystemExit(
                "--elastic supports strategies 'single' (with "
                "--supervise) and 'field_sparse'; config "
                f"{cfg.name!r} resolves to {strategy!r}"
            )
        if strategy == "single" and not args.supervise:
            raise SystemExit(
                "--elastic with strategy 'single' requires --supervise "
                "(the shrink trigger is the supervisor's "
                "permanent-fault verdict)"
            )
        if args.ckpt_sharded:
            raise SystemExit(
                "--elastic and --ckpt-sharded are exclusive: sharded "
                "checkpoints resume only onto the same mesh, but the "
                "whole point of elastic mode is resuming onto a "
                "smaller one (use the default canonical layout)"
            )
        if args.row_shards > 1:
            raise SystemExit(
                "--elastic requires --row-shards 1: a shrunk device set "
                "cannot honor a fixed row-shard extent (the halved count "
                "stops dividing by it) — the 2-D mesh's row capacity is "
                "a commitment elastic mode cannot keep"
            )
        if pc > 1:
            raise SystemExit(
                "--elastic is single-process: a multi-host gang cannot "
                "shrink without a coordinated re-initialize"
            )
        if strategy == "single":
            from fm_spark_tpu.resilience import ElasticController

            elastic = ElasticController(max_shrinks=args.max_shrinks,
                                        journal=health_journal)
    divergence_guard = None
    if args.divergence_guard is not None:
        if strategy != "single" or not args.checkpoint_dir:
            raise SystemExit(
                "--divergence-guard requires strategy 'single' and "
                "--checkpoint-dir (rollback restores the last good "
                f"checkpoint; config {cfg.name!r} resolves to strategy "
                f"{strategy!r})"
            )
        from fm_spark_tpu.resilience.divergence import DivergenceGuard

        divergence_guard = DivergenceGuard(
            spike_factor=args.divergence_guard, journal=health_journal
        )
    if (tconfig.host_dedup or tconfig.compact_device) and (
        strategy != "field_sparse"
    ):
        # Never silently ignore an explicit fast-path request: only the
        # fused field_sparse loop takes the compact/dedup paths.
        raise SystemExit(
            f"--host-dedup/--compact-device require strategy "
            f"'field_sparse' (config {cfg.name!r} resolves to "
            f"{strategy!r})"
        )
    if args.steps_per_call > 1 and strategy != "field_sparse":
        raise SystemExit(
            f"--steps-per-call requires strategy 'field_sparse' "
            f"(config {cfg.name!r} resolves to {strategy!r})"
        )
    if args.ckpt_sharded and (
        strategy != "field_sparse" or not args.checkpoint_dir
    ):
        raise SystemExit(
            "--ckpt-sharded requires strategy 'field_sparse' and "
            "--checkpoint-dir"
        )
    embed_mode = None
    if tconfig.embed_tier != "off":
        # ONE decision point (embed.tier_plan), same contract as the
        # fused_embed lever: 'require' turns a None verdict into a hard
        # failure carrying the reason; 'auto' falls back SAYING so.
        from fm_spark_tpu import embed as _embed

        embed_mode, embed_reason = _embed.tier_plan(spec, tconfig, strategy)
        if embed_mode is None:
            if tconfig.embed_tier == "require":
                raise SystemExit(
                    f"--embed-tier require cannot be served: "
                    f"{embed_reason}")
            print(
                f"embed-tier auto: in-HBM fallback ({embed_reason})",
                file=sys.stderr)
        else:
            if supervisor is not None or elastic is not None or \
                    divergence_guard is not None:
                raise SystemExit(
                    "--embed-tier is exclusive with --supervise/"
                    "--elastic/--divergence-guard: the tiered trainer "
                    "runs its own fit loop (residency state does not "
                    "survive a device rebuild)")
            if tconfig.eval_every > 0:
                raise SystemExit(
                    "--embed-tier does not run periodic in-fit eval "
                    "(eval_every > 0): held-out metrics come from the "
                    "merged view once at end of fit")
    from fm_spark_tpu.data import iterate_once as _iter_once

    if te is not None:
        eval_source = lambda: _iter_once(*te, tconfig.batch_size)
    elif te_packed is not None:
        eval_source = lambda: iter_packed_once(
            te_packed[0], tconfig.batch_size, bucket=te_packed[2],
            row_range=te_packed[1],
        )
    else:
        eval_source = None

    def setup_done():
        obs.record_interval("setup/run", t_entry, time.perf_counter(),
                            entry="train", config=cfg.name,
                            chips=_jax.device_count())

    if strategy != "field_sparse" or args.elastic:
        # These loops are not instrumented: their set-up ends here.
        setup_done()
    with profile_ctx:
        if strategy == "single" and embed_mode == "tiered":
            from fm_spark_tpu.embed import TieredTrainer

            trainer = TieredTrainer(spec, tconfig)
            params = trainer.fit(
                batches, checkpointer=checkpointer,
                prefetch=args.prefetch,
            )
        elif strategy == "single":
            trainer = FMTrainer(spec, tconfig)
            trainer.fit(
                batches, checkpointer=checkpointer,
                eval_batches=(
                    eval_source if tconfig.eval_every > 0 else None
                ),
                prefetch=args.prefetch,
                supervisor=supervisor,
                elastic=elastic,
                divergence_guard=divergence_guard,
            )
            params = trainer.params
        elif strategy == "field_sparse" and args.elastic:
            params, _ = _fit_field_sparse_elastic(
                spec, tconfig, batches, checkpointer, eval_source,
                prefetch=args.prefetch, row_shards=args.row_shards,
                steps_per_call=args.steps_per_call,
                max_shrinks=args.max_shrinks,
                journal=health_journal,
                metrics_path=tconfig.metrics_path,
            )
        else:
            # FMTrainer logs through its own MetricsLogger; these loops
            # need one built for them.
            logger = MetricsLogger(path=tconfig.metrics_path,
                                   n_chips=_jax.device_count())
            if strategy == "field_sparse":
                params = _fit_field_sparse(spec, tconfig, batches, logger,
                                           checkpointer,
                                           eval_source=eval_source,
                                           prefetch=args.prefetch,
                                           row_shards=args.row_shards,
                                           steps_per_call=args.steps_per_call,
                                           ckpt_sharded=args.ckpt_sharded,
                                           setup_done=setup_done)
            elif strategy in ("dp", "row"):
                params = _fit_parallel(spec, tconfig, batches, strategy,
                                       logger, checkpointer,
                                       eval_source=eval_source,
                                       prefetch=args.prefetch)
            else:
                raise SystemExit(f"unknown strategy {strategy!r}")

    ingest_guard = getattr(batches, "guard", None)
    if ingest_guard is not None and ingest_guard.n_bad:
        # Quarantine accounting in the CLI result stream (ISSUE 5),
        # whatever training loop ran; per-record detail stays in the
        # dead-letter journal.
        print(json.dumps({
            "bad_records": ingest_guard.n_bad,
            "good_records": ingest_guard.n_ok,
            "dead_letter": ingest_guard.dead_letter_path,
        }))

    metrics = None
    if strategy == "single" and embed_mode == "tiered":
        # The tiered trainer evaluates through its merged full-axis view.
        if eval_source is not None:
            metrics = evaluate_params(spec, params, eval_source())
    elif strategy == "single" and eval_source is not None:
        # fit() already evaluated the final model when eval_every > 0 —
        # don't re-stream the held-out set.
        metrics = trainer.last_eval or trainer.evaluate(eval_source())
    elif te is not None:
        from fm_spark_tpu.data import iterate_once

        metrics = evaluate_params(
            spec, params, iterate_once(*te, tconfig.batch_size)
        )
    elif te_packed is not None:
        ds, row_range, bucket = te_packed
        metrics = evaluate_params(
            spec, params,
            iter_packed_once(ds, tconfig.batch_size, bucket=bucket,
                             row_range=row_range),
        )
    if metrics is not None:
        print(json.dumps({"eval": metrics}))
    if args.model_out:
        models.save_model(args.model_out, spec, params)
        print(json.dumps({"saved": args.model_out}))
    from fm_spark_tpu import obs as _obs

    if _obs.enabled():
        # End-of-run device-memory watermark (ISSUE 9) — the final
        # metrics snapshot (obs.shutdown in main) then carries the HBM
        # peak/live-buffer gauges — and the run-doctor pointer, so the
        # run's diagnosis is one copy-paste away.
        _obs.device_memory_snapshot()
        print(json.dumps({
            "run_doctor": f"python tools/run_doctor.py {_obs.run_dir()}",
        }), flush=True)
    return 0


# ------------------------------------------------------------ eval/predict


def _batches_for_model(args, spec):
    """One finite pass of eval/predict batches shaped for a trained model.

    ``--synthetic N`` derives shapes from the model's own spec (never a
    config guess — mismatched shapes would silently clamp out-of-range
    ids into the table edge and print meaningless metrics). ``--data``
    needs ``--config`` to name the parser (packed dirs stream; text
    loads in memory), and the config's feature space must match the
    model's.
    """
    from fm_spark_tpu import configs as configs_lib
    from fm_spark_tpu import data as data_lib
    from fm_spark_tpu.data import iterate_once

    if args.synthetic:
        nnz = getattr(spec, "num_fields", 0) or min(8, spec.num_features)
        ids, vals, labels = data_lib.synthetic_ctr(
            args.synthetic, spec.num_features, nnz, seed=1,
            dense_fields=getattr(spec, "dense_fields", 0),
            hots=getattr(spec, "hots", None) or None,
        )
        if getattr(spec, "field_local_ids", False):
            ids = _synthetic_local(ids, spec)
        return iterate_once(ids, vals, labels, args.batch_size)

    if args.config is None:
        raise SystemExit(
            "eval/predict with --data needs --config to name the dataset "
            "loader (use --synthetic N for config-free smoke checks)"
        )
    cfg = configs_lib.get_config(args.config)
    if cfg.bucket > 0 and cfg.num_features != spec.num_features:
        raise SystemExit(
            f"config {cfg.name!r} encodes {cfg.num_features} features but "
            f"the model was trained with {spec.num_features}; ids would be "
            "silently clamped — pass the config the model was trained with"
        )
    if cfg.dataset in ("criteo", "avazu") and _is_packed_dir(args.data):
        ds = data_lib.PackedDataset(args.data)
        bucket = cfg.bucket if cfg.field_local_ids else 0
        return iter_packed_once(ds, args.batch_size, bucket=bucket)
    ids, vals, labels, num_features = load_dataset(cfg, args)
    if cfg.bucket <= 0 and num_features > spec.num_features:
        # Dense-id datasets (movielens/libsvm) size the feature space from
        # the data; ids beyond the model's table would be silently clamped
        # by XLA gather into the table edge — meaningless metrics.
        raise SystemExit(
            f"dataset has {num_features} features but the model was trained "
            f"with {spec.num_features}; out-of-range ids would be silently "
            "clamped — evaluate on data from the training feature space"
        )
    return iterate_once(ids, vals, labels, args.batch_size)


def cmd_eval(args) -> int:
    from fm_spark_tpu import models
    from fm_spark_tpu.train import evaluate_params

    spec, params = models.load_model(args.model)
    metrics = evaluate_params(spec, params, _batches_for_model(args, spec))
    print(json.dumps(metrics))
    return 0


def cmd_predict(args) -> int:
    from fm_spark_tpu import models
    from fm_spark_tpu.utils import compile_cache

    # Offline batch predict rides the serving engine (ISSUE 12
    # satellite): the same bucketed AOT executables the online path
    # dispatches — so the persistent compile cache gives a warm
    # process zero fresh XLA compiles here too. Output is bit-identical
    # to the pre-engine eager path (padded and unpadded executions
    # agree exactly; pinned in tests/test_serve.py).
    compile_cache.enable()
    spec, params = models.load_model(args.model)
    engine = None
    out = sys.stdout if args.out in (None, "-") else open(args.out, "w")
    try:
        for bids, bvals, _, w in _batches_for_model(args, spec):
            if engine is None:
                from fm_spark_tpu.serve import PredictEngine

                # One bucket = the batch size: every iterate_once
                # batch is already padded to it, so each dispatch is
                # shape-exact and warmup compiles exactly one program.
                engine = PredictEngine(
                    spec, params, nnz=bids.shape[1],
                    buckets=(args.batch_size,), latency_budget_ms=0.0,
                )
                engine.warmup()
            preds = engine.score(bids, bvals)
            for p in preds[w > 0]:
                out.write(f"{float(p):.6g}\n")
    finally:
        if out is not sys.stdout:
            out.close()
    return 0


def _serve_opt_example(spec, cfg):
    """The optimizer-state example a chain follower needs to restore
    the trainer's checkpoints: ``{}`` for the pure-SGD field families,
    the dense-head optax state for FieldDeepFM, and the FULL optax
    state for single-strategy dense families (an FMTrainer chain — the
    ``--online`` loop's layout — checkpoints the whole optimizer tree,
    per-coordinate FTRL/AdaGrad slots included). The two structured
    cases are buildable only with a config naming the optimizer."""
    from fm_spark_tpu.models.field_deepfm import FieldDeepFMSpec

    if spec.__class__.__name__.startswith("Field") and not isinstance(
            spec, FieldDeepFMSpec):
        return {}
    if cfg is None:
        raise SystemExit(
            "hot reload of this chain needs --config (the follower "
            "must rebuild the optimizer-state structure to restore "
            "the trainer's checkpoints)"
        )
    import jax

    from fm_spark_tpu.train import make_optimizer

    canonical = spec.init(jax.random.key(cfg.seed))
    if isinstance(spec, FieldDeepFMSpec):
        return make_optimizer(cfg.train_config()).init(
            {key: canonical[key] for key in spec.dense_keys}
        )
    return make_optimizer(cfg.train_config()).init(canonical)


def _serve_fleet(args, journal) -> int:
    """The production front door (ISSUE 17): ``--fleet N`` stands up N
    replica processes (each its own engine + read-only chain follower)
    behind one HTTP front door with deadline-aware admission control,
    and serves until SIGINT/SIGTERM (or ``--serve-seconds``). Emits
    the front door's URL up front and one summary JSON line (admission
    counters + per-replica health) on shutdown."""
    import os as _os
    import signal as _signal
    import tempfile as _tempfile
    import threading as _threading

    from fm_spark_tpu import obs
    from fm_spark_tpu.serve.fleet import Fleet
    from fm_spark_tpu.serve.frontdoor import (
        AdmissionController,
        FrontDoor,
    )

    if not args.model:
        raise SystemExit(
            "--fleet needs --model DIR: each replica loads the saved "
            "model, then (with --checkpoint-dir) hot-follows the "
            "chain through its own read-only follower")
    work_dir = (_os.path.join(obs.run_dir(), "fleet")
                if obs.run_dir()
                else _tempfile.mkdtemp(prefix="fm_fleet_"))
    if obs.run_dir():
        # The fleet gets its OWN journal stream — the file
        # tools/run_doctor.py's "Serving fleet" section reads —
        # keeping replica lifecycle events out of the single-engine
        # serve_health stream.
        from fm_spark_tpu.utils.logging import EventLog as _EventLog

        journal = _EventLog(
            _os.path.join(obs.run_dir(), "fleet_health.jsonl"),
            mirror_to_flight=True)
    # Replicas write their own obs run dirs under the SAME root as the
    # parent's (the per-process span files tools/trace_report.py
    # merges); no obs plane -> no replica tracing either.
    obs_root = (_os.path.dirname(obs.run_dir()) if obs.run_dir()
                else None)
    autoscaler = None
    if getattr(args, "autoscale_max", 0):
        from fm_spark_tpu.serve.autoscale import Autoscaler

        autoscaler = Autoscaler(
            min_replicas=1,
            max_replicas=max(args.autoscale_max, args.fleet))
    fleet = Fleet(
        args.model, n_replicas=args.fleet,
        chain_dir=args.checkpoint_dir, work_dir=work_dir,
        journal=journal, buckets=args.buckets,
        latency_budget_ms=args.latency_budget_ms,
        reload_poll_s=args.reload_poll_s,
        obs_root=obs_root,
        autoscaler=autoscaler)
    fleet.start()
    admission = (AdmissionController(args.classes)
                 if args.classes else AdmissionController())
    door = FrontDoor(fleet, admission=admission,
                     port=args.frontdoor_port or 0,
                     journal=journal,
                     trace_sample=getattr(args, "trace_sample",
                                          1.0)).start()
    print(json.dumps({"frontdoor": {
        "url": door.url, "replicas": args.fleet,
        "work_dir": work_dir,
        "classes": [dataclasses.asdict(c)
                    for c in admission.classes],
    }}), flush=True)

    stop = _threading.Event()
    for sig in (_signal.SIGINT, _signal.SIGTERM):
        _signal.signal(sig, lambda *_: stop.set())
    try:
        if args.serve_seconds > 0:
            stop.wait(args.serve_seconds)
        else:
            while not stop.wait(0.5):
                pass
    finally:
        stats = door.stats()
        health = fleet.healthz()
        door.stop()
    summary = {
        "frontdoor": stats,
        "fleet": {k: health[k] for k in
                  ("ready", "n_replicas", "capacity")},
        "replicas": health["replicas"],
    }
    if fleet.autoscaler is not None:
        summary["autoscale"] = fleet.autoscaler.summary()
    print(json.dumps({"serve_summary": summary}), flush=True)
    if obs.enabled():
        obs.export_snapshot()
        print(json.dumps({
            "run_doctor": f"python tools/run_doctor.py {obs.run_dir()}",
        }), flush=True)
    return 0


def cmd_serve(args) -> int:
    """Online serving loop (ISSUE 12): the AOT micro-batched engine +
    hot reload from the checkpoint chain, driven by a bounded request
    stream (the same dataset plumbing as predict). Emits one summary
    JSON line: request-latency percentiles, QPS, swap/reload and
    staleness accounting."""
    import time as _time

    from fm_spark_tpu import models, obs
    from fm_spark_tpu.resilience import watchdog
    from fm_spark_tpu.utils import compile_cache
    from fm_spark_tpu.utils.logging import EventLog

    cache_dir = compile_cache.enable()
    if args.fleet > 0:
        from fm_spark_tpu.serve.fleet import refuse_on_tpu

        refuse_on_tpu(f"cli serve --fleet {args.fleet}")
    _announce_device(cache_dir)

    _obs_dir = getattr(args, "obs_dir", None)
    if _obs_dir and _obs_dir.lower() != "none":
        import os as _os_obs

        _obs_run = obs.new_run_id()
        obs.configure(_os_obs.path.join(_obs_dir, _obs_run),
                      run_id=_obs_run, install_signals=True)
        # Deep captures (ISSUE 14): an SLO overrun / sentinel
        # regression fires a bounded capture bundle into this run dir.
        from fm_spark_tpu.obs import introspect as _introspect

        _introspect.configure(obs.run_dir(), run_id=_obs_run)
        print(json.dumps({"run_id": _obs_run, "obs_dir": obs.run_dir()}),
              flush=True)
    _start_metrics_endpoint(args)

    if args.slo_ms is not None:
        # Deadline = the SLO: an overrun becomes a structured
        # HangDetected + flight dump instead of a silent tail blowup.
        # An env-configured watchdog (subprocess drills) wins.
        if not watchdog.active():
            watchdog.configure({"serve_request": args.slo_ms / 1e3},
                               action="raise")

    buckets = tuple(sorted({int(b) for b in args.buckets.split(",")
                            if b}))
    if not buckets:
        raise SystemExit(f"--buckets parsed empty from {args.buckets!r}")

    cfg = None
    if args.config is not None:
        from fm_spark_tpu import configs as configs_lib

        # --optimizer names the TRAINER's rule for the followed chain
        # (an --online ftrl chain checkpoints FtrlState; restoring it
        # needs the matching opt-state structure).
        cfg = configs_lib.get_config(args.config,
                                     optimizer=args.optimizer)

    import os as _os

    # The serving journal lands in the run's OWN obs directory, never
    # in the trainer's chain directory: a serving reader must not
    # write into (or even create) the chain it follows — the same
    # contract ChainFollower keeps, and what lets many followers
    # share one chain without contending on a journal file. With the
    # obs plane off there is no journal; swaps/failures still show in
    # the metrics registry and the summary line.
    journal = None
    if obs.run_dir():
        journal = EventLog(
            _os.path.join(obs.run_dir(), "serve_health.jsonl"),
            mirror_to_flight=True)

    if args.fleet > 0:
        return _serve_fleet(args, journal)

    step0 = 0
    opt_example = None  # built once; FieldDeepFM's costs a full init
    if args.model:
        spec, params = models.load_model(args.model)
    else:
        # Serve straight off the trainer's chain: the initial
        # generation is the newest verified step, read through the
        # SAME read-only follower the hot-reload path polls.
        if not (args.checkpoint_dir and cfg is not None):
            raise SystemExit(
                "serve needs --model DIR, or --checkpoint-dir with "
                "--config to follow a training chain"
            )
        import jax as _jax_s

        from fm_spark_tpu.checkpoint import ChainFollower

        spec = cfg.spec()
        init_params = spec.init(_jax_s.random.key(cfg.seed))
        opt_example = _serve_opt_example(spec, cfg)
        chain = ChainFollower(args.checkpoint_dir, journal=journal)
        restored = chain.restore(init_params, opt_example)
        chain.close()
        if restored is None:
            raise SystemExit(
                f"no verified checkpoint to serve under "
                f"{args.checkpoint_dir} (the follower trusts only "
                "manifest-verified steps)"
            )
        params, step0 = restored["params"], restored["step"]

    from fm_spark_tpu.serve import PredictEngine, ReloadFollower

    engine = None
    follower = None
    out = None
    if args.out:
        out = sys.stdout if args.out == "-" else open(args.out, "w")
    n_requests = 0
    n_rows = 0
    t_serve0 = _time.perf_counter()
    try:
        for _pass in range(max(args.repeat, 1)):
            for bids, bvals, _, w in _batches_for_model(args, spec):
                if engine is None:
                    engine = PredictEngine(
                        spec, params, nnz=bids.shape[1], step=step0,
                        buckets=buckets,
                        latency_budget_ms=args.latency_budget_ms,
                        journal=journal,
                    )
                    wstats = engine.warmup()
                    print(json.dumps({
                        "serving": True, "step": step0,
                        "buckets": list(buckets),
                        "warmup_s": wstats["seconds"],
                        "fresh_compiles": wstats["fresh_compiles"],
                    }), flush=True)
                    if args.checkpoint_dir and args.reload_poll_s > 0:
                        if opt_example is None:
                            opt_example = _serve_opt_example(spec, cfg)
                        follower = ReloadFollower(
                            engine, args.checkpoint_dir,
                            poll_s=args.reload_poll_s, journal=journal,
                            opt_state_example=opt_example,
                        ).start()
                preds = engine.predict(bids, bvals)
                if out is not None:
                    for p in preds[w > 0]:
                        out.write(f"{float(p):.6g}\n")
                n_requests += 1
                n_rows += int((w > 0).sum())
                if args.max_requests and n_requests >= args.max_requests:
                    break
            else:
                continue
            break
    finally:
        if follower is not None:
            follower.stop()
        if engine is not None:
            engine.close()
        if out is not None and out is not sys.stdout:
            out.close()
    elapsed = _time.perf_counter() - t_serve0
    req_hist = obs.registry().histogram("serve/request_ms").summary()
    summary = {
        "served_requests": n_requests,
        "served_rows": n_rows,
        "elapsed_s": round(elapsed, 3),
        "qps": round(n_requests / elapsed, 2) if elapsed > 0 else None,
        "request_ms": {k: req_hist[k] for k in
                       ("count", "mean", "p50", "p95", "p99")},
        "generation_step": (engine.generation().step
                            if engine is not None else None),
        "swaps": follower.reloads if follower is not None else 0,
        "reload_failures": (follower.failures
                            if follower is not None else 0),
        "staleness_steps": int(
            obs.registry().gauge("serve/staleness_steps").value or 0),
        "degraded": bool(
            obs.registry().gauge("serve/degraded").value or 0),
    }
    print(json.dumps({"serve_summary": summary}), flush=True)
    if obs.enabled():
        obs.export_snapshot()
        print(json.dumps({
            "run_doctor": f"python tools/run_doctor.py {obs.run_dir()}",
        }), flush=True)
    return 0


def cmd_preprocess(args) -> int:
    import os
    import shutil

    from fm_spark_tpu import configs as configs_lib

    cfg = configs_lib.get_config(args.config)
    if cfg.dataset not in ("criteo", "avazu"):
        raise SystemExit("preprocess supports criteo/avazu configs")
    mod = __import__(
        f"fm_spark_tpu.data.{cfg.dataset}", fromlist=["preprocess"]
    )
    if args.shuffle:
        # Source text streams in raw (often temporal) order; a global
        # external shuffle here is what makes the training-time tail
        # holdout (--test-fraction) a random split rather than "the last
        # day of Criteo". One-time cost at preprocess, never in the hot
        # path.
        from fm_spark_tpu.data import shuffle_packed

        tmp = args.out_dir.rstrip("/") + ".unshuffled.tmp"
        stats = mod.preprocess(args.input, tmp, cfg.bucket)
        # remove_src drops the unshuffled copy as soon as its rows are
        # dealt — peak scratch ~2x the dataset, not 3x.
        shuffle_packed(tmp, args.out_dir, seed=cfg.seed, remove_src=True)
        if os.path.isdir(tmp):
            shutil.rmtree(tmp)
    else:
        stats = mod.preprocess(args.input, args.out_dir, cfg.bucket)
    print(json.dumps({"out_dir": args.out_dir, "num_examples": stats,
                      "shuffled": bool(args.shuffle)}))
    return 0


def cmd_cap_advise(args) -> int:
    """Recommend a ``--compact-cap`` for a packed dir at a batch size.

    The compact lever's capacity must bound EVERY field's per-batch
    unique-id count (overflow is a crash/poison/degradation per
    ``--compact-overflow``), and a tight cap is measurably faster —
    the round-5 on-chip cap ladder priced ~+1-1.5% per step down
    16384 → 13312 → 12288 at the bench batch (PERF.md). This scans
    real batches the way training would draw them (same chunk-shuffled
    order) and reports the observed per-field max, so operators pick
    caps from measurement instead of folklore."""
    import numpy as np

    from fm_spark_tpu.data import PackedBatches, PackedDataset

    ds = PackedDataset(args.data)
    batches = PackedBatches(ds, args.batch_size, seed=args.seed)
    overall = 0
    per_field_max = np.zeros((ds.num_fields,), np.int64)
    maxima = []
    for _ in range(args.batches):
        ids, _, _, _ = next(batches)
        counts = np.array([
            np.unique(ids[:, f]).size for f in range(ids.shape[1])
        ])
        per_field_max = np.maximum(per_field_max, counts)
        maxima.append(int(counts.max()))
        overall = max(overall, maxima[-1])
    # segtotal's tile (ops/pallas_segsum._TILE) and the aux layouts
    # want a 512 multiple; headroom covers batches not scanned.
    pad = max(64, int(overall * args.headroom))
    recommended = ((overall + pad) + 511) // 512 * 512
    note = ("cap must bound EVERY future batch; rounded to the "
            "segtotal 512 tile with "
            f"{int(args.headroom * 100)}% headroom over the "
            "scanned max — rescan after changing batch size, "
            "hashing, or data distribution")
    if recommended > args.batch_size:
        # A batch of B rows can never contain more than B unique ids,
        # so clamping to batch_size preserves the "bounds EVERY future
        # batch" guarantee unconditionally. Rounding the clamp DOWN to
        # the 512 tile would sacrifice that (a future batch may hold
        # more uniques than the scan observed), so the clamp wins and
        # the note stops claiming tile alignment when the clamp broke
        # it — benign for the Pallas segtotal kernel, which pads B,
        # not cap (ADVICE r5).
        recommended = args.batch_size
        if recommended % 512:
            note = ("cap must bound EVERY future batch; clamped to "
                    "batch_size (a batch's unique count is necessarily "
                    "bounded by it), which is NOT tile-aligned — "
                    "benign for the Pallas segtotal kernel, which "
                    "pads B, not cap — rescan after changing batch "
                    "size, hashing, or data distribution")
        else:
            note = ("cap must bound EVERY future batch; clamped to "
                    "batch_size (a batch's unique count is necessarily "
                    "bounded by it; itself a segtotal 512 tile "
                    "multiple) — rescan after changing batch size, "
                    "hashing, or data distribution")
    print(json.dumps({
        "data": args.data,
        "batch_size": args.batch_size,
        "batches_scanned": args.batches,
        "max_unique_per_field_overall": overall,
        "per_batch_max": maxima,
        "per_field_max": per_field_max.tolist(),
        "recommended_compact_cap": int(recommended),
        "note": note,
    }))
    return 0


def cmd_list_configs(args) -> int:
    from fm_spark_tpu import configs as configs_lib

    for name, cfg in sorted(configs_lib.CONFIGS.items()):
        if args.verbose:
            print(json.dumps(dataclasses.asdict(cfg)))
        else:
            print(f"{name:24s} {cfg.description}")
    return 0


# ----------------------------------------------------------------- parser


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="fm_spark_tpu")
    sub = p.add_subparsers(dest="cmd", required=True)

    def add_data_args(sp):
        sp.add_argument("--data", help="dataset path (see `load_dataset`)")
        sp.add_argument("--synthetic", type=int, metavar="N",
                        help="use N synthetic planted-FM examples")
        sp.add_argument("--batch-size", type=int, default=None)

    t = sub.add_parser("train", help="train a registered config")
    t.add_argument("--config", required=True)
    t.add_argument("--distributed", action="store_true",
                   help="jax.distributed.initialize before training: bare "
                        "flag on a Cloud TPU pod slice (topology "
                        "auto-detected); elsewhere also pass "
                        "--coordinator/--num-processes/--process-id")
    t.add_argument("--coordinator", default=None,
                   help="coordinator host:port (with --distributed)")
    t.add_argument("--num-processes", type=int, default=None,
                   dest="num_processes",
                   help="total process count (with --distributed)")
    t.add_argument("--process-id", type=int, default=None,
                   dest="process_id",
                   help="this process's index (with --distributed)")
    add_data_args(t)
    t.add_argument("--steps", type=int, default=None)
    t.add_argument("--lr", type=float, default=None)
    t.add_argument("--optimizer", default=None)
    t.add_argument("--loss", default=None,
                   choices=["logistic", "squared", "hinge"],
                   help="override the config's loss (task compatibility "
                        "is validated at spec construction)")
    t.add_argument("--strategy", default=None,
                   choices=["single", "field_sparse", "dp", "row"])
    t.add_argument("--sparse-update", default=None, dest="sparse_update",
                   choices=["scatter_add", "dedup", "dedup_sr"],
                   help="row-write strategy for the fused sparse steps "
                        "(dedup_sr = the bf16 quality fix, see PERF.md)")
    t.add_argument("--param-dtype", default=None, dest="param_dtype",
                   choices=["float32", "bfloat16"],
                   help="table storage dtype (bfloat16 halves gather bytes; "
                        "pair with --sparse-update dedup_sr)")
    t.add_argument("--compute-dtype", default=None, dest="compute_dtype",
                   choices=["float32", "bfloat16"],
                   help="forward/backward buffer dtype for the [B, w] "
                        "passes (storage stays --param-dtype; reductions "
                        "and the compact cumsum stay fp32 — the measured "
                        "+6%% lever, quality pinned in QUALITY.md)")
    t.add_argument("--use-pallas", action="store_true", dest="use_pallas",
                   help="route fused-step row gather/update through the "
                        "Pallas pipelined-DMA kernels (TPU; interpret mode "
                        "elsewhere)")
    _add_lever_args(t)
    t.add_argument("--batch-per-chip", type=int, default=None,
                   dest="batch_per_chip",
                   help="WEAK-SCALING batch sizing: global batch = N x "
                        "device_count (per-chip feed constant as the "
                        "mesh grows); exclusive with --batch-size")
    t.add_argument("--seed", type=int, default=None)
    t.add_argument("--row-shards", type=int, default=1, dest="row_shards",
                   help="field_sparse strategy: shard each field's bucket "
                        "dimension over this many chips (2-D feat x row "
                        "mesh; row capacity scale-out)")
    t.add_argument("--ckpt-sharded", action="store_true",
                   dest="ckpt_sharded",
                   help="checkpoint the live sharded arrays (each process "
                        "writes its shards; no host gather). Resumes only "
                        "onto the same mesh; the default canonical layout "
                        "is topology-portable")
    t.add_argument("--steps-per-call", type=int, default=1,
                   dest="steps_per_call",
                   help="roll N steps into one compiled program "
                        "(single-chip FM/FFM field_sparse; amortizes "
                        "per-dispatch overhead, PERF.md fact 1); "
                        "logging/eval/checkpoint round to call boundaries")
    t.add_argument("--prefetch", type=int, default=2,
                   help="background batch read-ahead depth (0 = off); "
                        "overlaps host batch assembly with device compute")
    t.add_argument("--native-ingest", action="store_true",
                   dest="native_ingest",
                   help="parse streaming raw-text shards with the C++ "
                        "chunk parser (ISSUE 6): same exactly-once "
                        "cursor, quarantine semantics, and record "
                        "stream as the per-line Python path, at native "
                        "rate; falls back to the Python parser "
                        "automatically when the library cannot be built")
    t.add_argument("--data-policy", default="strict", dest="data_policy",
                   choices=["strict", "quarantine"],
                   help="per-record error policy for raw-text ingest "
                        "(ISSUE 5): strict = first malformed/out-of-"
                        "contract record raises with path:lineno "
                        "context; quarantine = bad records land in "
                        "<quarantine-dir>/deadletter.jsonl and "
                        "training continues")
    t.add_argument("--quarantine-dir", dest="quarantine_dir",
                   help="dead-letter directory for --data-policy "
                        "quarantine (one JSONL record per bad line: "
                        "path, lineno, reason, repr-escaped preview)")
    t.add_argument("--max-bad-frac", type=float, default=1.0,
                   dest="max_bad_frac", metavar="FRAC",
                   help="bad-record-rate circuit breaker (quarantine "
                        "policy): abort the run when more than FRAC of "
                        "a trailing record window is bad — a truncated "
                        "or garbage shard must never silently train as "
                        "noise (1.0 = never abort)")
    t.add_argument("--test-fraction", type=float, default=0.2)
    t.add_argument("--log-every", type=int, default=100)
    t.add_argument("--eval-every", type=int, default=0,
                   help="run held-out eval every N steps during training "
                        "(single strategy; needs --test-fraction > 0)")
    t.add_argument("--metrics", help="JSONL metrics file")
    t.add_argument("--model-out", help="directory to save the final model")
    t.add_argument("--checkpoint-dir", help="orbax checkpoint directory")
    t.add_argument("--checkpoint-every", type=int, default=1000)
    t.add_argument("--supervise", action="store_true",
                   help="wrap single-strategy training in the device-"
                        "fault supervisor (resilience/): a mid-run "
                        "device loss probes the attachment, backs off "
                        "with bounded exponential delay, and resumes "
                        "from the latest checkpoint with loss "
                        "continuity; health events land in "
                        "<checkpoint-dir>/health.jsonl. Requires "
                        "--checkpoint-dir")
    t.add_argument("--elastic", action="store_true",
                   help="elastic degraded mode (resilience/elastic.py): "
                        "N identical consecutive device losses are "
                        "classified PERMANENT and the run sheds "
                        "capacity — mesh rebuilt from the surviving "
                        "half (8>4>2>1), last good checkpoint restored "
                        "onto it, per-chip metrics re-normalized — "
                        "instead of dying. Strategies: field_sparse, "
                        "or single with --supervise. Requires "
                        "--checkpoint-dir; exclusive with "
                        "--ckpt-sharded")
    t.add_argument("--max-shrinks", type=int, default=3,
                   dest="max_shrinks",
                   help="with --elastic: how many times the device set "
                        "may halve before a permanent fault propagates "
                        "(3 = an 8-chip mesh degrades down to 1)")
    t.add_argument("--divergence-guard", type=float, nargs="?",
                   const=10.0, default=None, dest="divergence_guard",
                   metavar="FACTOR",
                   help="opt-in divergence guard (strategy single, "
                        "requires --checkpoint-dir): NaN/Inf loss or a "
                        "loss > FACTOR x the trailing median (bare "
                        "flag: 10x) rolls back to the last good "
                        "checkpoint and resumes with a reduced step "
                        "budget — a numeric blowup costs one "
                        "checkpoint window, not the run. Costs one "
                        "loss fetch per step")
    t.add_argument("--online", action="store_true",
                   help="continuous-learning protocol (ISSUE 13; "
                        "strategy single, requires --checkpoint-dir): "
                        "train day N, evaluate streamed AUC on the "
                        "never-seen day N+1, checkpoint per day, and "
                        "run the concept-drift sentry over the AUC "
                        "series — a drift verdict DEMOTES the "
                        "offending day's saves (durable tombstones; "
                        "last_good republished at the pre-drift save) "
                        "and rolls the weights back, so a serving "
                        "follower can never hot-load the bad "
                        "generation. Days come from --data d0,d1,... "
                        "(one text shard per day) or --synthetic N "
                        "with --online-days")
    t.add_argument("--online-days", type=int, default=8,
                   dest="online_days",
                   help="with --online --synthetic: split the "
                        "synthetic set into this many time-ordered "
                        "day slices")
    t.add_argument("--drift-drop-factor", type=float, default=1.15,
                   dest="drift_drop_factor", metavar="FACTOR",
                   help="drift sentry threshold: eval AUC below "
                        "trailing-median / FACTOR is a drift verdict "
                        "(maximize-mode DivergenceGuard; min-history "
                        "floor keeps short series from tripping it)")
    t.add_argument("--drift-max-rollbacks", type=int, default=2,
                   dest="drift_max_rollbacks",
                   help="how many drift rollbacks the online run "
                        "absorbs before the verdict propagates "
                        "(persistent drift is a data/model problem "
                        "the operator must see)")
    t.add_argument("--drift-inject", type=int, default=None,
                   dest="drift_inject", metavar="DAY",
                   help="DRILL LEVER: flip the labels of every "
                        "synthetic day >= DAY (a planted concept "
                        "drift), to exercise the sentry/rollback path "
                        "end-to-end — the online analog of the chaos "
                        "canary")
    t.add_argument("--quality-ledger", dest="quality_ledger",
                   default=None, metavar="PATH",
                   help="append one quality_eval record per online "
                        "eval day to this perf-ledger JSONL (own "
                        "sentinel cohorts, isolated from bench legs "
                        "by leg namespace); default: off")
    import os as _os_parser

    t.add_argument("--obs-dir", dest="obs_dir",
                   default=_os_parser.environ.get("FM_SPARK_OBS_DIR",
                                                  "artifacts/obs"),
                   help="telemetry root (ISSUE 7): span traces, metrics "
                        "snapshots, and the crash flight recorder land "
                        "under <obs-dir>/<run_id>/ (the run_id is "
                        "echoed as the first JSON line); 'none' "
                        "disables the plane entirely. Default "
                        "overridable via FM_SPARK_OBS_DIR — the test "
                        "harness sets it to 'none' so hundreds of "
                        "in-process train calls don't each open a run "
                        "directory")
    t.add_argument("--metrics-port", type=int, default=None,
                   dest="metrics_port", metavar="PORT",
                   help="serve the live metrics registry over stdlib "
                        "HTTP on 127.0.0.1:PORT (0 = OS-assigned; the "
                        "bound port is echoed as a JSON line): "
                        "/metrics is the Prometheus text dump, "
                        "/healthz a JSON liveness doc (run_id, "
                        "generation, staleness, breaker state, last "
                        "sentinel verdict) — a long-running loop is "
                        "inspectable without touching the process")
    t.add_argument("--force", action="store_true",
                   help="override safety guardrails (currently: the "
                        "strategy=row >=1M-feature check) with a "
                        "warning instead of an error")
    t.add_argument("--profile", metavar="DIR",
                   help="write a jax.profiler trace for the run")
    t.set_defaults(fn=cmd_train)

    e = sub.add_parser("eval", help="evaluate a saved model")
    e.add_argument("--model", required=True)
    e.add_argument("--config", help="config naming the dataset loader")
    add_data_args(e)
    e.set_defaults(fn=cmd_eval, batch_size=8192)

    pr = sub.add_parser("predict", help="write predictions for a dataset")
    pr.add_argument("--model", required=True)
    pr.add_argument("--config", help="config naming the dataset loader")
    add_data_args(pr)
    pr.add_argument("--out", help="output file ('-' = stdout)")
    pr.set_defaults(fn=cmd_predict, batch_size=8192)

    sv = sub.add_parser(
        "serve",
        help="online serving: AOT micro-batched predict engine with "
             "hot reload from a checkpoint chain (ISSUE 12)",
    )
    sv.add_argument("--model", help="saved model dir (models.io format)")
    sv.add_argument("--config",
                    help="config naming the dataset loader / the "
                         "chain's model family (required with "
                         "--checkpoint-dir and no --model)")
    sv.add_argument("--optimizer", default=None,
                    help="the TRAINER's optimizer for the followed "
                         "chain (when it differs from the config's "
                         "default, e.g. an --online ftrl chain): the "
                         "follower must rebuild the same opt-state "
                         "structure to restore the checkpoints")
    add_data_args(sv)
    sv.add_argument("--checkpoint-dir", dest="checkpoint_dir",
                    help="training chain to follow: the initial "
                         "generation is the newest verified step, and "
                         "with --reload-poll-s > 0 new last_good "
                         "publishes hot-swap in")
    sv.add_argument("--latency-budget-ms", type=float, default=2.0,
                    dest="latency_budget_ms",
                    help="how long the coalescer may hold a request "
                         "waiting for micro-batch peers (0 = dispatch "
                         "immediately)")
    sv.add_argument("--buckets", default="1,8,64,512",
                    help="comma-separated padded-batch buckets; every "
                         "dispatch pads to one of these shapes, so a "
                         "warm process never compiles on the request "
                         "path")
    sv.add_argument("--reload-poll-s", type=float, default=2.0,
                    dest="reload_poll_s",
                    help="how often the follower polls last_good.json "
                         "(0 = no hot reload)")
    sv.add_argument("--slo-ms", type=float, default=None, dest="slo_ms",
                    help="arm the serve_request watchdog phase at this "
                         "deadline: an overrun becomes a structured "
                         "HangDetected + flight dump")
    sv.add_argument("--fleet", type=int, default=0,
                    help="production front door (ISSUE 17): run N "
                         "replica processes behind one HTTP front "
                         "door with deadline-aware admission control "
                         "(requires --model; --checkpoint-dir adds "
                         "per-replica hot reload)")
    sv.add_argument("--autoscale-max", type=int, default=0,
                    dest="autoscale_max", metavar="N",
                    help="with --fleet: enable the bidirectional "
                         "autoscaler (ISSUE 19) with this replica "
                         "ceiling — grows on sustained front-door "
                         "shed, parks idle replicas on low coalescer "
                         "fill; decisions journal as "
                         "autoscale_decision events (default 0 = "
                         "fixed-size fleet)")
    sv.add_argument("--frontdoor-port", type=int, default=0,
                    dest="frontdoor_port", metavar="PORT",
                    help="front door listen port (default: ephemeral, "
                         "printed at startup)")
    sv.add_argument("--classes", default=None,
                    help="admission classes as "
                         "'name:queue_cap:deadline_ms,...' in "
                         "priority order (default: "
                         "interactive:64:500,batch:64:2000,"
                         "background:32:8000)")
    sv.add_argument("--serve-seconds", type=float, default=0.0,
                    dest="serve_seconds",
                    help="with --fleet: serve for this long then "
                         "exit cleanly (default 0 = until "
                         "SIGINT/SIGTERM)")
    sv.add_argument("--trace-sample", type=float, default=1.0,
                    dest="trace_sample", metavar="FRAC",
                    help="fraction of accepted requests that get a "
                         "distributed trace (ISSUE 18; default 1.0 — "
                         "production fleets at high QPS should sample, "
                         "e.g. 0.01: spans cost one JSONL write per "
                         "hop)")
    sv.add_argument("--repeat", type=int, default=1,
                    help="passes over the request stream (reload drills "
                         "keep serving while a trainer advances the "
                         "chain)")
    sv.add_argument("--max-requests", type=int, default=0,
                    dest="max_requests",
                    help="stop after N requests (0 = the full stream)")
    sv.add_argument("--out",
                    help="write predictions here ('-' = stdout; "
                         "default: measured, not dumped)")
    import os as _os_sv

    sv.add_argument("--obs-dir", dest="obs_dir",
                    default=_os_sv.environ.get("FM_SPARK_OBS_DIR",
                                               "artifacts/obs"),
                    help="telemetry root (same convention as train); "
                         "'none' disables")
    sv.add_argument("--metrics-port", type=int, default=None,
                    dest="metrics_port", metavar="PORT",
                    help="live-metrics endpoint (same contract as "
                         "train --metrics-port): /metrics Prometheus "
                         "text + /healthz JSON with generation/"
                         "staleness/breaker/last-verdict, served from "
                         "a daemon thread off the request path")
    sv.set_defaults(fn=cmd_serve, batch_size=256)

    pp = sub.add_parser("preprocess",
                        help="hash raw criteo/avazu text → packed binary")
    pp.add_argument("--config", required=True)
    pp.add_argument("--input", required=True, nargs="+")
    pp.add_argument("--out-dir", required=True)
    pp.add_argument("--no-shuffle", dest="shuffle", action="store_false",
                    help="keep raw source order (tail holdouts become "
                         "temporal splits — see train --test-fraction)")
    pp.set_defaults(fn=cmd_preprocess, shuffle=True)

    ca = sub.add_parser(
        "cap-advise",
        help="scan a packed dir and recommend a --compact-cap "
             "(bounds the per-field per-batch unique-id count)",
    )
    ca.add_argument("--data", required=True, help="packed dir")
    ca.add_argument("--batch-size", type=int, required=True,
                    help="the training batch size the cap must serve")
    ca.add_argument("--batches", type=int, default=20,
                    help="batches to scan (chunk-shuffled, like training)")
    ca.add_argument("--seed", type=int, default=0)
    ca.add_argument("--headroom", type=float, default=0.10,
                    help="fractional headroom over the scanned max "
                         "before rounding up to the 512 tile")
    ca.set_defaults(fn=cmd_cap_advise)

    lc = sub.add_parser("list-configs", help="show registered configs")
    lc.add_argument("--verbose", action="store_true")
    lc.set_defaults(fn=cmd_list_configs)
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    try:
        return args.fn(args)
    finally:
        # Clean-run flush for the telemetry plane (no-op when the
        # command never configured it): the final metrics snapshot and
        # flight dump land even when a command exits via SystemExit.
        # The live endpoint stops first — a scrape racing shutdown must
        # read a consistent registry, not a half-flushed one — and
        # obs.shutdown also disarms the capture engine.
        from fm_spark_tpu import obs
        from fm_spark_tpu.obs import export as _obs_export

        _obs_export.stop_metrics_server()
        obs.shutdown()


if __name__ == "__main__":
    sys.exit(main())
