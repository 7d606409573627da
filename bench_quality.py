"""Quality-parity protocol: committed, reproducible AUC envelope.

BASELINE.md's quality bar is "AUC within 1e-3 of the Spark CPU baseline"
on config 1 (MovieLens-100K). Neither the reference implementation nor
real MovieLens/Criteo data exists in this image (SURVEY.md §0), so the
committed stand-in oracle chain is:

  numpy float64 full-batch SGD  (this file — independent of JAX; the
        reference's runMiniBatchSGD semantics, SURVEY.md §3.1)
    ⇕  budget 5e-3: different implementation, RNG stream, and init —
       this rung checks the IMPLEMENTATION, not bitwise numerics
    ⇕  the same exact rank-sum AUC is applied to both sides
  fm_spark_tpu fp32 fused step  (the shipped path)
    ⇕  budget 1e-3 (the BASELINE-style bar): same code path, same
       batches — only the numeric shortcut under test differs
  every numeric variant         (bf16+dedup_sr, host_dedup, dedup, ...)

Run `python bench_quality.py` (CPU or TPU); it prints one JSON line per
variant plus a `pass` verdict per comparison. `--model ffm|deepfm`
runs the same protocol against model-matched float64 oracles (the
field-aware pairwise term; a hand-written relu-MLP forward/backward) —
VERDICT r2 #5. QUALITY.md records the committed numbers from this
exact script. The planted-FM task (data/synthetic.py) is fully
deterministic from its seed, so drift in any committed number is a
regression signal, not noise.
"""

import argparse
import json
import sys

import numpy as np

TASK = dict(n=20_000, num_fields=8, bucket=128, rank=8, planted_rank=4,
            seed=7)
TRAIN = dict(steps=1500, batch=512, lr=0.15)
# DeepFM quality task: small relu stack over the shared embedding; the
# oracle replicates exactly this architecture in numpy float64.
MLP_DIMS = (32, 32)


def _log(msg):
    print(f"bench_quality: {msg}", file=sys.stderr, flush=True)


def _data():
    from fm_spark_tpu.data import synthetic_ctr, train_test_split

    ids, vals, labels = synthetic_ctr(
        TASK["n"], TASK["num_fields"] * TASK["bucket"], TASK["num_fields"],
        rank=TASK["planted_rank"], seed=TASK["seed"],
    )
    offs = (np.arange(TASK["num_fields"]) * TASK["bucket"]).astype(np.int32)
    return train_test_split(ids - offs[None, :], vals, labels, 0.25,
                            seed=TASK["seed"])


def _auc(scores, labels):
    """Exact rank-sum AUC with tie-averaged (mid) ranks — the SAME metric
    is applied to the oracle and to every framework variant so the deltas
    measure numerics, not metric definition (the framework's streaming
    histogram AUC is deliberately NOT used here)."""
    scores = np.asarray(scores, np.float64)
    order = np.argsort(scores, kind="stable")
    s = scores[order]
    ranks_sorted = np.arange(1, len(s) + 1, dtype=np.float64)
    # Average ranks within tied runs.
    boundary = np.concatenate([[True], s[1:] != s[:-1]])
    grp = np.cumsum(boundary) - 1
    sums = np.bincount(grp, weights=ranks_sorted)
    cnts = np.bincount(grp)
    ranks = np.empty(len(s), np.float64)
    ranks[order] = (sums / cnts)[grp]
    pos = np.asarray(labels) > 0.5
    n_pos, n_neg = pos.sum(), (~pos).sum()
    if n_pos == 0 or n_neg == 0:
        return float("nan")
    return float(
        (ranks[pos].sum() - n_pos * (n_pos + 1) / 2) / (n_pos * n_neg)
    )


def numpy_float64_oracle(tr, te):
    """Minibatch SGD on the FM identity in float64 numpy — an
    implementation with no JAX, no fused step, no scatter tricks: the
    independent oracle the fp32 path is judged against."""
    rng = np.random.default_rng(TASK["seed"])
    F, bucket, k = TASK["num_fields"], TASK["bucket"], TASK["rank"]
    n_rows = F * bucket
    v = rng.normal(0, 0.05, size=(n_rows, k)).astype(np.float64)
    w = np.zeros(n_rows, np.float64)
    w0 = 0.0
    ids_tr, vals_tr, y_tr = (np.asarray(a) for a in tr)
    gids = ids_tr + (np.arange(F) * bucket)[None, :]
    n = len(y_tr)
    order = rng.permutation(n)
    lr, B = TRAIN["lr"], TRAIN["batch"]
    pos = 0
    for step in range(TRAIN["steps"]):
        if pos + B > n:
            order = rng.permutation(n)
            pos = 0
        sel = order[pos: pos + B]
        pos += B
        bi, bx, by = gids[sel], vals_tr[sel].astype(np.float64), y_tr[sel]
        rows = v[bi]                                   # [B, F, k]
        xv = rows * bx[..., None]
        s = xv.sum(axis=1)                             # [B, k]
        scores = (w0 + (w[bi] * bx).sum(axis=1)
                  + 0.5 * ((s * s).sum(axis=1) - (xv * xv).sum(axis=(1, 2))))
        p = 1.0 / (1.0 + np.exp(-scores))
        d = (p - by) / B                               # dL/dscore
        g_rows = d[:, None, None] * bx[..., None] * (s[:, None, :] - xv)
        np.add.at(v, bi, -lr * g_rows)
        np.add.at(w, bi, -lr * (d[:, None] * bx))
        w0 -= lr * d.sum()
    ids_te, vals_te, y_te = (np.asarray(a) for a in te)
    gte = ids_te + (np.arange(F) * bucket)[None, :]
    rows = v[gte]
    xv = rows * vals_te[..., None].astype(np.float64)
    s = xv.sum(axis=1)
    scores = (w0 + (w[gte] * vals_te).sum(axis=1)
              + 0.5 * ((s * s).sum(axis=1) - (xv * xv).sum(axis=(1, 2))))
    return _auc(scores, y_te)


def numpy_float64_oracle_ffm(tr, te):
    """Minibatch SGD on the FIELD-AWARE interaction in float64 numpy —
    the FFM analog of :func:`numpy_float64_oracle` (VERDICT r2 #5):
    ``½ Σ_{i≠j} ⟨v[id_i, field j], v[id_j, field i]⟩ x_i x_j`` plus the
    linear/bias terms, no JAX anywhere."""
    rng = np.random.default_rng(TASK["seed"])
    F, bucket, k = TASK["num_fields"], TASK["bucket"], TASK["rank"]
    n_rows = F * bucket
    v = rng.normal(0, 0.05, size=(n_rows, F, k)).astype(np.float64)
    w = np.zeros(n_rows, np.float64)
    w0 = 0.0
    ids_tr, vals_tr, y_tr = (np.asarray(a) for a in tr)
    offs = (np.arange(F) * bucket)[None, :]
    gids = ids_tr + offs
    n = len(y_tr)
    order = rng.permutation(n)
    lr, B = TRAIN["lr"], TRAIN["batch"]
    eye = np.eye(F, dtype=np.float64)[None, :, :, None]

    def ffm_scores(bi, bx, vv, ww, b0):
        sel = vv[bi] * bx[..., None, None]          # [B, F(i), F(j), k]
        a = np.einsum("bijk,bjik->bij", sel, sel)
        diag = np.trace(a, axis1=1, axis2=2)
        return (b0 + (ww[bi] * bx).sum(axis=1)
                + 0.5 * (a.sum(axis=(1, 2)) - diag)), sel

    pos = 0
    for step in range(TRAIN["steps"]):
        if pos + B > n:
            order = rng.permutation(n)
            pos = 0
        sel_idx = order[pos: pos + B]
        pos += B
        bi, bx = gids[sel_idx], vals_tr[sel_idx].astype(np.float64)
        by = y_tr[sel_idx]
        scores, sel = ffm_scores(bi, bx, v, w, w0)
        p = 1.0 / (1.0 + np.exp(-scores))
        d = (p - by) / B
        # dsel[b,i,j] = d · sel[b,j,i], zero diagonal; dv = dsel · x_i.
        dsel = d[:, None, None, None] * np.swapaxes(sel, 1, 2) * (1.0 - eye)
        np.add.at(v, bi, -lr * dsel * bx[..., None, None])
        np.add.at(w, bi, -lr * (d[:, None] * bx))
        w0 -= lr * d.sum()
    ids_te, vals_te, y_te = (np.asarray(a) for a in te)
    scores, _ = ffm_scores(ids_te + offs, vals_te.astype(np.float64), v,
                           w, w0)
    return _auc(scores, y_te)


def numpy_float64_oracle_deepfm(tr, te):
    """Minibatch SGD on DeepFM (shared-embedding FM + relu MLP head) in
    float64 numpy — same architecture as FieldDeepFMSpec with
    ``mlp_dims=MLP_DIMS``, every parameter updated by plain SGD (the
    framework rung below uses optimizer='sgd' to match)."""
    rng = np.random.default_rng(TASK["seed"])
    F, bucket, k = TASK["num_fields"], TASK["bucket"], TASK["rank"]
    n_rows = F * bucket
    v = rng.normal(0, 0.05, size=(n_rows, k)).astype(np.float64)
    w = np.zeros(n_rows, np.float64)
    w0 = 0.0
    dims = (F * k, *MLP_DIMS, 1)
    layers = []
    for d_in, d_out in zip(dims[:-1], dims[1:]):
        layers.append([
            rng.normal(0, np.sqrt(2.0 / d_in),
                       size=(d_in, d_out)).astype(np.float64),
            np.zeros(d_out, np.float64),
        ])
    ids_tr, vals_tr, y_tr = (np.asarray(a) for a in tr)
    offs = (np.arange(F) * bucket)[None, :]
    gids = ids_tr + offs
    n = len(y_tr)
    order = rng.permutation(n)
    lr, B = TRAIN["lr"], TRAIN["batch"]

    def forward(bi, bx, train=True):
        rows = v[bi]
        xv = rows * bx[..., None]                      # [B, F, k]
        s = xv.sum(axis=1)
        fm = (w0 + (w[bi] * bx).sum(axis=1)
              + 0.5 * ((s * s).sum(axis=1) - (xv * xv).sum(axis=(1, 2))))
        h = xv.reshape(len(bi), F * k)
        acts = [h]
        a = h
        for li, (kern, bias) in enumerate(layers):
            a = a @ kern + bias
            if li < len(MLP_DIMS):
                a = np.maximum(a, 0.0)
            acts.append(a)
        return fm + a[:, 0], xv, s, acts

    pos = 0
    for step in range(TRAIN["steps"]):
        if pos + B > n:
            order = rng.permutation(n)
            pos = 0
        sel = order[pos: pos + B]
        pos += B
        bi, bx, by = gids[sel], vals_tr[sel].astype(np.float64), y_tr[sel]
        scores, xv, s, acts = forward(bi, bx)
        p = 1.0 / (1.0 + np.exp(-scores))
        d = (p - by) / B
        # MLP backward (relu stack), collecting the pullback to h.
        g = d[:, None]                                # d wrt last act
        grads = []
        for li in range(len(layers) - 1, -1, -1):
            kern, bias = layers[li]
            a_in = acts[li]
            grads.append((a_in.T @ g, g.sum(axis=0)))
            g = g @ kern.T
            if li > 0:
                g = g * (acts[li] > 0)                # relu mask
        g_h = g.reshape(len(bi), F, k)
        for li, (gk, gb) in enumerate(reversed(grads)):
            layers[li][0] -= lr * gk
            layers[li][1] -= lr * gb
        g_rows = (d[:, None, None] * bx[..., None] * (s[:, None, :] - xv)
                  + g_h * bx[..., None])
        np.add.at(v, bi, -lr * g_rows)
        np.add.at(w, bi, -lr * (d[:, None] * bx))
        w0 -= lr * d.sum()
    ids_te, vals_te, y_te = (np.asarray(a) for a in te)
    scores, _, _, _ = forward(ids_te + offs, vals_te.astype(np.float64))
    return _auc(scores, y_te)


def framework_variant(tr, te, model="fm", param_dtype="float32",
                      sparse_update="scatter_add", host_dedup=False,
                      compact_cap=0, compute_dtype="float32",
                      compact_device=False, sharded=False,
                      collective_dtype="float32", score_sharded=False,
                      deep_sharded=False):
    import jax
    import jax.numpy as jnp

    from fm_spark_tpu import models
    from fm_spark_tpu.data import Batches, DedupAuxBatches
    from fm_spark_tpu.sparse import (
        make_field_deepfm_sparse_step,
        make_field_ffm_sparse_sgd_step,
        make_field_sparse_sgd_step,
    )
    from fm_spark_tpu.train import TrainConfig

    common = dict(
        num_features=TASK["num_fields"] * TASK["bucket"],
        rank=TASK["rank"], num_fields=TASK["num_fields"],
        bucket=TASK["bucket"], init_std=0.05, param_dtype=param_dtype,
        compute_dtype=compute_dtype,
    )
    config = TrainConfig(
        learning_rate=TRAIN["lr"], lr_schedule="constant", optimizer="sgd",
        sparse_update=sparse_update, host_dedup=host_dedup,
        compact_cap=compact_cap, compact_device=compact_device,
        seed=TASK["seed"], collective_dtype=collective_dtype,
        score_sharded=score_sharded, deep_sharded=deep_sharded,
    )
    opt = None
    if sharded:
        # The wire-precision rows (collective_dtype / score_sharded /
        # deep_sharded) exist only on the sharded steps — run them on
        # every available device (the 8-fake-device CPU mesh in CI; a
        # real slice on hardware). All three families (round 5: FFM
        # budgets the sel-a2a wire dtype — the step's dominant ICI term
        # — and DeepFM the example-sharded head).
        from fm_spark_tpu.parallel import (
            make_field_ffm_sharded_step,
            make_field_mesh,
            make_field_sharded_sgd_step,
            pad_field_batch,
            shard_field_batch,
            shard_field_params,
            stack_field_params,
            unstack_field_params,
        )
        from fm_spark_tpu.parallel.deepfm_step import (
            make_field_deepfm_sharded_step,
            shard_field_deepfm_params,
            stack_field_deepfm_params,
            unstack_field_deepfm_params,
        )

        n = jax.device_count()
        if n < 2:
            raise ValueError(
                "sharded quality rows need >1 device (set "
                "XLA_FLAGS=--xla_force_host_platform_device_count=8)"
            )
        mesh = make_field_mesh(n)
        opt_sh = None
        if model == "fm":
            spec = models.FieldFMSpec(**common)
            step_sh = make_field_sharded_sgd_step(spec, config, mesh)
        elif model == "ffm":
            spec = models.FieldFFMSpec(**common)
            step_sh = make_field_ffm_sharded_step(spec, config, mesh)
        elif model == "deepfm":
            spec = models.FieldDeepFMSpec(**common, mlp_dims=MLP_DIMS)
            step_sh = make_field_deepfm_sharded_step(spec, config, mesh)
        else:
            raise ValueError(f"unknown model {model!r}")
        init = spec.init(jax.random.key(TASK["seed"]))
        if model == "deepfm":
            params = shard_field_deepfm_params(
                stack_field_deepfm_params(spec, init, n), mesh
            )
            opt_sh = step_sh.init_opt_state(params)
        else:
            params = shard_field_params(
                stack_field_params(spec, init, n), mesh
            )
        batches = Batches(*tr, TRAIN["batch"], seed=TASK["seed"])
        nf = TASK["num_fields"]
        for i in range(TRAIN["steps"]):
            b = shard_field_batch(
                pad_field_batch(tuple(batches.next_batch()), nf, n), mesh
            )
            if model == "deepfm":
                params, opt_sh, _ = step_sh(params, opt_sh,
                                            jnp.int32(i), *b)
            else:
                params, _ = step_sh(params, jnp.int32(i), *b)
        host = jax.device_get(params)
        params = (unstack_field_deepfm_params(spec, host)
                  if model == "deepfm"
                  else unstack_field_params(spec, host))
        ids_te, vals_te, y_te = te
        scores = np.asarray(
            spec.scores(params, jnp.asarray(ids_te), jnp.asarray(vals_te)),
            np.float64,
        )
        return _auc(scores, np.asarray(y_te))
    if model == "fm":
        spec = models.FieldFMSpec(**common)
        step = make_field_sparse_sgd_step(spec, config)
    elif model == "ffm":
        spec = models.FieldFFMSpec(**common)
        step = make_field_ffm_sparse_sgd_step(spec, config)
    elif model == "deepfm":
        # optimizer='sgd' keeps the dense head on the same rule as the
        # numpy oracle (config 5's Adam is an optimizer choice, not a
        # numerics variant — this chain isolates numerics).
        spec = models.FieldDeepFMSpec(**common, mlp_dims=MLP_DIMS)
        step = make_field_deepfm_sparse_step(spec, config)
    else:
        raise ValueError(f"unknown model {model!r}")
    params = spec.init(jax.random.key(TASK["seed"]))
    if model == "deepfm":
        opt = step.init_opt_state(params)
    batches = Batches(*tr, TRAIN["batch"], seed=TASK["seed"])
    if host_dedup:
        batches = DedupAuxBatches(batches, cap=compact_cap)
    for i in range(TRAIN["steps"]):
        b = tuple(jax.tree_util.tree_map(jnp.asarray, tuple(
            batches.next_batch()
        )))
        if model == "deepfm":
            params, opt, _ = step(params, opt, jnp.int32(i), *b)
        else:
            params, _ = step(params, jnp.int32(i), *b)
    # Score the held-out set and apply the SAME exact AUC as the oracle
    # (evaluate_params' histogram AUC would conflate metric quantization
    # with numeric parity).
    ids_te, vals_te, y_te = te
    scores = np.asarray(
        spec.scores(params, jnp.asarray(ids_te), jnp.asarray(vals_te)),
        np.float64,
    )
    return _auc(scores, np.asarray(y_te))


VARIANTS = {
    "fp32_scatter_add": dict(),
    "fp32_dedup": dict(sparse_update="dedup"),
    "fp32_host_dedup": dict(sparse_update="dedup", host_dedup=True),
    "bf16_scatter_add": dict(param_dtype="bfloat16"),
    "bf16_dedup_sr": dict(param_dtype="bfloat16", sparse_update="dedup_sr"),
    "bf16_dedup_sr_host": dict(param_dtype="bfloat16",
                               sparse_update="dedup_sr", host_dedup=True),
    # COMPACT host-dedup (the round-2 headline winner): cap=bucket is
    # always sufficient on this task (a field can't have more unique ids
    # than its bucket), so the cap-overflow path never triggers here.
    "fp32_dedup_compact": dict(sparse_update="dedup", host_dedup=True,
                               compact_cap=128),
    "bf16_dedup_sr_compact": dict(param_dtype="bfloat16",
                                  sparse_update="dedup_sr",
                                  host_dedup=True, compact_cap=128),
    # bf16 COMPUTE buffers on top of the compact bf16 path (the [B, w]
    # forward/backward passes in bf16; reductions/cumsum stay fp32).
    "bf16_compact_cdbf16": dict(param_dtype="bfloat16",
                                sparse_update="dedup_sr",
                                host_dedup=True, compact_cap=128,
                                compute_dtype="bfloat16"),
    # bf16 COMPUTE over EXACT fp32 storage + plain scatter_add — the
    # measured config-4 (FFM avazu) winner: only the forward/backward
    # buffers round to bf16; tables, gradients-at-rest, and the
    # scatter_add accumulation stay fp32, so no SR is needed.
    "fp32_cdbf16": dict(compute_dtype="bfloat16"),
    # The round-4 wire-precision rows (multi-device only — skipped on a
    # single device): fp32-wire sharded pins the sharded step's own
    # numerics; the bf16-wire rows budget the collective_dtype lever and
    # its composition with the exact score-sharded path.
    "sharded_fp32_wire": dict(sharded=True),
    "sharded_bf16_wire": dict(sharded=True, collective_dtype="bfloat16"),
    "sharded_bf16_wire_ss": dict(sharded=True,
                                 collective_dtype="bfloat16",
                                 score_sharded=True),
    # Round 5: the example-sharded deep head under the bf16 wire
    # (deepfm only — _variant_applies): budgets the lever's end-to-end
    # AUC cost on top of the wire dtype's.
    "sharded_bf16_wire_deep": dict(sharded=True,
                                   collective_dtype="bfloat16",
                                   deep_sharded=True),
}

# The committed protocol budgets (QUALITY.md): fp32-vs-oracle is expected
# to sit within the BASELINE-style 1e-3 band up to seed noise; the bf16
# scatter_add row is EXPECTED to fail (that is the measured failure
# dedup_sr exists to fix).
#
# The ORACLE rung compares two INDEPENDENT implementations (different
# RNG streams, inits, batch orders) — it checks the implementation, not
# numerics. For the convex-ish FM/FFM objectives 5e-3 absorbs that
# variance; DeepFM's nonconvex relu head adds optimization-path variance
# on top (measured fp32-vs-oracle delta 6.2e-3 with tight ≤3e-4
# variant-vs-fp32 rows — i.e. the spread is the TASK, not the code), so
# its rung gets 1e-2. The numerics budgets below are per-variant and
# model-independent.
ORACLE_BUDGET = {"fm": 5e-3, "ffm": 5e-3, "deepfm": 1e-2}
BUDGET_VS_FP32 = {
    "fp32_dedup": 1e-3,
    "fp32_host_dedup": 1e-3,
    "bf16_dedup_sr": 5e-3,
    "bf16_dedup_sr_host": 5e-3,
    "fp32_dedup_compact": 1e-3,
    "bf16_dedup_sr_compact": 5e-3,
    "bf16_compact_cdbf16": 5e-3,
    "fp32_cdbf16": 5e-3,
    "sharded_fp32_wire": 1e-3,
    "sharded_bf16_wire": 5e-3,
    "sharded_bf16_wire_ss": 5e-3,
    "sharded_bf16_wire_deep": 1e-2,
}


def _variant_applies(name: str, kw: dict, model: str) -> bool:
    """Per-model variant applicability (replaces the old FM-only gate on
    every sharded row — round 5 runs the sharded wire rows for all
    three families; only the family-specific levers stay scoped)."""
    if kw.get("score_sharded") and model != "fm":
        return False
    if kw.get("deep_sharded") and model != "deepfm":
        return False
    return True


ORACLES = {
    "fm": numpy_float64_oracle,
    "ffm": numpy_float64_oracle_ffm,
    "deepfm": numpy_float64_oracle_deepfm,
}


def online_smoke():
    """The continuous-learning quality trajectory (ISSUE 13): run the
    online protocol on the planted task with a label-flip drift at
    ``drift_day`` and report the day-over-day eval AUC series, the
    sentry verdict, and the rollback accounting as one JSON line —
    the maintained source of PERF.md's round-17 reference trajectory.
    Passes iff the sentry fires at exactly the first drifted eval day
    and the post-rollback chain tip is a non-demoted generation."""
    import tempfile

    from fm_spark_tpu import models, online
    from fm_spark_tpu.checkpoint import Checkpointer
    from fm_spark_tpu.data import synthetic_ctr
    from fm_spark_tpu.train import FMTrainer, TrainConfig

    n_days, drift_day = 8, 5
    ids, vals, labels = synthetic_ctr(
        4096, TASK["num_fields"] * TASK["bucket"], TASK["num_fields"],
        rank=TASK["planted_rank"], seed=TASK["seed"])
    days = online.flip_labels(
        online.split_days(ids, vals, labels, n_days), drift_day)
    spec = models.FMSpec(num_features=TASK["num_fields"] * TASK["bucket"],
                         rank=TASK["rank"], init_std=0.05)
    trainer = FMTrainer(spec, TrainConfig(
        num_steps=0, batch_size=128, learning_rate=TRAIN["lr"],
        lr_schedule="constant", optimizer="ftrl", log_every=10_000))
    trainer.logger._stream = None
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d, save_every=10**9, async_save=False)
        summary = online.run_online(trainer, days, ck,
                                    sentry=online.drift_guard())
        stones = ck.tombstoned_steps()
        ck.close()
    rolled = [e for e in summary["days"] if e["rolled_back"]]
    ok = (summary["rollbacks"] >= 1
          and bool(rolled) and rolled[0]["eval_day"] == drift_day
          and summary["last_good"] not in stones)
    print(json.dumps({
        "online_smoke": True, "drift_day": drift_day,
        "days": summary["days"], "rollbacks": summary["rollbacks"],
        "demoted_steps": summary["demoted_steps"],
        "last_good": summary["last_good"],
        "all_pass": ok,
    }))
    return 0 if ok else 1


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="fm", choices=list(ORACLES),
                    help="which oracle chain to run (VERDICT r2 #5: the "
                         "FM protocol, extended to FFM and DeepFM)")
    ap.add_argument("--variants", nargs="*", default=None,
                    choices=list(VARIANTS))
    ap.add_argument("--skip-oracle", action="store_true")
    ap.add_argument("--online-smoke", action="store_true",
                    dest="online_smoke",
                    help="run the continuous-learning quality "
                         "trajectory instead of the oracle chains "
                         "(ISSUE 13): planted drift at day 5 must "
                         "fire the sentry at exactly that eval day "
                         "and roll back")
    args = ap.parse_args()

    if args.online_smoke:
        return online_smoke()

    names = args.variants
    if names is None:
        # Full-B host_dedup rows are FM-only history; the shared compact
        # machinery is what FFM/DeepFM exercise. Sharded wire rows need
        # devices to shard over.
        import jax

        multi = jax.device_count() > 1
        names = [n for n in VARIANTS
                 if (args.model == "fm" or "host" not in n)
                 and _variant_applies(n, VARIANTS[n], args.model)
                 and (multi or "sharded" not in n)]
    tr, te = _data()
    out = {}
    if not args.skip_oracle:
        _log(f"numpy float64 {args.model} oracle...")
        out["numpy_float64_oracle"] = ORACLES[args.model](tr, te)
        _log(f"  auc={out['numpy_float64_oracle']:.4f}")
    for name in names:
        _log(f"variant {name}...")
        out[name] = framework_variant(tr, te, model=args.model,
                                      **VARIANTS[name])
        _log(f"  auc={out[name]:.4f}")

    checks = {}
    fp32 = out.get("fp32_scatter_add")
    if fp32 is not None and "numpy_float64_oracle" in out:
        d = abs(fp32 - out["numpy_float64_oracle"])
        ob = ORACLE_BUDGET[args.model]
        checks["fp32_vs_float64_oracle"] = {
            "delta": round(d, 5), "budget": ob, "pass": d <= ob,
        }
    for name, budget in BUDGET_VS_FP32.items():
        if fp32 is not None and name in out:
            d = abs(out[name] - fp32)
            checks[f"{name}_vs_fp32"] = {
                "delta": round(d, 5), "budget": budget, "pass": d <= budget,
            }
    # An empty check set must never read as success (a --variants subset
    # that skips the fp32 reference would otherwise vacuously pass).
    ok = bool(checks) and all(c["pass"] for c in checks.values())
    print(json.dumps({
        "model": args.model,
        "task": TASK, "train": TRAIN,
        "auc": {k: round(v, 5) for k, v in out.items()},
        "checks": checks,
        "all_pass": ok,
        **({} if checks else {"error": "no comparisons ran — include "
                              "fp32_scatter_add and/or the oracle"}),
    }))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
