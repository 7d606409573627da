"""Weak-scaling convergence A/B: does an n×-larger GLOBAL batch (the
``--batch-per-chip`` weak-scaling recipe) cost quality at an EQUAL
sample budget?

The projection model (parallel/projection.py, PERF.md "Round-4
scale-out levers") names "larger global batch" as a throughput lever
and flags the convergence question; this script answers it on the
committed deterministic planted-FM task (bench_quality.py's TASK) so
the answer is a number, not a guess. Protocol: EPOCH-EXACT equal real
sample budgets — Batches pads each epoch's final partial batch with
weight-0 rows, so every epoch trains on exactly the train-split size
regardless of batch; each arm therefore runs the SAME epoch count
(the baseline's 1500 steps = 50 epochs at batch 512), with per-arm
steps = epochs × ceil(n_train/batch). lr rules per scaled arm: same /
linear ·m / sqrt ·√m. Reported: held-out exact AUC per arm (same
metric as the oracle chain).

Prints one JSON line. CPU-runnable; nothing here measures speed.
"""

import argparse
import json
import sys

import numpy as np

from bench_quality import TASK, TRAIN, _auc, _data


def _log(msg):
    print(f"bench_convergence: {msg}", file=sys.stderr, flush=True)


def run_arm(tr, te, batch, steps, lr):
    import jax
    import jax.numpy as jnp

    from fm_spark_tpu import models
    from fm_spark_tpu.data import Batches
    from fm_spark_tpu.sparse import make_field_sparse_sgd_step
    from fm_spark_tpu.train import TrainConfig

    spec = models.FieldFMSpec(
        num_features=TASK["num_fields"] * TASK["bucket"],
        rank=TASK["rank"], num_fields=TASK["num_fields"],
        bucket=TASK["bucket"], init_std=0.05,
    )
    step = make_field_sparse_sgd_step(
        spec, TrainConfig(learning_rate=lr, lr_schedule="constant",
                          optimizer="sgd", seed=TASK["seed"]),
    )
    params = spec.init(jax.random.key(TASK["seed"]))
    batches = Batches(*tr, batch, seed=TASK["seed"])
    for i in range(steps):
        b = tuple(map(jnp.asarray, batches.next_batch()))
        params, _ = step(params, jnp.int32(i), *b)
    ids_te, vals_te, y_te = te
    scores = np.asarray(
        spec.scores(params, jnp.asarray(ids_te), jnp.asarray(vals_te)),
        np.float64,
    )
    return _auc(scores, np.asarray(y_te))


def main():
    ap = argparse.ArgumentParser()
    def _pos_int(v):
        iv = int(v)
        if iv < 2:
            raise argparse.ArgumentTypeError("multiplier must be >= 2")
        return iv

    ap.add_argument("--mults", type=_pos_int, nargs="+", default=[4, 8],
                    help="global-batch multipliers to test vs the "
                         "batch-512 baseline (8 = one v5e-8's weak "
                         "scaling)")
    args = ap.parse_args()

    from bench_quality import _jax

    _jax()

    tr, te = _data()
    n_tr = len(tr[2])
    b0, s0, lr0 = TRAIN["batch"], TRAIN["steps"], TRAIN["lr"]
    spe0 = -(-n_tr // b0)                 # steps per epoch, baseline
    if s0 % spe0:
        raise SystemExit(
            f"baseline steps ({s0}) must be whole epochs "
            f"({spe0} steps/epoch at batch {b0}) for the epoch-exact "
            "budget protocol"
        )
    epochs = s0 // spe0
    out = {"baseline": {"batch": b0, "steps": s0, "lr": lr0,
                        "auc": None}}
    _log(f"baseline batch={b0} steps={s0} ({epochs} epochs) lr={lr0}")
    out["baseline"]["auc"] = round(run_arm(tr, te, b0, s0, lr0), 5)
    arms = {}
    for m in args.mults:
        steps_m = epochs * -(-n_tr // (b0 * m))
        for rule, lr in (("same_lr", lr0),
                         ("linear_lr", lr0 * m),
                         ("sqrt_lr", lr0 * m ** 0.5)):
            name = f"x{m}_{rule}"
            _log(f"{name}: batch={b0 * m} steps={steps_m} lr={lr:.3g}")
            arms[name] = {
                "batch": b0 * m, "steps": steps_m, "lr": round(lr, 4),
                "auc": round(run_arm(tr, te, b0 * m, steps_m, lr), 5),
            }
    base_auc = out["baseline"]["auc"]
    best = max(arms.items(), key=lambda kv: kv[1]["auc"])
    print(json.dumps({
        "task": TASK,
        "epochs": epochs,
        "real_samples_budget": epochs * n_tr,
        **out,
        "arms": arms,
        "best_scaled": {"arm": best[0], **best[1],
                        "delta_vs_baseline": round(
                            best[1]["auc"] - base_auc, 5)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
