#!/usr/bin/env python3
"""Five faults the DeepFM cell's check has to catch, and how its limits
were read on the chip:

    python3 benchmark/tests/deepfm_faults.py --seed <n> [--out chiprun_out/deepfm_faults]

Each fault is a context manager that changes the PROGRAM (never the
benchmark) for the length of one check run; ``tests/test_deepfm_reference
.py`` runs them at a tiny size on the CPU against the driver's own
comparison, and this script runs them at the cell's sizes on the chip:
the unchanged program first (reading one of each limit), then each
fault (which must come out ``ok: false``), then the unchanged program
against the reference computed one precision lower (reading two). One
JSON line per case; with ``--out`` what the program returned is kept as
``.npz`` for reading the limits again off the chip.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@contextlib.contextmanager
def _registry(name: str, **changes):
    from fm_spark_tpu import configs

    real = configs.CONFIGS[name]
    configs.CONFIGS[name] = dataclasses.replace(real, **changes)
    try:
        yield
    finally:
        configs.CONFIGS[name] = real


@contextlib.contextmanager
def _deep_scores(replacement):
    from fm_spark_tpu.models.field_deepfm import FieldDeepFMSpec

    real = FieldDeepFMSpec.deep_scores
    FieldDeepFMSpec.deep_scores = replacement(real)
    try:
        yield
    finally:
        FieldDeepFMSpec.deep_scores = real


def head_one_bf16_pass(name: str):
    """The head's products in one bfloat16 pass (what a float32 ``@`` at
    the default precision is on the TPU, and what the head did before
    it stated a precision). Off the chip the default is float32, so
    there the pass is written out: both operands rounded to bfloat16,
    products summed in float32, forward and in the pullback."""
    import jax
    import jax.numpy as jnp

    highest = jax.lax.Precision.HIGHEST

    def rounded(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    @jax.custom_vjp
    def one_pass(a, b):
        return jnp.dot(rounded(a), rounded(b), precision=highest)

    def fwd(a, b):
        return one_pass(a, b), (a, b)

    def bwd(saved, g):
        a, b = saved
        return (jnp.dot(rounded(g), rounded(b).T, precision=highest),
                jnp.dot(rounded(a).T, rounded(g), precision=highest))

    one_pass.defvjp(fwd, bwd)
    on_chip = jax.default_backend() == "tpu"

    def replacement(_real):
        def deep_scores(self, mlp, h):
            for li, layer in enumerate(mlp):
                h = (jnp.dot(h, layer["kernel"]) if on_chip
                     else one_pass(h, layer["kernel"])) + layer["bias"]
                if li < len(self.mlp_dims):
                    h = jax.nn.relu(h)
            return h[:, 0]
        return deep_scores

    return _deep_scores(replacement)


def bf16_tables(name: str):
    """The tables stored in bfloat16."""
    return _registry(name, param_dtype="bfloat16")


def pullback_left_out(name: str):
    """``g_h``, the head's pullback to the embedding, left out of the
    row gradient (the head's own gradients are whole)."""
    import jax

    def replacement(real):
        return lambda self, mlp, h: real(self, mlp, jax.lax.stop_gradient(h))

    return _deep_scores(replacement)


def sgd_on_head(name: str):
    """Plain SGD where the head and the bias should descend by Adam."""
    return _registry(name, optimizer="sgd")


@contextlib.contextmanager
def head_l2_dropped(name: str):
    """The L2 term taken back out of every head gradient before Adam
    sees it (the tables keep theirs)."""
    import jax
    import optax

    from fm_spark_tpu import train

    real = train.make_optimizer

    def make_optimizer(config):
        inner = real(config)

        def update(grads, state, params=None):
            grads = {**grads, "mlp": jax.tree_util.tree_map(
                lambda g, p: g - config.reg_factors * p,
                grads["mlp"], params["mlp"])}
            return inner.update(grads, state, params)

        return optax.GradientTransformation(inner.init, update)

    train.make_optimizer = make_optimizer
    try:
        yield
    finally:
        train.make_optimizer = real


FAULTS = {
    "head_one_bf16_pass": head_one_bf16_pass,
    "bf16_tables": bf16_tables,
    "pullback_left_out": pullback_left_out,
    "sgd_on_head": sgd_on_head,
    "head_l2_dropped": head_l2_dropped,
}


def rows_by_count(got, want, start, counts, steps: int) -> list:
    """The factors' error in ulps against how often a row was met:
    ``[lowest count, rows, largest |err|, largest |err| / sqrt(steps *
    count)]`` per octave of counts, over the elements of ordinary size
    (an element near zero has an ulp to match)."""
    import numpy as np

    from benchmark.drivers.train_deep import _ulp

    factors = slice(0, want.shape[-1] - 1)
    ulp = _ulp(want, start)[..., factors]
    err = np.abs(got - want)[..., factors] / ulp
    err = np.where(ulp >= 2.0 ** -31, err, 0.0).max(-1)   # |weight| >= 2^-8
    out = []
    lo = 1
    while lo <= counts.max():
        pick = (counts >= lo) & (counts < 2 * lo)
        if pick.any():
            out.append([lo, int(pick.sum()), round(float(err[pick].max()), 1),
                        round(float((err / np.sqrt(np.maximum(
                            steps * counts, 1)))[pick].max()), 3)])
        lo *= 2
    return out


def main(argv=None) -> int:
    import argparse
    import json
    import time

    import numpy as np

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--cell", default="deepfm_r16.train")
    ap.add_argument("--out", default=None)
    ap.add_argument("--keep", default="reference,base,head_one_bf16_pass,"
                    "head_l2_dropped",
                    help="cases whose arrays --out keeps (10 MB each)")
    ap.add_argument("--only", default=None,
                    help="comma-separated cases (base, a fault's name)")
    ap.add_argument("--rehearse", action="store_true",
                    help="the cell's tiny sizes, on any backend")
    args = ap.parse_args(argv)

    import jax

    from benchmark.drivers import train_deep
    from benchmark.drivers.registry import registry_config
    from benchmark.harness import Context, load_cell
    from fm_spark_tpu import configs

    cell = load_cell(args.cell, rehearse=args.rehearse)
    ctx = Context(cell=cell, seed=args.seed, seconds=0.0,
                  t_start=time.perf_counter(), trace_dir=None)
    cfg = registry_config(cell.config)
    chips = jax.device_count()
    steps, head_steps = (int(cell.mix[k])
                         for k in ("check_steps", "head_steps"))
    uniq, counts, inv, vals, labels = train_deep.one_batch(ctx, chips)
    want, rows0 = train_deep.reference_run(ctx, uniq, inv, vals, labels)
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        np.savez_compressed(os.path.join(args.out, "batch.npz"),
                            counts=counts, rows0=rows0)

    def keep(name, **runs):
        if args.out and name in args.keep.split(","):
            flat = {}
            for run, doc in runs.items():
                flat.update({f"{run}.{k}": v for k, v in
                             train_deep.blocks(doc).items()})
                flat[f"{run}.losses"] = np.asarray(doc["losses"], np.float64)
                flat[f"{run}.rows"] = doc["rows"]
            np.savez_compressed(os.path.join(args.out, name + ".npz"),
                                **flat)

    def say(name, late, early, against):
        verdict = train_deep.compare(
            late, early, against, rows0, counts, steps=steps,
            head_steps=head_steps,
            learning_rate=cell.config["training"]["learning_rate"],
            tol=cell.mix)
        for run, doc, ref, n in (
                ("early", early, train_deep.early_of(against, head_steps),
                 head_steps),
                ("late", late, against, steps)):
            if run in verdict:
                verdict[run]["rows_by_count"] = rows_by_count(
                    doc["rows"], ref["rows"], rows0, counts, n)
        print(json.dumps({"case": name, "device": jax.default_backend(),
                          "seed": args.seed, **verdict}), flush=True)

    def as_runs(ref):
        return {"late": ref, "early": train_deep.early_of(ref, head_steps)}

    keep("reference", **as_runs(want))
    cases = ["base", *FAULTS]
    if args.only:
        cases = [c for c in cases if c in args.only.split(",")]
    base = None
    for name in cases:
        with (FAULTS[name](cfg.name) if name != "base"
              else contextlib.nullcontext()):
            late, early = train_deep.two_runs(
                ctx, configs.CONFIGS[cfg.name], chips, uniq)
        keep(name, late=late, early=early)
        say(name, late, early, want)
        if name == "base":
            base = late, early
    if base is not None:
        low, _ = train_deep.reference_run(ctx, uniq, inv, vals, labels,
                                          matmul_precision="default")
        keep("reference_one_precision_lower", **as_runs(low))
        say("base_against_reference_one_precision_lower", *base, low)
    return 0


if __name__ == "__main__":
    sys.exit(main())
