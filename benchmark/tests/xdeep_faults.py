#!/usr/bin/env python3
"""The faults the xDeepFM cell's check has to catch, and how its limits
were read on the chip:

    python3 benchmark/tests/xdeep_faults.py --seed <n> [--only base,...]

Each fault is a context manager that changes the PROGRAM (never the
benchmark) for the length of one check run; ``tests/test_field_xdeepfm.py``
runs them at a small size on the CPU against the driver's own comparison,
and this script runs them at the cell's sizes on the chip: the unchanged
program first (reading one of each limit), then each fault (which must
come out ``ok: false``), then the unchanged program against the reference
computed one precision lower (reading two). One JSON line per case.

Eight faults, (a) to (h); (a) is two here, the CIN in one bfloat16 pass
wherever it is computed and in the step's head alone, and so is (h), the
CIN's kernels left out of Adam and left out of L2.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _one_pass():
    """``dlrm_faults.py``'s ``_one_pass`` (a product as one bfloat16 pass
    computes it, on the chip and off it), for the CIN's ``dot_general``."""
    spec = importlib.util.spec_from_file_location(
        "dlrm_faults", os.path.join(HERE, "dlrm_faults.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    import jax

    return module._one_pass(lambda a, b, precision: jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), precision=precision))


@contextlib.contextmanager
def _spec_method(method: str, replacement):
    from fm_spark_tpu.models.field_xdeepfm import FieldXDeepFMSpec

    real = getattr(FieldXDeepFMSpec, method)
    setattr(FieldXDeepFMSpec, method, replacement(real))
    try:
        yield
    finally:
        setattr(FieldXDeepFMSpec, method, real)


def _cin_fn(outer=None, dot=None, pool=None):
    """``FieldXDeepFMSpec.cin`` with ``outer(k, x, x0)`` for layer k's
    Hadamard products (``[B D, H_{k-1}, m]``, flattened as they come),
    ``dot(z, w)`` for the
    compression and ``pool(k, x)`` for a layer's pooled ``[B, H_k]``."""
    import jax
    import jax.numpy as jnp

    def cin(self, kernels, x0):
        rows, m = x0.shape
        x, pooled = x0, []
        for k, w in enumerate(kernels, start=1):
            h, h_prev, _ = w.shape
            z = (outer(k, x, x0) if outer
                 else x[:, :, None] * x0[:, None, :])
            z = z.reshape(rows, h_prev * m)
            w2 = w.reshape(h, h_prev * m)
            x = (dot(z, w2) if dot else jax.lax.dot_general(
                z, w2, (((1,), (1,)), ((), ())),
                precision=self._precision))
            p = x.reshape(-1, self.rank, h)
            pooled.append(pool(k, p) if pool else p.sum(axis=1))
        return jnp.concatenate(pooled, axis=1)

    return cin


def _cin(**how):
    """``FieldXDeepFMSpec.cin`` replaced by :func:`_cin_fn` ``(**how)``."""
    return _spec_method("cin", lambda _real: _cin_fn(**how))


def cin_one_bf16_pass(name: str):
    """(a) The CIN's compressions in one bfloat16 pass, forward and in the
    pullback."""
    return _cin(dot=_one_pass())


def step_cin_one_bf16_pass(name: str):
    """(a) as a change to the step alone would make it: the CIN the step's
    head computes (``head_scores_and_stats``, which the fused body takes)
    in one bfloat16 pass, while ``spec.cin`` called on its own — what the
    check's second witness reads — is as it was. Only what the step itself
    reports can catch it."""
    wrong = _cin_fn(dot=_one_pass())

    def replacement(real):
        def head(self, dense, h):
            with _spec_method("cin", lambda _real: wrong):
                return real(self, dense, h)
        return head

    return _spec_method("head_scores_and_stats", replacement)


def x_prev_in_place_of_x0(name: str):
    """(b) From layer 2 on, the Hadamard products take the layer before's
    first m maps where ``X^0`` belongs (the shape the kernel wants)."""
    def outer(k, x, x0):
        m = x0.shape[1]
        other = x0 if k == 1 else x[:, :m]
        return x[:, :, None] * other[:, None, :]

    return _cin(outer=outer)


def pooled_over_maps(name: str):
    """(c) Each layer pooled over its feature maps where it pools over the
    ``D`` columns: ``[B, D]``, padded with zeros to the layer's ``H_k``."""
    import jax.numpy as jnp

    def pool(k, p):
        over_maps = p.sum(axis=2)                               # [B, D]
        return jnp.pad(over_maps, ((0, 0), (0, p.shape[2] - p.shape[1])))

    return _cin(pool=pool)


def last_layer_only(name: str):
    """(d) Only the last layer's ``p^K`` reaches the output: the others'
    pooled maps are zeros."""
    import jax.numpy as jnp
    from fm_spark_tpu import configs

    layers = len(configs.CONFIGS[name].cin_layers)
    return _cin(pool=lambda k, p: (p.sum(axis=1) if k == layers
                                   else jnp.zeros_like(p[:, 0])))


def kernel_indices_swapped(name: str):
    """(e) The kernel's two input indices read the other way round: every
    layer's Hadamard block laid out ``(j, i)``, field-major, against
    ``W^k`` flattened ``(i, j)``. At layer 1 that is ``W^1[h, j, i]`` for
    ``W^1[h, i, j]``, and it is NO fault there: ``Z^1[i, j] = X^0[i] *
    X^0[j]`` is symmetric, so the swapped kernel gives the same ``X^1``
    and the same gradient, the same products added in another order
    (``tests/test_field_xdeepfm.py`` holds that). From layer 2 on, where ``H_{k-1} != m``, the order is a
    fault of the right shape."""
    return _cin(outer=lambda k, x, x0: x0[:, :, None] * x[:, None, :])


@contextlib.contextmanager
def linear_term_dropped(name: str):
    """(f) The linear term left out of the logit (its column never
    moves)."""
    from fm_spark_tpu import configs

    real = configs.RunConfig.spec

    def spec(self, *args, **kwargs):
        out = real(self, *args, **kwargs)
        return (dataclasses.replace(out, use_linear=False)
                if self.name == name else out)

    configs.RunConfig.spec = spec
    try:
        yield
    finally:
        configs.RunConfig.spec = real


def cin_pullback_left_out(name: str):
    """(g) The rows' gradient without the CIN's pullback: ``X^0`` enters
    the CIN as a constant, the DNN's ``g_h`` alone reaches the rows (the
    CIN's own gradients are whole)."""
    import jax

    def replacement(real):
        return lambda self, kernels, x0: real(self, kernels,
                                              jax.lax.stop_gradient(x0))

    return _spec_method("cin", replacement)


@contextlib.contextmanager
def _dense_grads(change):
    """``train.make_optimizer``'s update replaced by ``change(inner, grads,
    state, params, config) -> (updates, state)``, ``inner`` the real
    optimizer."""
    import optax

    from fm_spark_tpu import train

    real = train.make_optimizer

    def make_optimizer(config):
        inner = real(config)

        def update(grads, state, params=None):
            return change(inner, grads, state, params, config)

        return optax.GradientTransformation(inner.init, update)

    train.make_optimizer = make_optimizer
    try:
        yield
    finally:
        train.make_optimizer = real


def cin_out_of_adam(name: str):
    """(h) The CIN's kernels left out of Adam: descended by plain SGD at
    the rate, as the rows are."""
    import jax

    def change(inner, grads, state, params, config):
        updates, state = inner.update(grads, state, params)
        sgd = jax.tree.map(lambda g: -config.learning_rate * g,
                           grads["cin"]["layers"])
        return ({**updates, "cin": {**updates["cin"], "layers": sgd}},
                state)

    return _dense_grads(change)


def cin_out_of_l2(name: str):
    """(h) The L2 term taken back out of the CIN kernels' gradients before
    Adam sees them (every other leaf keeps its)."""
    import jax

    def change(inner, grads, state, params, config):
        layers = jax.tree.map(lambda g, p: g - config.reg_factors * p,
                              grads["cin"]["layers"],
                              params["cin"]["layers"])
        grads = {**grads, "cin": {**grads["cin"], "layers": layers}}
        return inner.update(grads, state, params)

    return _dense_grads(change)


FAULTS = {
    "cin_one_bf16_pass": cin_one_bf16_pass,
    "step_cin_one_bf16_pass": step_cin_one_bf16_pass,
    "x_prev_in_place_of_x0": x_prev_in_place_of_x0,
    "pooled_over_maps": pooled_over_maps,
    "last_layer_only": last_layer_only,
    "kernel_indices_swapped": kernel_indices_swapped,
    "linear_term_dropped": linear_term_dropped,
    "cin_pullback_left_out": cin_pullback_left_out,
    "cin_out_of_adam": cin_out_of_adam,
    "cin_out_of_l2": cin_out_of_l2,
}


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", required=True,
                    help="one seed, or several with commas")
    ap.add_argument("--cell", default="xdeepfm_cin200.train")
    ap.add_argument("--only", default=None,
                    help="comma-separated cases (base, lower, a fault's name)")
    ap.add_argument("--rehearse", action="store_true",
                    help="the cell's tiny sizes, on any backend")
    args = ap.parse_args(argv)

    from benchmark.harness import load_cell

    cell = load_cell(args.cell, rehearse=args.rehearse)
    for seed in map(int, args.seed.split(",")):
        one_seed(args, cell, seed)
    return 0


def one_seed(args, cell, seed: int) -> None:
    import json
    import time

    import jax

    from benchmark.drivers import train_deep, train_xdeep
    from benchmark.harness import Context
    from fm_spark_tpu import configs

    ctx = Context(cell=cell, seed=seed, seconds=0.0,
                  t_start=time.perf_counter(), trace_dir=None)
    cfg, memory = train_xdeep.hold_program(ctx)
    chips = jax.device_count()
    steps, early_steps = (int(cell.mix[k])
                          for k in ("check_steps", "early_steps"))
    uniq, counts, inv, vals, labels = train_deep.one_batch(ctx, chips)
    want, start = train_xdeep.reference_run(ctx, uniq, inv, vals, labels)

    block = min(int(cell.mix["check_chunk"]), inv.shape[0])
    rank, layers = cell.config["model"]["rank"], cell.config["head"][
        "cin_layers"]

    def say(name, runs, against, got, want_cin):
        forward = train_xdeep.cin_errors(got, want_cin, layers)
        verdict = train_xdeep.compare(
            *runs, against, start, counts, forward, steps=steps,
            early_steps=early_steps,
            learning_rate=cell.config["training"]["learning_rate"],
            tol=cell.mix, cin_layers=layers)
        print(json.dumps({"case": name, "device": jax.default_backend(),
                          "seed": seed, "compiled_step_bytes": memory,
                          **verdict, "losses": runs[0]["losses"]}),
              flush=True)

    cases = ["base", *FAULTS, "lower"]
    if args.only:
        cases = [c for c in cases if c in args.only.split(",")]
    base = None
    for name in cases:
        if name == "lower":
            continue
        with (FAULTS[name](cfg.name) if name != "base"
              else contextlib.nullcontext()):
            runs = train_xdeep.two_runs(ctx, configs.CONFIGS[cfg.name],
                                        chips, uniq)
            got = train_xdeep.program_cin(configs.CONFIGS[cfg.name],
                                          runs[1], inv, vals)
        say(name, runs, want, got,
            train_xdeep.reference_cin(runs[1], inv, vals, rank, block))
        if name == "base":
            base = runs, got
    if "lower" in cases:
        if base is None:
            runs = train_xdeep.two_runs(ctx, cfg, chips, uniq)
            base = runs, train_xdeep.program_cin(cfg, runs[1], inv, vals)
        low, _ = train_xdeep.reference_run(ctx, uniq, inv, vals, labels,
                                           precision="bfloat16")
        say("base_against_reference_one_precision_lower", base[0], low,
            base[1], train_xdeep.reference_cin(base[0][1], inv, vals, rank,
                                               block, precision="bfloat16"))


if __name__ == "__main__":
    sys.exit(main())
