#!/usr/bin/env python3
"""Six faults the FFM-under-AdaGrad cell's check has to catch, and how its
limits were read on the chip:

    python3 benchmark/tests/adagrad_faults.py --seed <n> [--only base,...]

Each fault is a context manager that changes the PROGRAM (never the
benchmark) for the length of one check run; ``tests/test_field_ffm_adagrad
.py`` runs them at a tiny size on the CPU against the driver's own
comparison (``drivers/train_adagrad.py`` ``compare``), and this script
runs them at the cell's sizes on the chip: the unchanged program first
(reading one of each limit), then each fault (which must come out ``ok:
false``), then the unchanged program against the reference computed one
precision lower (bfloat16 products; reading two). One JSON line per case.
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
def _rule(replacement):
    """``optim.adagrad_rows`` replaced by ``replacement(rows, n, g, lr,
    seen)``; ``seen`` is what coalescing saw of the same field's lanes
    beside their sum (``{"squares": sum of g^2, "count": occurrences}``
    per unique row), for the faults that need it."""
    import jax.numpy as jnp

    from fm_spark_tpu import optim
    from fm_spark_tpu.ops import scatter

    real_rule, real_coalesce = optim.adagrad_rows, scatter.coalesce
    seen: list = []

    def coalesce(ids, delta):
        _, squares, _ = real_coalesce(ids, delta.astype(jnp.float32) ** 2)
        _, count, _ = real_coalesce(
            ids, jnp.ones((ids.shape[0], 1), jnp.float32))
        seen.append({"squares": squares, "count": count})
        return real_coalesce(ids, delta)

    def rule(rows, n, g, lr):
        return replacement(rows.astype(jnp.float32), n.astype(jnp.float32),
                           g.astype(jnp.float32), lr, seen.pop())

    # One chunk of all lanes, so that the rule meets a field's rows in
    # the order coalescing saw them (the same mathematics, lane for lane).
    chunk, scatter.RULE_CHUNK = scatter.RULE_CHUNK, 1 << 30
    optim.adagrad_rows, scatter.coalesce = rule, coalesce
    try:
        yield
    finally:
        optim.adagrad_rows, scatter.coalesce = real_rule, real_coalesce
        scatter.RULE_CHUNK = chunk


EPS = 1e-8


def accumulator_never_updated(name: str):
    """(a) ``G`` stays where it started: the step divides by its root
    and nothing is added."""
    import jax.numpy as jnp

    return _rule(lambda rows, n, g, lr, seen: (
        rows - lr * g / (jnp.sqrt(n) + EPS), n))


def duplicates_not_coalesced(name: str):
    """(b) ``G`` takes the SUM OF SQUARES of a row's occurrences where it
    should take the square of their sum."""
    import jax.numpy as jnp

    def rule(rows, n, g, lr, seen):
        n = n + seen["squares"]
        return rows - lr * g / (jnp.sqrt(n) + EPS), n

    return _rule(rule)


def stale_denominator(name: str):
    """(c) The step divides by the root of ``G`` from BEFORE the add."""
    import jax.numpy as jnp

    return _rule(lambda rows, n, g, lr, seen: (
        rows - lr * g / (jnp.sqrt(n) + EPS), n + g * g))


@contextlib.contextmanager
def bf16_slots(name: str):
    """(d) The accumulator tables stored in bfloat16."""
    import jax
    import jax.numpy as jnp

    from fm_spark_tpu import optim

    real = optim.init_field_slots

    def init_field_slots(*args, **kwargs):
        return jax.tree.map(lambda t: t.astype(jnp.bfloat16),
                            real(*args, **kwargs))

    optim.init_field_slots = init_field_slots
    try:
        yield
    finally:
        optim.init_field_slots = real


@contextlib.contextmanager
def l2_outside_gradient(name: str):
    """(e) The L2 term as a decay beside the rule (``w -= eta * lambda *
    occurrences * w``) and not inside ``g``: it is neither divided by the
    root of ``G`` nor squared into it."""
    import jax.numpy as jnp

    from fm_spark_tpu import configs

    reg = configs.CONFIGS[name].reg_factors

    def rule(rows, n, g, lr, seen):
        n = n + g * g
        decay = lr * reg * seen["count"] * rows
        decay = decay.at[:, -1].set(0.0)          # reg_linear is 0
        return rows - lr * g / (jnp.sqrt(n) + EPS) - decay, n

    with _registry(name, reg_factors=0.0), _rule(rule):
        yield


def plain_sgd(name: str):
    """(f) Plain SGD on the tables at the same rate."""
    return _registry(name, optimizer="sgd")


FAULTS = {
    "accumulator_never_updated": accumulator_never_updated,
    "duplicates_not_coalesced": duplicates_not_coalesced,
    "stale_denominator": stale_denominator,
    "bf16_slots": bf16_slots,
    "l2_outside_gradient": l2_outside_gradient,
    "plain_sgd": plain_sgd,
}


def rows_by_count(got, want, start, counts) -> list:
    """The rows' error against how often a row was met: per octave of
    counts ``[lowest count, rows, largest |err| / the row's largest
    delta (factors), the same (linear), smallest such delta]``."""
    import numpy as np

    err = np.abs(got - want)
    delta = np.abs(want - start)
    out = []
    lo = 1
    while lo <= counts.max():
        pick = (counts >= lo) & (counts < 2 * lo)
        if pick.any():
            row = [lo, int(pick.sum())]
            for cols in (slice(0, -1), slice(-1, None)):
                scale = delta[..., cols].max(-1)
                worst = err[..., cols].max(-1) / np.maximum(scale, 1e-30)
                row += [float(f"{worst[pick].max():.3g}"),
                        float(f"{scale[pick].min():.3g}")]
            out.append(row)
        lo *= 2
    return out


def main(argv=None) -> int:
    import argparse
    import json
    import time

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--cell", default="ffm_r16_adagrad.train")
    ap.add_argument("--only", default=None,
                    help="comma-separated cases (base, a fault's name, lower)")
    ap.add_argument("--rehearse", action="store_true",
                    help="the cell's tiny sizes, on any backend")
    args = ap.parse_args(argv)

    import jax

    from benchmark.drivers import train_adagrad
    from benchmark.drivers.registry import registry_config
    from benchmark.harness import Context, load_cell
    from fm_spark_tpu import configs

    cell = load_cell(args.cell, rehearse=args.rehearse)
    ctx = Context(cell=cell, seed=args.seed, seconds=0.0,
                  t_start=time.perf_counter(), trace_dir=None)
    cfg = registry_config(cell.config)
    chips = jax.device_count()
    steps, early_steps = (int(cell.mix[k])
                          for k in ("check_steps", "early_steps"))
    uniq, counts, inv, vals, labels, _ = train_adagrad.one_batch(ctx, chips)
    want, rows0 = train_adagrad.reference_run(ctx, uniq, inv, vals, labels)

    def say(name, late, early, against):
        verdict = train_adagrad.compare(
            late, early, against, rows0, counts, steps=steps,
            early_steps=early_steps,
            learning_rate=cell.config["training"]["learning_rate"],
            tol=cell.mix, step_per_residual=train_adagrad.step_per_residual(
                cell.config["training"]))
        for run, doc, ref in (
                ("early", early,
                 train_adagrad.early_of(against, early_steps)),
                ("late", late, against)):
            if run in verdict:
                verdict[run]["rows_by_count"] = rows_by_count(
                    doc["rows"], ref["rows"], rows0, counts)
        print(json.dumps({"case": name, "device": jax.default_backend(),
                          "seed": args.seed, **verdict}), flush=True)

    cases = ["base", *FAULTS, "lower"]
    if args.only:
        cases = [c for c in cases if c in args.only.split(",")]
    base = None
    for name in cases:
        if name == "lower":
            continue
        with (FAULTS[name](cfg.name) if name != "base"
              else contextlib.nullcontext()):
            late, early = train_adagrad.two_runs(
                ctx, configs.CONFIGS[cfg.name], chips, uniq)
        say(name, late, early, want)
        if name == "base":
            base = late, early
    if base is not None and "lower" in cases:
        low, _ = train_adagrad.reference_run(ctx, uniq, inv, vals, labels,
                                             compute_dtype="bfloat16")
        say("base_against_reference_one_precision_lower", *base, low)
    return 0


if __name__ == "__main__":
    sys.exit(main())
