"""The DeepFM training cell: ``drivers/train.py``'s run — the same adapter,
the same window, the same clock — with the check replaced, because that
driver's reference (``reference/sgd.py``) knows tables, a bias and plain
SGD only, and this configuration adds a dense head under Adam.

The check runs ``cli train`` on a set of exactly one batch, as that
driver's does, parameters taken where the program saves them — but
TWICE: ``check_steps`` steps (8, the mix's) and ``head_steps`` steps (2),
each compared with ``reference/deepfm.py`` after as many: every logged
loss, every touched row, the bias and the head. The program has to be
read early. Adam's step is ``lr * m_hat / (sqrt(v_hat) + eps)``: about
``lr * sign(g)`` whatever the gradient's size, so every element whose
gradient lies within rounding noise of zero moves by up to a learning
rate in the direction the noise chooses, the next step's gradients
inherit that, and the distance between two float32 runs of the same
program grows with every step: after eight the head of a correct run is
as far from the reference as a head computed in bfloat16 is after two,
and the pullback carries that into the rows (PERF.md section 6 has the
readings). After two steps both moments, both bias corrections and the L2
term have acted, and a wrong one is still an order or two above the
noise: the early run is held to the tight limits, the late run to
what that divergence leaves of them (:func:`compare`).

A traced run also reads the head's device time out of the profiler's
trace before it returns (``benchmark/deep_trace.py``): the harness
deletes the trace once it has reduced it to ten op families, none of
which is the head.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmark import deep_trace, flops, synthetic, trace_reduce
from benchmark.drivers import train
from benchmark.drivers.registry import registry_config
from benchmark.harness import Context, Result
from benchmark.reference import deepfm, sgd

# |m_hat| / sqrt(v_hat) stays under 1.03 over Adam's first eight steps
# (Cauchy-Schwarz over its two weightings), so two runs differ by at most
# this many learning rates a step in any element, whatever the noise.
ADAM_STEP_CAP = 2.1


def blocks(dense: dict) -> dict:
    """``{"w0", "head"}`` by block name, in one flat dict of arrays."""
    out = {"w0": np.asarray(dense["w0"], np.float32)}
    for i, layer in enumerate(dense["head"]):
        out[f"kernel{i}"] = np.asarray(layer["kernel"], np.float32)
        out[f"bias{i}"] = np.asarray(layer["bias"], np.float32)
    return out


def _ulp(*arrays):
    return np.spacing(np.maximum.reduce(
        [np.abs(a) for a in arrays]).astype(np.float32))


def compare_dense(got: dict, want: dict, *, steps: int, learning_rate: float,
                  mean_rates: float) -> dict:
    """The bias and the head after ``steps`` steps, block by block (the
    bias, each kernel, each bias vector), in LEARNING RATES: Adam moves
    every element by about one a step, so that is the unit in which a
    wrong step shows, whatever the weight's or the gradient's size.

    - the MEAN distance of a block's elements may be ``mean_rates``. A
      mean, because the distance of single elements has a heavy tail in
      every run (the elements at the noise, module docstring) that a
      maximum or a root mean square would read and a mean does not, and
      because what this is here to catch — a product in lower precision,
      a missing term, another optimizer — moves every element;
    - no element may be further off than Adam can move it:
      ``ADAM_STEP_CAP`` rates a step."""
    out = {}
    got, want = blocks(got), blocks(want)
    for name, w in want.items():
        rates = np.abs(got[name] - w) / learning_rate
        out[name] = {"mean_rates": float(rates.mean()),
                     "max_rates": float(rates.max()),
                     "over_allowed": float(max(
                         rates.mean() / mean_rates,
                         rates.max() / (ADAM_STEP_CAP * steps)))}
    return out


def compare_rows(got, want, start, counts, *, steps: int, rtol: float,
                 ulps_per_root_occurrence: float) -> dict:
    """Every touched row after ``steps`` steps, as deltas from ``start``,
    factor and linear columns apart: an element may be off by ``rtol`` of
    its block's largest delta plus the rounding of a float32 scatter-add
    that adds one occurrence at a time into a weight some 1e7 ulps
    large: one ulp a step, and ``ulps_per_root_occurrence`` ulps times
    the ROOT of the occurrences it has added (``steps * counts``).
    (``drivers/train.py`` allows one ulp per occurrence: right for config
    4, whose decays of under half an ulp are lost one by one, void here,
    where at rate 1e-3 the hottest row's whole delta is some ten thousand
    ulps and its 6,000 occurrences a step would excuse all of it. Here an
    occurrence adds 2 to 3 ulps, its rounding has no side, and the sum
    walks: PERF.md section 6.)"""
    live = counts > 0
    err = np.abs(got - want)
    delta = np.abs(want - start)
    ulp = _ulp(want, start)
    walk = (steps + ulps_per_root_occurrence
            * np.sqrt(steps * counts))[..., None]
    factor_cols = want.shape[-1] - 1
    out = {}
    for name, cols in (("factors", slice(0, factor_cols)),
                       ("linear", slice(factor_cols, None))):
        scale = float(delta[..., cols][live].max())
        allowed = rtol * scale + walk * ulp[..., cols]
        out[name] = {
            "largest_delta": scale,
            "err_over_largest_delta": float(err[..., cols][live].max()
                                            / max(scale, 1e-30)),
            "over_allowed": float(
                (err[..., cols] / np.maximum(allowed, 1e-30))[live].max()),
        }
    return out


def early_of(want: dict, head_steps: int) -> dict:
    """The reference's run as the short run sees it: its state after
    ``head_steps`` steps and the losses up to there."""
    return {**want["after"][head_steps],
            "losses": want["losses"][:head_steps]}


def compare(late: dict, early: dict, want: dict, start_rows: np.ndarray,
            counts: np.ndarray, *, steps: int, head_steps: int,
            learning_rate: float, tol: dict) -> dict:
    """The program's two check runs (``losses``, ``rows`` ``[F, U, w]``,
    ``w0``, ``head``: ``late`` after ``steps`` steps, ``early`` after
    ``head_steps``) against the reference (``reference.deepfm.train``'s
    result with ``after[head_steps]``); ``counts[f, u]`` says how often
    the batch met a row (0: padding). Each limit is ``tol``'s, the mix's;
    the early run is held to the tight ones, and the late run to what
    the head's divergence (module docstring) leaves of them:

    - ``loss_rtol``: every logged loss of both runs, relative. A mean of
      16,384 float32 terms of about 0.7 that Adam moves by a few parts
      in a thousand a step: it holds what the parameters' own limits
      would miss, a head or a loss that is another function.
    - rows (:func:`compare_rows`): ``rows_rtol`` early; late
      ``rows_rtol_late``, because the pullback ``g_h`` carries the
      head's divergence into every row (a correct run's hot rows end 1
      to 3% of the largest delta off; one without ``g_h`` 100%).
    - the bias and the head (:func:`compare_dense`): ``head_mean_rates``
      early; late ``head_mean_rates_late``, which only says that Adam at
      this rate ran."""
    out: dict = {}
    for name, run, ref, n, late_ in (
            ("early", early, early_of(want, head_steps), head_steps, ""),
            ("late", late, want, steps, "_late")):
        losses = np.asarray(run["losses"], np.float64)
        if len(losses) != n or not np.all(np.isfinite(losses)):
            return {"ok": False,
                    "why": f"{len(losses)} finite-or-not losses for {n} steps"}
        out[name] = {
            "loss_rel_err": float(np.max(
                np.abs(losses - ref["losses"]) / np.abs(ref["losses"]))),
            "rows": compare_rows(
                run["rows"], ref["rows"], start_rows, counts, steps=n,
                rtol=float(tol["rows_rtol" + late_]),
                ulps_per_root_occurrence=float(
                    tol["rows_ulps_per_root_occurrence"])),
            "dense": compare_dense(
                run, ref, steps=n, learning_rate=learning_rate,
                mean_rates=float(tol["head_mean_rates" + late_])),
        }
    ok = all(run["loss_rel_err"] <= float(tol["loss_rtol"])
             and all(b["over_allowed"] <= 1.0 for group in ("rows", "dense")
                     for b in run[group].values())
             for run in out.values())
    return {**out, "ok": bool(ok)}


def program_run(ctx: Context, cfg, chips: int,
                steps: int) -> tuple[dict, list]:
    """``steps`` steps of ``cli train`` on exactly one batch: ``(the model
    it saved, the losses it logged)``."""
    from fm_spark_tpu import models

    training = ctx.cell.config["training"]
    losses: list[float] = []
    captured: dict = {}

    def on_doc(_now, doc):
        if "step" in doc and "loss" in doc:
            losses.append(doc["loss"])

    def capture(path, spec, params):
        captured["params"] = params

    # The program hands out parameters only by saving them.
    real_save, models.save_model = models.save_model, capture
    try:
        train.run_cli(
            train.train_argv(cfg.name,
                             rows=training["batch_per_chip"] * chips,
                             batch_per_chip=training["batch_per_chip"],
                             seed=ctx.seed, steps=steps, log_every=1)
            + ["--model-out", train.CAPTURE], on_doc)
    finally:
        models.save_model = real_save
    return captured.get("params"), losses


def one_batch(ctx: Context, chips: int):
    """The check run's one batch as the reference wants it:
    ``(uniq, counts, inv, vals, labels)`` (``reference/sgd.py``)."""
    model = ctx.cell.config["model"]
    fields, bucket = model["num_fields"], model["bucket"]
    batch = ctx.cell.config["training"]["batch_per_chip"] * chips
    ids, vals, labels = synthetic.synthetic_ctr(
        batch, fields * bucket, fields, seed=ctx.seed)
    uniq, counts, inv, _ = sgd.touched(synthetic.field_local(ids, bucket))
    return uniq, counts, inv, vals, labels


def taken(params, uniq) -> dict:
    """What the comparison reads of a saved model, as NumPy (so that the
    tables can go): the rows ``uniq`` of each table, the bias, the head."""
    return {
        "rows": np.stack([np.asarray(params["vw"][f][uniq[f]], np.float32)
                          for f in range(len(uniq))]),
        "w0": float(np.asarray(params["w0"])),
        "head": [{k: np.asarray(v, np.float32) for k, v in layer.items()}
                 for layer in params["mlp"]],
    }


def reference_run(ctx: Context, uniq, inv, vals, labels,
                  matmul_precision: str = "highest") -> tuple[dict, np.ndarray]:
    """The long run's steps through ``reference/deepfm.py``, the state
    kept as it stood after the short run's: ``(want, initial rows)``."""
    config, mix = ctx.cell.config, ctx.cell.mix
    model, training = config["model"], config["training"]
    fields, rank = model["num_fields"], model["rank"]
    rows0 = deepfm.init_rows(ctx.seed, uniq, model["bucket"], rank,
                             training["init_std"])
    head0 = deepfm.init_head(ctx.seed, deepfm.head_dims(
        fields, rank, config["head"]["mlp_dims"]))
    want = deepfm.train(
        rows0, head0, inv, vals, labels, rank=rank,
        steps=int(mix["check_steps"]),
        learning_rate=training["learning_rate"],
        reg_factors=training["reg_factors"],
        reg_linear=training["reg_linear"], reg_bias=training["reg_bias"],
        keep_after=(int(mix["head_steps"]),),
        matmul_precision=matmul_precision)
    return want, np.asarray(rows0)


def two_runs(ctx: Context, cfg, chips: int, uniq) -> tuple[dict, dict]:
    """The program's long and short check runs, ``(late, early)`` as
    :func:`compare` reads them; the tables of each go before the next
    thing allocates (the run's memory peak has to stay the program's)."""
    out = []
    for steps in (int(ctx.cell.mix["check_steps"]),
                  int(ctx.cell.mix["head_steps"])):
        params, losses = program_run(ctx, cfg, chips, steps)
        if params is None:
            raise RuntimeError("the check run saved no model")
        out.append({**taken(params, uniq), "losses": losses})
        del params
        gc.collect()
    return tuple(out)


def check_against_reference(ctx: Context, cfg, chips: int) -> dict:
    config, mix = ctx.cell.config, ctx.cell.mix
    uniq, counts, inv, vals, labels = one_batch(ctx, chips)
    t0 = time.perf_counter()
    late, early = two_runs(ctx, cfg, chips, uniq)
    took = {"cli_s": round(time.perf_counter() - t0, 2)}
    t0 = time.perf_counter()
    want, rows0 = reference_run(ctx, uniq, inv, vals, labels)
    took["reference_s"] = round(time.perf_counter() - t0, 2)
    verdict = compare(
        late, early, want, rows0, counts, steps=int(mix["check_steps"]),
        head_steps=int(mix["head_steps"]),
        learning_rate=config["training"]["learning_rate"], tol=mix)
    return {"took": took, "losses": late["losses"],
            "reference_losses": np.asarray(want["losses"]).tolist(),
            "unique_rows_max": int((counts > 0).sum(axis=1).max()),
            **verdict}


def hold_head(ctx: Context) -> tuple:
    """The program's head held to the configuration's file, before any
    work; returns its layer widths. ``registry_config`` compares numbers
    and strings, so two things are left to hold here:

    - the widths (a list in the file, a tuple in the registry);
    - what ``compute_dtype`` MEANS for the head's products. float32
      products on a TPU have to be asked for (``precision=HIGHEST``; the
      default is one bfloat16 pass over float32 operands), so the string
      alone says nothing; the precision is read from the head as the
      program lowers it. A program whose head multiplies below the
      declared precision does not run this configuration (the parent of
      the PR that added this cell is one), and the cell says so at once
      (SystemExit) where a check run would say ``correct: false`` a
      minute later."""
    import re

    import jax
    import jax.numpy as jnp

    config = ctx.cell.config
    cfg = registry_config(config)
    model, head = config["model"], config["head"]
    if (list(cfg.mlp_dims) != list(head["mlp_dims"])
            or head["activation"] != "relu"):
        raise SystemExit(
            f"benchmark: registry config {cfg.name!r} has head "
            f"{cfg.mlp_dims}; {config['name']}.json says {head}")
    dims = deepfm.head_dims(model["num_fields"], model["rank"],
                            head["mlp_dims"])
    mlp = [{"kernel": jax.ShapeDtypeStruct((a, b), jnp.float32),
            "bias": jax.ShapeDtypeStruct((b,), jnp.float32)}
           for a, b in zip(dims[:-1], dims[1:])]
    text = jax.jit(cfg.spec().deep_scores).lower(
        mlp, jax.ShapeDtypeStruct((8, dims[0]), jnp.float32)).as_text()
    stated = [re.search(r"precision = \[(\w+), (\w+)\]", line)
              for line in text.splitlines() if "dot_general" in line]
    want = {"float32": "HIGHEST", "bfloat16": "DEFAULT"}[
        model["compute_dtype"]]
    if len(stated) != len(dims) - 1 or not all(
            m and set(m.groups()) == {want} for m in stated):
        raise SystemExit(
            f"benchmark: {config['name']}.json declares compute_dtype "
            f"{model['compute_dtype']!r}, so the head's {len(dims) - 1} "
            f"products take precision {want}; this program's head lowers to "
            f"{[m.groups() if m else None for m in stated]}. It does not "
            "run this configuration. Nothing was run.")
    return dims


def run(ctx: Context) -> Result:
    """``drivers/train.py``'s run with this module's check in place of
    its own, then the head's device time from the trace."""
    dims = hold_head(ctx)
    real = train.check_against_reference
    train.check_against_reference = check_against_reference
    try:
        result = train.run(ctx)
    finally:
        train.check_against_reference = real
    batch = result.log["batch"] // result.log["chips"]
    result.log["deep_head_flops"] = flops.head_matmul_flops(batch, dims)
    xplane = (trace_reduce.find_xplane(ctx.trace_dir)
              if ctx.trace_dir is not None else None)
    if xplane is not None:
        found = deep_trace.head_seconds(
            xplane, deep_trace.head_shapes(batch, dims))
        if found is not None:
            result.log["deep_head"] = result.notes["deep_head"] = found
    return result
