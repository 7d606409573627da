"""The FFM-under-AdaGrad training cell: ``drivers/train.py``'s run — the
same adapter, the same window, the same clock — with the check replaced,
because that driver's reference (``reference/sgd.py``) applies ``p -= lr *
g`` and this configuration's update rule keeps state: a per-coordinate
accumulator beside every table (``reference/adagrad.py``).

The check runs ``cli train`` on a set of exactly one batch, as that
driver's does, parameters taken where the program saves them — TWICE:
``check_steps`` steps (8, the mix's) and ``early_steps`` (2), each
compared with the reference after as many: every logged loss, the bias,
every touched row as a delta from its initial row. The program hands out
no accumulators (``--model-out`` writes parameters), so they are held
through the rows, at two horizons: the rows after two steps depend on
``G_1`` and ``G_2``, and a rule that adds the wrong square, adds it late
or keeps it in fewer bits has moved a hot row by step 2 and goes on
moving it to step 8 (``tests/test_field_ffm_adagrad.py`` compares the
accumulators themselves, at a small size). The early run is held element
by element, the late run as a whole (a block's distance): :func:`compare`
has the limits and why each.

The run also counts: every log line of this configuration carries
``unique_rows`` (the batch's unique rows summed over the fields, what the
step's coalescing made of it); the check run's has to equal the
benchmark's own count of its one batch exactly, and the window's mean is
what ``benchmark/opt_bytes.py`` prices. A traced run reads the update's
device time out of the profiler's trace before it returns
(``benchmark/opt_trace.py``): the harness deletes the trace once it has
reduced it to ten op families, none of which is the update.
"""

from __future__ import annotations

import gc
import importlib
import time

import numpy as np

from benchmark import opt_bytes, opt_trace, trace_reduce
from benchmark.drivers import train, train_deep
from benchmark.drivers.registry import registry_config
from benchmark.drivers.train import CAPTURE, Window, run_cli, train_argv
from benchmark.harness import Context, Result
from benchmark.reference import adagrad

# What reference/adagrad.py implements of the configuration file's
# "update" group; a file that states another rule is not this driver's.
UPDATE = {"rule": "adagrad", "duplicates": "coalesced",
          "denominator": "updated_accumulator",
          "accumulator_dtype": "float32", "l2": "inside_gradient",
          "bias": "sgd"}


def one_batch(ctx: Context, chips: int):
    """The check run's one batch as the reference wants it
    (``drivers/train_deep.py``'s ``one_batch``), and the benchmark's own
    count of its unique rows over all fields: ``(uniq, counts, inv, vals,
    labels, unique_rows)``."""
    uniq, counts, inv, vals, labels = train_deep.one_batch(ctx, chips)
    return uniq, counts, inv, vals, labels, int((counts > 0).sum())


def program_run(ctx: Context, cfg, chips: int, steps: int, uniq) -> dict:
    """``steps`` steps of ``cli train`` on exactly one batch: what the
    comparison reads of it, as NumPy (so that the tables can go before
    the next thing allocates: the run's memory peak has to stay the
    program's own): the touched rows, the bias, and what every log line
    said."""
    from fm_spark_tpu import models

    training = ctx.cell.config["training"]
    lines: list[dict] = []
    captured: dict = {}

    def on_doc(_now, doc):
        if "step" in doc and "loss" in doc:
            lines.append(doc)

    def capture(path, spec, params):
        captured["params"] = params

    # The program hands out parameters only by saving them.
    real_save, models.save_model = models.save_model, capture
    try:
        run_cli(train_argv(cfg.name, rows=training["batch_per_chip"] * chips,
                           batch_per_chip=training["batch_per_chip"],
                           seed=ctx.seed, steps=steps, log_every=1)
                + ["--model-out", CAPTURE], on_doc)
    finally:
        models.save_model = real_save
    params = captured.pop("params", None)
    if params is None:
        raise RuntimeError("the check run saved no model")
    out = {
        "rows": np.stack([np.asarray(params["vw"][f][uniq[f]], np.float32)
                          for f in range(len(uniq))]),
        "w0": float(np.asarray(params["w0"])),
        "losses": [doc["loss"] for doc in lines],
        "unique_rows": [doc.get("unique_rows") for doc in lines],
    }
    del params
    gc.collect()
    return out


def two_runs(ctx: Context, cfg, chips: int, uniq) -> tuple[dict, dict]:
    """The program's long and short check runs, ``(late, early)``."""
    mix = ctx.cell.mix
    return tuple(program_run(ctx, cfg, chips, int(mix[key]), uniq)
                 for key in ("check_steps", "early_steps"))


def reference_run(ctx: Context, uniq, inv, vals, labels,
                  compute_dtype: str = "float32") -> tuple[dict, np.ndarray]:
    """The long run's steps through ``reference/adagrad.py``, the state
    kept as it stood after the short run's: ``(want, initial rows)``."""
    config, mix = ctx.cell.config, ctx.cell.mix
    model, training = config["model"], config["training"]
    ref = importlib.import_module(f"benchmark.reference.{config['reference']}")
    fields, rank = model["num_fields"], model["rank"]
    factor_cols = ref.factor_columns(fields, rank)
    rows0 = adagrad.init_rows(ctx.seed, uniq, model["bucket"], factor_cols,
                              training["init_std"])
    want = adagrad.train(
        ref.scores, rank, factor_cols, rows0, inv, vals, labels,
        steps=int(mix["check_steps"]),
        learning_rate=training["learning_rate"],
        reg_factors=training["reg_factors"],
        reg_linear=training["reg_linear"], reg_bias=training["reg_bias"],
        init_accumulator=training["adagrad_init_accumulator"],
        keep_after=(int(mix["early_steps"]),), compute_dtype=compute_dtype)
    return want, np.asarray(rows0)


def step_per_residual(training: dict) -> float:
    """The most a weight moves for a unit's change in ONE occurrence's
    residual: ``eta / sqrt(G)`` is at most ``eta / sqrt(G0)``, and the
    mean divides every occurrence by the batch. The recipe's ``G0`` is
    ``1 / B^2``, so at the cell's sizes this is ``eta``; a rehearsal at
    another batch keeps the registry's ``G0`` and it is not."""
    return training["learning_rate"] / (
        training["batch_per_chip"]
        * float(np.sqrt(training["adagrad_init_accumulator"])))


def compare_rows(got, want, start, counts, *, steps: int, rtol: float,
                 step_per_residual: float,
                 ulps_per_occurrence: float) -> dict:
    """Every touched row after ``steps`` steps, as deltas from ``start``,
    factor and linear columns apart. An element may be off by ``rtol`` of
    ITS ROW's largest delta (in that block), plus, per occurrence of the
    row per step, ``ulps_per_occurrence`` ulps of one times
    ``step_per_residual`` (:func:`step_per_residual`: ``eta`` at the
    cell's sizes).

    The scale is the row's and not the block's, because this rule's
    deltas span three orders by design: a row met once moves by about
    ``eta`` times its per-example gradient (1e-3), a row met thousands of
    times by up to ``eta`` a step, and a limit scaled by the largest would
    hold the cold rows to nothing. The ulps are what the coalesced sum
    costs. Each of its terms is a residual times a value, of order one
    before the mean's 1/B; program and reference each evaluate the
    sigmoid in it (the chip's is good to a few ulps of one) and round
    it; the program then adds a row's occurrences in segment order,
    autodiff's scatter-add in another; and ``eta / sqrt(G)``, at most
    ``eta / sqrt(G0) = eta * B``, turns the mean gradient's error into at
    most ``eta`` times the per-example one. That is all of a delta where a row's
    occurrences cancel (a linear weight met twice whose residuals sum to
    2e-6 moves by 4e-7): there the two runs differ by up to 8.2 such
    ulps per occurrence per step on the chip and 11.4 on the CPU
    (PERF.md section 6 has the seeds), and bfloat16 accumulators by 480.
    ``drivers/train.py`` allows an ulp of the weight per occurrence per
    step for the same reason, the rounding of its scatter-add."""
    live = counts > 0
    err = np.abs(got - want)
    delta = np.abs(want - start)
    per_occurrence = (ulps_per_occurrence * step_per_residual
                      * float(np.spacing(np.float32(1.0))))
    factor_cols = want.shape[-1] - 1
    out = {}
    for name, cols in (("factors", slice(0, factor_cols)),
                       ("linear", slice(factor_cols, None))):
        scale = delta[..., cols].max(axis=-1, keepdims=True)
        allowed = rtol * scale + steps * counts[..., None] * per_occurrence
        over = np.where(live[..., None],
                        err[..., cols] / np.maximum(allowed, 1e-30), 0.0)
        worst = np.unravel_index(np.argmax(over), over.shape)
        out[name] = {
            "largest_delta": float(delta[..., cols][live].max()),
            "smallest_row_delta": float(scale[live].min()),
            "err_over_row_delta": float(
                (err[..., cols] / np.maximum(scale, 1e-30))[live].max()),
            "over_allowed": float(over.max()),
            "worst_row_count": int(counts[worst[0], worst[1]]),
        }
    return out


def rows_distance(got, want, start, counts) -> dict:
    """How far the touched rows are from the reference's AS A WHOLE: per
    block (factors, linear) the norm of the error over the norm of the
    reference's deltas, ``|got - want| / |want - start|`` over every
    touched row. 1 where the program did not move or moved by another
    rule; what a few rows do, however far they stray, it hardly sees."""
    live = (counts > 0)[..., None]
    err = np.where(live, got - want, 0.0).astype(np.float64)
    delta = np.where(live, want - start, 0.0).astype(np.float64)
    factor_cols = want.shape[-1] - 1
    return {name: float(np.linalg.norm(err[..., cols])
                        / max(np.linalg.norm(delta[..., cols]), 1e-300))
            for name, cols in (("factors", slice(0, factor_cols)),
                               ("linear", slice(factor_cols, None)))}


def early_of(want: dict, early_steps: int) -> dict:
    """The reference's run as the short run sees it."""
    return {**want["after"][early_steps],
            "losses": want["losses"][:early_steps]}


def compare(late: dict, early: dict, want: dict, start_rows: np.ndarray,
            counts: np.ndarray, *, steps: int, early_steps: int,
            learning_rate: float, tol: dict,
            step_per_residual: float | None = None) -> dict:
    """The program's two check runs (``losses``, ``rows`` ``[F, U, w]``,
    ``w0``: ``late`` after ``steps`` steps, ``early`` after
    ``early_steps``) against the reference (``reference.adagrad.train``'s
    result with ``after[early_steps]``); ``counts[f, u]`` says how often
    the batch met a row (0: padding). Each limit is ``tol``'s, the mix's.

    The EARLY run is held element by element, tightly: after two steps
    the program and the reference are two float32 evaluations of the same
    mathematics, and what the wrong rule, the wrong square, the stale
    denominator or fewer bits in the accumulators do to a row is tens to
    millions of times what rounding does.

    The LATE run cannot be. The check trains on ONE batch eight times
    over at ``eta`` 0.2: every coordinate of a hot row jumps by up to 0.2
    a step, the scores reach 20 to 50 by steps 5 to 7 and the loss RISES
    (0.69 to 2 and more), so the trajectory is unstable: from step 3 the
    distance between two float32 runs of it grows 3 to 5 times a step,
    by a factor that differs from seed to seed (the loss's error by step
    8 spans 7e-7 to 2.4e-4 over some seventy seeds on the chip; the worst
    ELEMENT of the worst row 2e-4 to 0.07 of its row's delta, against 1
    for plain SGD: no limit on it both passes every seed and catches a
    fault). What stays apart is the rows' distance as a whole
    (:func:`rows_distance`): 4.3e-4 at most over those seeds, 1 for
    another rule. So the late run is held as a whole, by limits sited
    midway (on the log scale) between the largest correct reading and
    the smallest reading of a wrong rule; what rounding decides, and
    bfloat16 accumulators, which drift less than that by step 8, are the
    early run's to catch. PERF.md section 6 has both readings of each.

    - ``loss_rtol`` (early), ``loss_rtol_late``: every logged loss,
      relative. A mean of 8,192 float32 terms of about 0.7 (late: 2 and
      more); it holds what the rows' limits would miss, a score or a
      loss that is another function.
    - ``w0_rates``, ``w0_rates_late``: the bias, in LEARNING RATES (it
      moves by ``eta`` times the mean of 8,192 residuals a step, so that
      is the unit in which a wrong bias rule shows, whatever its size);
      late it carries the drift of every residual.
    - rows, element by element, early only (:func:`compare_rows`):
      ``rows_rtol`` of the row's largest delta plus
      ``rows_ulps_per_occurrence`` times ``step_per_residual`` (left
      out: ``learning_rate``, the recipe's ``G0 = 1 / B^2``). It holds
      EVERY touched row: one that was dropped, doubled or set from a
      neighbour's chunk.
    - rows, as a whole (:func:`rows_distance`), each block's:
      ``rows_distance`` early, ``rows_distance_late``. Early it is what
      tells accumulators kept in fewer bits, a relative error of 1e-3 in
      every step of every row, from rounding, which lands here and
      there: 4e-4 against 3e-6 (9e-6 for the linear weights of the
      hottest rows), where the worst element reads 2e-3 against 2e-4."""
    out: dict = {}
    for name, run, ref, n in (
            ("early", early, early_of(want, early_steps), early_steps),
            ("late", late, want, steps)):
        losses = np.asarray(run["losses"], np.float64)
        if len(losses) != n or not np.all(np.isfinite(losses)):
            return {"ok": False,
                    "why": f"{len(losses)} finite-or-not losses for {n} steps"}
        loss_err = np.abs(losses - ref["losses"]) / np.abs(ref["losses"])
        out[name] = {
            "loss_rel_err": float(np.max(loss_err)),
            "loss_rel_err_by_step": [float(f"{e:.3g}") for e in loss_err],
            "w0_err_rates": abs(run["w0"] - ref["w0"]) / learning_rate,
            "rows_distance": rows_distance(run["rows"], ref["rows"],
                                           start_rows, counts),
        }
    early_, late_ = out["early"], out["late"]
    early_["rows"] = compare_rows(
        early["rows"], early_of(want, early_steps)["rows"], start_rows,
        counts, steps=early_steps, rtol=float(tol["rows_rtol"]),
        step_per_residual=(learning_rate if step_per_residual is None
                           else step_per_residual),
        ulps_per_occurrence=float(tol["rows_ulps_per_occurrence"]))
    early_["ok"] = bool(
        early_["loss_rel_err"] <= float(tol["loss_rtol"])
        and early_["w0_err_rates"] <= float(tol["w0_rates"])
        and all(b["over_allowed"] <= 1.0 for b in early_["rows"].values())
        and all(d <= float(tol["rows_distance"])
                for d in early_["rows_distance"].values()))
    late_["ok"] = bool(
        late_["loss_rel_err"] <= float(tol["loss_rtol_late"])
        and late_["w0_err_rates"] <= float(tol["w0_rates_late"])
        and all(d <= float(tol["rows_distance_late"])
                for d in late_["rows_distance"].values()))
    return {**out, "ok": bool(early_["ok"] and late_["ok"])}


def check_against_reference(ctx: Context, cfg, chips: int) -> dict:
    config, mix = ctx.cell.config, ctx.cell.mix
    uniq, counts, inv, vals, labels, unique_rows = one_batch(ctx, chips)
    t0 = time.perf_counter()
    late, early = two_runs(ctx, cfg, chips, uniq)
    took = {"cli_s": round(time.perf_counter() - t0, 2)}
    t0 = time.perf_counter()
    want, rows0 = reference_run(ctx, uniq, inv, vals, labels)
    took["reference_s"] = round(time.perf_counter() - t0, 2)
    verdict = compare(
        late, early, want, rows0, counts, steps=int(mix["check_steps"]),
        early_steps=int(mix["early_steps"]),
        learning_rate=config["training"]["learning_rate"], tol=mix,
        step_per_residual=step_per_residual(config["training"]))
    # What the step's coalescing counted is the benchmark's own count of
    # this batch, on every line of both runs: the number opt_bytes prices.
    said = late["unique_rows"] + early["unique_rows"]
    counted = all(u is not None and int(u) == unique_rows for u in said)
    return {"took": took, "losses": late["losses"],
            "reference_losses": np.asarray(want["losses"]).tolist(),
            "unique_rows": unique_rows, "unique_rows_logged": said[:1],
            "unique_rows_max": int((counts > 0).sum(axis=1).max()),
            **verdict, "ok": bool(verdict["ok"] and counted)}


def hold_update(ctx: Context) -> None:
    """The configuration file's ``update`` group held to what this
    driver's reference implements, before any work (``registry_config``
    holds ``model`` and ``training``, the accumulator's start among
    them, to the program's registry)."""
    stated = ctx.cell.config.get("update")
    if stated != UPDATE:
        raise SystemExit(
            f"benchmark: {ctx.cell.config['name']}.json states the update "
            f"{stated}; drivers/train_adagrad.py checks {UPDATE}. "
            "Nothing was run.")
    try:
        registry_config(ctx.cell.config)
    except KeyError as e:
        raise SystemExit(
            f"benchmark: this program's registry has no such configuration "
            f"({e}); it does not run this cell. Nothing was run.") from e


class CountingWindow(Window):
    """``drivers/train.py``'s window, which also keeps what each of its
    lines said of ``unique_rows``."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.unique_rows: list[float] = []

    def on_doc(self, now: float, doc: dict) -> None:
        super().on_doc(now, doc)      # raises when the window is over
        if self.t_open is not None and "unique_rows" in doc:
            self.unique_rows.append(float(doc["unique_rows"]))


def run(ctx: Context) -> Result:
    """``drivers/train.py``'s run with this module's check in place of
    its own and a window that counts, then the update's bytes and, from
    the trace, its device time."""
    hold_update(ctx)
    windows: list[CountingWindow] = []

    def window(*args, **kwargs):
        windows.append(CountingWindow(*args, **kwargs))
        return windows[-1]

    real = train.check_against_reference, train.Window
    train.check_against_reference, train.Window = (
        check_against_reference, window)
    try:
        result = train.run(ctx)
    finally:
        train.check_against_reference, train.Window = real
    model = ctx.cell.config["model"]
    said = windows[-1].unique_rows
    if said:
        ref = importlib.import_module(
            f"benchmark.reference.{ctx.cell.config['reference']}")
        unique_rows = sum(said) / len(said)
        result.log["unique_rows"] = result.notes["unique_rows"] = unique_rows
        result.log["opt_update_bytes"] = opt_bytes.update_bytes(
            unique_rows=unique_rows,
            row_width=ref.row_width(model["num_fields"], model["rank"]),
            param_bytes=opt_bytes.DTYPE_BYTES[model["param_dtype"]])
    xplane = (trace_reduce.find_xplane(ctx.trace_dir)
              if ctx.trace_dir is not None else None)
    if xplane is not None:
        found = opt_trace.update_seconds(xplane)
        if found is not None:
            result.log["opt_update"] = result.notes["opt_update"] = found
        else:
            result.notes["opt_update"] = "the trace states no opt/* scope"
    return result
