"""The xDeepFM training cell: ``drivers/train.py``'s run — the same adapter,
the same window, the same clock — with the check replaced, because that
driver's reference knows tables, a bias and plain SGD only: this
configuration adds a Compressed Interaction Network and a DNN under Adam
beside the tables (``reference/xdeepfm.py``).

Before any work the program is held to the configuration's file
(:func:`hold_program`): the registry's sizes, the CIN's feature maps and
the DNN's widths, and the fused step AS LOWERED at the cell's sizes: every
one of its products at the precision ``compute_dtype`` declares
(``precision=HIGHEST`` has to be asked for on a TPU) and every table an
argument ``[bucket, 128]`` float32 on the chip (an 11-wide row is held
lane-padded by the loop where the device lays it out otherwise,
``models/rows.held_form``). A program that differs does not run this
configuration, and the cell says so at once (SystemExit), as it does where
the registry has no such configuration (the parent of the PR that added
the cell). The compiled step's memory, as the compiler counts it, goes
into the run's notes.

The check then runs ``cli train`` on a set of exactly one batch,
parameters taken where the program saves them — TWICE: ``check_steps``
steps (8, the mix's) and ``early_steps`` (2), each compared with the
reference after as many: every logged loss, every touched row and every
dense leaf (the bias, the CIN's kernels and output vector, the DNN's
kernels, biases and output vector); the CIN as the step computed it,
the batch's sum of each pooled map that every log line carries, at each
step of the short run against the reference's at the same step; and, a
second witness, ``spec.cin`` on the short run's state against the
reference's on the same state, example by example (:func:`compare` says
why the trajectory alone cannot read the CIN). The
program hands out no Adam moments
(``--model-out`` writes parameters), so they are held through the
parameters at two horizons (``tests/test_field_xdeepfm.py`` compares the
moments themselves, at a small size). :func:`compare` has the limits and
why each.

A traced run also reads the CIN's device time out of the profiler's trace
before it returns (``benchmark/cin_trace.py``): the harness deletes the
trace once it has reduced it to ten op families.
"""

from __future__ import annotations

import gc
import re
import time

import jax
import numpy as np

from benchmark import cin_flops, cin_trace, trace_reduce
from benchmark.drivers import train, train_deep
from benchmark.drivers.registry import registry_config
from benchmark.harness import Context, Result, log
from benchmark.reference import xdeepfm

DENSE = ("w0", "cin", "mlp")


def shapes_of(config: dict) -> dict:
    """The sizes the file states."""
    model, head = config["model"], config["head"]
    return {"fields": model["num_fields"], "rank": model["rank"],
            "bucket": model["bucket"],
            "cin_layers": tuple(head["cin_layers"]),
            "mlp_dims": tuple(head["mlp_dims"])}


def products(sizes: dict) -> int:
    """``dot_general``s of the lowered step: forward, the input's gradient
    and the kernel's of each CIN layer, each DNN layer and each output
    vector."""
    return 3 * (len(sizes["cin_layers"]) + len(sizes["mlp_dims"]) + 2)


def hold_program(ctx: Context):
    """The program held to the configuration's file, before any work
    (module docstring); returns the registry's configuration and what the
    compiler said of the step's memory."""
    config = ctx.cell.config
    name = config["name"]
    try:
        cfg = registry_config(config)
    except KeyError as e:
        raise SystemExit(
            f"benchmark: this program's registry has no such configuration "
            f"({e}); it does not run this cell. Nothing was run.") from e
    head, sizes = config["head"], shapes_of(config)
    said = {"cin_layers": tuple(getattr(cfg, "cin_layers", ())),
            "mlp_dims": tuple(cfg.mlp_dims)}
    want = {k: sizes[k] for k in said}
    if (said != want or head["activation"] != "relu"
            or head["cin_activation"] != "identity" or head["cin_bias"]):
        raise SystemExit(
            f"benchmark: registry config {cfg.name!r} has {said}; "
            f"{name}.json says {want}. Nothing was run.")

    from fm_spark_tpu import sparse

    batch = config["training"]["batch_per_chip"]
    lowered = sparse.lower_field_sparse_step(
        cfg.spec(), cfg.train_config(), batch)
    text = lowered.as_text()
    stated = [re.search(r"precision = \[(\w+), (\w+)\]", line)
              for line in text.splitlines() if "dot_general" in line]
    precision = {"float32": "HIGHEST", "bfloat16": "DEFAULT"}[
        config["model"]["compute_dtype"]]
    if len(stated) != products(sizes) or not all(
            m and set(m.groups()) == {precision} for m in stated):
        raise SystemExit(
            f"benchmark: {name}.json declares compute_dtype "
            f"{config['model']['compute_dtype']!r}, so the step's "
            f"{products(sizes)} products take precision {precision}; this "
            f"program's step lowers {len(stated)} of them, to "
            f"{sorted({m.groups() if m else None for m in stated})}. It "
            "does not run this configuration. Nothing was run.")
    from fm_spark_tpu.models import rows as rows_lib

    signature = text[text.index("@main("):].split(") -> (")[0]
    row = (sizes["bucket"], sizes["rank"] + 1)
    lanes = (128 if rows_lib.held_form(row, np.float32, jax.devices()[0],
                                       True) == "padded" else row[1])
    table = f"tensor<{sizes['bucket']}x{lanes}xf32>"
    if (config["model"]["param_dtype"] != "float32"
            or signature.count(table) != sizes["fields"]):
        raise SystemExit(
            f"benchmark: {name}.json holds {sizes['fields']} tables "
            f"[{row[0]}, {row[1]}] float32, held here as [{row[0]}, "
            f"{lanes}]; this program's step takes {signature.count(table)} "
            "such arguments. Nothing was run.")
    memory = lowered.compile().memory_analysis()
    said = {k: int(getattr(memory, f"{k}_size_in_bytes", 0))
            for k in ("argument", "output", "temp", "alias")}
    log("compiled step:", said)
    return cfg, said


# ------------------------------------------------------------ the check run


def leaves(dense: dict) -> dict:
    """Every dense leaf by its path (``cin/layers/0``, ``mlp/out``, ...),
    as float32 NumPy."""
    return {"/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path): np.asarray(leaf, np.float32)
            for path, leaf in jax.tree_util.tree_leaves_with_path(
                {key: dense[key] for key in DENSE})}


def taken(params, uniq) -> dict:
    """What the comparison reads of a saved model, as NumPy (so that the
    tables can go): the rows ``uniq`` of each table and every dense
    leaf."""
    return {"rows": np.stack([np.asarray(params["vw"][f][uniq[f]],
                                         np.float32)
                              for f in range(len(uniq))]),
            "dense": leaves(params)}


def program_run(ctx: Context, cfg, chips: int, steps: int, uniq) -> dict:
    """``steps`` steps of ``cli train`` on exactly one batch: what
    :func:`taken` reads of the model it saved, every logged loss, and the
    ``cin_pooled`` every log line carries (the step's own ``p+`` summed
    over the batch, ``FieldXDeepFMSpec.head_scores_and_stats``)."""
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
        train.run_cli(
            train.train_argv(cfg.name,
                             rows=training["batch_per_chip"] * chips,
                             batch_per_chip=training["batch_per_chip"],
                             seed=ctx.seed, steps=steps, log_every=1)
            + ["--model-out", train.CAPTURE], on_doc)
    finally:
        models.save_model = real_save
    params = captured.pop("params", None)
    if params is None:
        raise RuntimeError("the check run saved no model")
    out = {**taken(params, uniq), "losses": [doc["loss"] for doc in lines],
           "pooled": [doc.get("cin_pooled") for doc in lines]}
    # The tables go before the next thing allocates: the run's memory
    # peak has to stay the program's own.
    del params
    gc.collect()
    return out


def two_runs(ctx: Context, cfg, chips: int, uniq) -> tuple[dict, dict]:
    """The program's long and short check runs, ``(late, early)``."""
    return tuple(program_run(ctx, cfg, chips, int(ctx.cell.mix[key]), uniq)
                 for key in ("check_steps", "early_steps"))


def reference_run(ctx: Context, uniq, inv, vals, labels,
                  precision: str = "float32") -> tuple[dict, dict]:
    """The long check run's steps through ``reference/xdeepfm.py``, the
    state kept as it stood after the short run's: ``(want, start)``, both
    as :func:`compare` reads them."""
    config, mix = ctx.cell.config, ctx.cell.mix
    training, sizes = config["training"], shapes_of(config)
    rows0 = xdeepfm.init_rows(ctx.seed, uniq, sizes["bucket"], sizes["rank"],
                              training["init_std"])
    dense0 = xdeepfm.init_dense(ctx.seed, sizes["fields"], sizes["rank"],
                                sizes["cin_layers"], sizes["mlp_dims"])
    start = {"rows": np.asarray(rows0), "dense": leaves(dense0)}
    t0 = time.perf_counter()
    out = xdeepfm.train(
        rows0, dense0, inv, vals, labels, rank=sizes["rank"],
        steps=int(mix["check_steps"]),
        learning_rate=training["learning_rate"],
        reg_factors=training["reg_factors"],
        reg_linear=training["reg_linear"], reg_bias=training["reg_bias"],
        block=min(int(mix["check_chunk"]), inv.shape[0]),
        keep_after=(int(mix["early_steps"]),), precision=precision)
    log(f"reference: {mix['check_steps']} steps in "
        f"{time.perf_counter() - t0:.1f} s")

    def as_run(state, upto=None):
        return {"rows": state["rows"], "dense": leaves(state["dense"]),
                "losses": out["losses"][:upto],
                "pooled": out["pooled"][:upto]}

    early = int(mix["early_steps"])
    want = {**as_run(out), "early": as_run(out["after"][early], early)}
    return want, start


def cin_inputs(run: dict, inv, vals, rank: int) -> np.ndarray:
    """``h = concat_f e_f`` ``[B, F * rank]`` of a check run's rows: the
    batch's embeddings as the run left them."""
    rows = run["rows"]
    return np.concatenate([rows[f][inv[:, f], :rank] * vals[:, f:f + 1]
                           for f in range(rows.shape[0])],
                          axis=1).astype(np.float32)


def cin_kernels(run: dict) -> list:
    layers = sorted((k for k in run["dense"] if k.startswith("cin/layers/")),
                    key=lambda k: int(k.rsplit("/", 1)[1]))
    return [run["dense"][k] for k in layers]


def program_cin(cfg, run: dict, inv, vals) -> np.ndarray:
    """``p+`` ``[B, sum_k H_k]`` as the program's CIN (``spec.cin`` of
    ``spec.cin_input``, what the step's head calls) computes it on a check
    run's embeddings and kernels."""
    import jax.numpy as jnp

    spec = cfg.spec()
    h = jnp.asarray(cin_inputs(run, inv, vals, spec.rank))
    kernels = [jnp.asarray(w) for w in cin_kernels(run)]
    return np.asarray(jax.jit(lambda ks, h: spec.cin(ks, spec.cin_input(h)))(
        kernels, h))


def reference_cin(run: dict, inv, vals, rank: int, block: int,
                  precision: str = "float32") -> np.ndarray:
    """The same as :func:`program_cin` by ``reference/xdeepfm.py``'s
    ``cin_pooled``, ``block`` examples at a time."""
    h = cin_inputs(run, inv, vals, rank)
    x0 = h.reshape(h.shape[0], -1, rank)
    kernels = cin_kernels(run)
    pooled = jax.jit(lambda ks, x: xdeepfm.cin_pooled(ks, x, precision))
    with jax.default_matmul_precision("highest"):
        return np.concatenate([np.asarray(pooled(kernels, x0[lo:lo + block]))
                               for lo in range(0, len(x0), block)])


def cin_errors(got: np.ndarray, want: np.ndarray, cin_layers) -> dict:
    """Each CIN layer's pooled maps ``p^k``, program against reference:
    ``|got - want| / |want|`` over the leading axis and the layer's maps
    (Frobenius norms)."""
    out, lo = {}, 0
    for k, h in enumerate(cin_layers, start=1):
        g, w = got[:, lo:lo + h], want[:, lo:lo + h]
        out[f"p{k}"] = float(np.linalg.norm(g - w)
                             / max(float(np.linalg.norm(w)), 1e-30))
        lo += h
    return out


def compare_dense(got: dict, want: dict, start: dict, *, steps: int,
                  learning_rate: float, mean_rates: float) -> dict:
    """Every dense leaf after ``steps`` steps, in LEARNING RATES: Adam
    moves every element by about one a step whatever its gradient's size,
    so that is the unit in which a wrong step shows.

    - the MEAN distance of a leaf's elements may be ``mean_rates``: the
      distance of single elements has a heavy tail in every run (an
      element whose gradient's terms cancel takes the sign of the
      rounding, and Adam moves it a whole rate that way), which a mean
      does not read and a product in lower precision, a missing term or
      another rule, each of which moves every element, does;
    - no element may be further off than Adam can move it
      (``train_deep.ADAM_STEP_CAP`` rates a step);
    - a leaf the reference moved has to have moved: its distance from
      ``start`` is more than half the reference's."""
    out = {}
    for name, w in want.items():
        rates = np.abs(got[name] - w) / learning_rate
        moved = float(np.abs(got[name] - start[name]).mean()
                      / max(float(np.abs(w - start[name]).mean()), 1e-30))
        out[name] = {"mean_rates": float(rates.mean()),
                     "max_rates": float(rates.max()),
                     "moved_share": moved,
                     "over_allowed": float(max(
                         rates.mean() / mean_rates,
                         rates.max() / (train_deep.ADAM_STEP_CAP * steps),
                         0.5 / max(moved, 1e-30)))}
    return out


def step_cin_errors(got: list, want: np.ndarray, cin_layers) -> dict | None:
    """The ``cin_pooled`` each logged step of a check run reported (the
    batch's sum of each of the step's own pooled maps) against the
    reference's at the same step: :func:`cin_errors` a step, the largest
    over the steps; None where a step reported none."""
    if not got or any(g is None for g in got):
        return None
    out: dict = {}
    for g, w in zip(got, want):
        for k, err in cin_errors(np.asarray(g, np.float64)[None],
                                 np.asarray(w, np.float64)[None],
                                 cin_layers).items():
            out[k] = max(out.get(k, 0.0), err)
    return out


def compare(late: dict, early: dict, want: dict, start: dict,
            counts: np.ndarray, forward: dict, *, steps: int,
            early_steps: int, learning_rate: float, tol: dict,
            cin_layers) -> dict:
    """The program's two check runs (``losses``, ``pooled``, ``rows`` ``[F,
    U, w]``, ``dense``: ``late`` after ``steps`` steps, ``early`` after
    ``early_steps``) against the reference (:func:`reference_run`'s
    ``want``, its ``early`` state beside it); ``counts[f, u]`` says how
    often the batch met a row (0: padding). Each limit is ``tol``'s, the
    mix's; the early run is held to the tight ones and the late run to what
    Adam's divergence leaves of them (``drivers/train_deep.py`` says why
    two float32 runs under Adam drift apart by step; PERF.md section 4 has
    both readings of each limit):

    - ``loss_rtol`` (early), ``loss_rtol_late``: every logged loss,
      relative; a float32 mean of some 0.69 that Adam moves by parts in
      ten thousand a step, read to a few of its ulps early;
    - rows (``train_deep.compare_rows``: a share of the block's largest
      delta plus the walk of a float32 scatter-add's rounding):
      ``rows_rtol`` early, ``rows_rtol_late`` late, because the pullback
      carries the dense leaves' divergence into every row;
    - the dense leaves (:func:`compare_dense`): ``head_mean_rates`` early,
      ``head_mean_rates_late`` late;
    - ``cin_pooled_rtol``: ``step`` (:func:`step_cin_errors`), each CIN
      layer's pooled maps summed over the batch as the TIMED STEP computed
      them at each step of the early run, against the reference's at the
      same step. At this configuration's initial values ``X^k`` scales as
      the embeddings (``N(0, 0.01)``) to the power ``k + 1``: the CIN's
      share of the logit, and of every gradient but its own kernels', is
      under float32 rounding of the rest, and L2 outweighs the data in the
      kernels' gradients, so what the CIN computes hardly moves the
      trajectory. This reads the CIN as the step computes it, whatever
      computes it there;
    - ``cin_rtol``: ``forward`` (:func:`cin_errors`), each layer's pooled
      maps of every example as ``spec.cin`` computes them on the early
      run's state, against the reference's on the same state: a second
      witness, example by example, of the function the step's head calls."""
    out: dict = {}
    for name, run, ref, n, late_ in (
            ("early", early, want["early"], early_steps, ""),
            ("late", late, want, steps, "_late")):
        losses = np.asarray(run["losses"], np.float64)
        if len(losses) != n or not np.all(np.isfinite(losses)):
            return {"ok": False,
                    "why": f"{len(losses)} finite-or-not losses for {n} steps"}
        out[name] = {
            "loss_rel_err": float(np.max(
                np.abs(losses - ref["losses"]) / np.abs(ref["losses"]))),
            "rows": train_deep.compare_rows(
                run["rows"], ref["rows"], start["rows"], counts, steps=n,
                rtol=float(tol["rows_rtol" + late_]),
                ulps_per_root_occurrence=float(
                    tol["rows_ulps_per_root_occurrence"])),
            "dense": compare_dense(
                run["dense"], ref["dense"], start["dense"], steps=n,
                learning_rate=learning_rate,
                mean_rates=float(tol["head_mean_rates" + late_])),
        }
    for name, run in out.items():
        run["ok"] = bool(
            run["loss_rel_err"] <= float(
                tol["loss_rtol" + ("_late" if name == "late" else "")])
            and all(b["over_allowed"] <= 1.0 for group in ("rows", "dense")
                    for b in run[group].values()))
    stepped = step_cin_errors(early["pooled"], want["early"]["pooled"],
                              cin_layers)
    out["step"] = {"cin_rel_err": stepped,
                   "ok": stepped is not None and max(stepped.values())
                   <= float(tol["cin_pooled_rtol"])}
    out["forward"] = {"cin_rel_err": forward,
                      "ok": max(forward.values()) <= float(tol["cin_rtol"])}
    return {**out, "ok": bool(all(run["ok"] for run in out.values()))}


def check_against_reference(ctx: Context, cfg, chips: int) -> dict:
    config, mix = ctx.cell.config, ctx.cell.mix
    uniq, counts, inv, vals, labels = train_deep.one_batch(ctx, chips)
    t0 = time.perf_counter()
    late, early = two_runs(ctx, cfg, chips, uniq)
    took = {"cli_s": round(time.perf_counter() - t0, 2)}
    t0 = time.perf_counter()
    want, start = reference_run(ctx, uniq, inv, vals, labels)
    forward = cin_errors(
        program_cin(cfg, early, inv, vals),
        reference_cin(early, inv, vals, config["model"]["rank"],
                      min(int(mix["check_chunk"]), inv.shape[0])),
        config["head"]["cin_layers"])
    took["reference_s"] = round(time.perf_counter() - t0, 2)
    verdict = compare(
        late, early, want, start, counts, forward,
        steps=int(mix["check_steps"]),
        early_steps=int(mix["early_steps"]),
        learning_rate=config["training"]["learning_rate"], tol=mix,
        cin_layers=config["head"]["cin_layers"])
    return {"took": took, "losses": late["losses"],
            "reference_losses": np.asarray(want["losses"]).tolist(),
            "unique_rows_max": int((counts > 0).sum(axis=1).max()),
            **verdict}


def run(ctx: Context) -> Result:
    """``drivers/train.py``'s run with this module's check in place of its
    own, then the CIN's operations, the program's own gauges and the CIN's
    device time from the trace."""
    _, memory = hold_program(ctx)
    real = train.check_against_reference
    train.check_against_reference = check_against_reference
    try:
        result = train.run(ctx)
    finally:
        train.check_against_reference = real

    from fm_spark_tpu import obs

    sizes = shapes_of(ctx.cell.config)
    batch = result.log["batch"] // result.log["chips"]
    flops = cin_flops.step_matmul_flops(batch, sizes["fields"], sizes["rank"],
                                        sizes["cin_layers"])
    result.log["cin_flops"] = flops
    result.notes["compiled_step_bytes"] = memory
    # What the loop said of itself once its step was built.
    gauges = {name: obs.gauge(f"train/{name}").value for name in
              ("mxu_flops_per_step", "cin_outer_elems_per_step")}
    result.notes["gauges"] = gauges
    dnn = (sizes["fields"] * sizes["rank"], *sizes["mlp_dims"])
    dense_weights = (sum(a * b for a, b in zip(dnn[:-1], dnn[1:]))
                     + dnn[-1] + sum(sizes["cin_layers"]))
    want = {"mxu_flops_per_step": flops + 6 * batch * dense_weights,
            "cin_outer_elems_per_step": cin_flops.outer_elems(
                batch, sizes["fields"], sizes["rank"], sizes["cin_layers"])}
    if any(gauges[name] != value for name, value in want.items()):
        log(f"the program's gauges {gauges} are not the benchmark's {want}")
        result.correct = False
    xplane = (trace_reduce.find_xplane(ctx.trace_dir)
              if ctx.trace_dir is not None else None)
    if xplane is not None:
        found = cin_trace.cin_seconds(xplane)
        if found is not None:
            result.log["cin"] = result.notes["cin"] = found
        else:
            result.notes["cin"] = "the trace states no cin/*"
    return result
