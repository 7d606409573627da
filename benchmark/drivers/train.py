"""The training cells: ``fm_spark_tpu.cli.main(["train", ...])`` in-process,
as ``chip_smoke.py`` drives it — one process, no child.

The adapter passes the configuration, the traffic (``--synthetic``,
``--batch-per-chip``, ``--seed``), the cadence it needs to read the clock
(``--steps``, ``--log-every``) and what keeps a run from writing
(``--obs-dir none``, ``--test-fraction 0``, no checkpoints), and nothing
else: what the program does with a configuration by default is what a
cell measures.

Set-up, in order: (1) the benchmark's copy of the traffic arithmetic is
compared with the program's on a small seeded draw; (2) the check run —
``check_steps`` steps on a set of exactly one batch, so that every step
is the whole set whatever the shuffle — whose losses and updated rows
are compared with the plain reference; (3) the measured call starts,
compiles (or loads from the cache) and logs ``warm_lines`` lines. The
window opens at that line and closes by the clock: the first log line
later than ``--seconds`` after it raises out of ``cli.main`` (the
program has no "run for a time" flag, and a faster program must not
measure a shorter window). Every log line follows a device-to-host fetch
of its step's loss, so a line's arrival — on the benchmark's own clock —
says those steps are done.
"""

from __future__ import annotations

import contextlib
import gc
import importlib
import io
import json
import math
import sys
import time

import numpy as np

from benchmark import synthetic
from benchmark.drivers.registry import registry_config
from benchmark.harness import Context, Result, log, start_trace, trace_span
from benchmark.reference import sgd

CAPTURE = "benchmark-capture"      # --model-out of the check run; never a path


class WindowClosed(BaseException):
    """Raised through ``cli.main`` when the measured window is over
    (BaseException: nothing in the program may swallow it)."""


def traffic_matches(seed: int, fields: int) -> bool:
    """Does the program's generator still draw what the benchmark's copy
    draws? (A small seeded draw of both, by checksum.)"""
    from fm_spark_tpu import data as data_lib

    args = (256, fields * 64, fields)
    ours = synthetic.checksum(*synthetic.synthetic_ctr(*args, seed=seed))
    theirs = synthetic.checksum(*data_lib.synthetic_ctr(*args, seed=seed))
    return ours == theirs


class _Stdout(io.TextIOBase):
    """What ``cli.main`` prints: passed on to stderr, and every JSON
    object handed to ``on_doc`` with the instant its line arrived."""

    def __init__(self, on_doc):
        self._on_doc = on_doc
        self._buf = ""

    def write(self, s: str) -> int:
        now = time.perf_counter()
        sys.stderr.write(s)
        self._buf += s
        while "\n" in self._buf:
            line, self._buf = self._buf.split("\n", 1)
            if line.startswith("{"):
                try:
                    doc = json.loads(line)
                except json.JSONDecodeError:
                    continue
                self._on_doc(now, doc)
        return len(s)

    def flush(self) -> None:
        sys.stderr.flush()


def run_cli(argv: list[str], on_doc) -> None:
    from fm_spark_tpu import cli

    log("cli", " ".join(argv))
    with contextlib.redirect_stdout(_Stdout(on_doc)):
        rc = cli.main(argv)
    if rc != 0:
        raise RuntimeError(f"cli {argv[0]} returned {rc}")


def train_argv(cfg_name: str, *, rows: int, batch_per_chip: int, seed: int,
               steps: int, log_every: int) -> list[str]:
    return ["train", "--config", cfg_name, "--synthetic", str(rows),
            "--batch-per-chip", str(batch_per_chip), "--seed", str(seed),
            "--steps", str(steps), "--log-every", str(log_every),
            "--obs-dir", "none", "--test-fraction", "0"]


# ------------------------------------------------------------ the check run


def check_against_reference(ctx: Context, cfg, chips: int) -> dict:
    """``check_steps`` steps on one batch through ``cli train``, against
    the plain reference: the loss each step logged, the bias, and every
    row the batch touched."""
    from fm_spark_tpu import models

    config, mix = ctx.cell.config, ctx.cell.mix
    model, training = config["model"], config["training"]
    ref = importlib.import_module(f"benchmark.reference.{config['reference']}")
    fields, rank, bucket = model["num_fields"], model["rank"], model["bucket"]
    steps = int(mix["check_steps"])
    batch = training["batch_per_chip"] * chips

    losses: list[float] = []
    captured: dict = {}

    def on_doc(_now, doc):
        if "step" in doc and "loss" in doc:
            losses.append(doc["loss"])

    def capture(path, spec, params):
        captured["params"] = params

    # The program hands out parameters only by saving them; the check
    # takes them at that door instead of writing 2.7 GB.
    t_cli = time.perf_counter()
    real_save, models.save_model = models.save_model, capture
    try:
        run_cli(train_argv(cfg.name, rows=batch,
                           batch_per_chip=training["batch_per_chip"],
                           seed=ctx.seed, steps=steps, log_every=1)
                + ["--model-out", CAPTURE], on_doc)
    finally:
        models.save_model = real_save
    if "params" not in captured:
        return {"ok": False, "why": "the check run saved no model"}

    t0 = time.perf_counter()
    took = {"cli_s": round(t0 - t_cli, 2)}
    ids, vals, labels = synthetic.synthetic_ctr(
        batch, fields * bucket, fields, seed=ctx.seed)
    ids = synthetic.field_local(ids, bucket)
    uniq, counts, inv, n_uniq = sgd.touched(ids)
    took["rows_s"] = round(time.perf_counter() - t0, 2)
    # Take the touched rows and let the tables go before the reference
    # allocates: the run's memory peak has to stay the program's own.
    params = captured.pop("params")
    got_rows = np.stack([np.asarray(params["vw"][f][uniq[f]], np.float32)
                         for f in range(fields)])
    got_w0 = float(np.asarray(params["w0"]))
    del params
    gc.collect()
    factor_cols = ref.factor_columns(fields, rank)
    t1 = time.perf_counter()
    rows0 = sgd.init_rows(ctx.seed, uniq, bucket, factor_cols,
                          training["init_std"])
    rows0.block_until_ready()
    took["init_rows_s"] = round(time.perf_counter() - t1, 2)
    t1 = time.perf_counter()
    want_losses, want_rows, want_w0 = sgd.train(
        ref.scores, rank, factor_cols, rows0, inv, vals, labels,
        steps=steps, learning_rate=training["learning_rate"],
        lr_schedule=training["lr_schedule"],
        reg_factors=training["reg_factors"],
        reg_linear=training["reg_linear"], reg_bias=training["reg_bias"],
        chunk=min(int(mix["check_chunk"]), batch))
    rows0 = np.asarray(rows0)
    took["sgd_s"] = round(time.perf_counter() - t1, 2)

    out = {"took": took,
           "unique_rows_max": int(n_uniq.max()), "losses": losses}
    if len(losses) != steps or not all(map(math.isfinite, losses)):
        return {**out, "ok": False,
                "why": f"{len(losses)} finite-or-not losses for {steps} steps"}
    loss_err = float(np.max(np.abs(np.asarray(losses) - want_losses)
                            / np.abs(want_losses)))
    # Updated rows, as deltas from the initial rows. A float32 scatter-add
    # rounds once per occurrence of a row per step, and the program's does
    # lose that much: on the chip the hottest FFM rows (3,000 occurrences,
    # each adding a decay of under half an ulp) end up to 25% of the
    # largest delta off, 0.99 of half an ulp per occurrence (my chip run,
    # PR 22). So each element is allowed one ulp per occurrence per step
    # on top of rows_rtol of its block's largest delta; a row met once is
    # held to about the latter alone, which bfloat16 storage misses by
    # two orders.
    rtol = float(mix["rows_rtol"])
    live = counts > 0
    err = np.abs(got_rows - want_rows)
    delta = np.abs(want_rows - rows0)
    ulp = np.spacing(np.maximum(np.abs(want_rows),
                                np.abs(rows0)).astype(np.float32))
    worst = {}
    for name, cols in (("factors", slice(0, factor_cols)),
                       ("linear", slice(factor_cols, None))):
        scale = float(delta[..., cols][live].max())
        allowed = (rtol * scale
                   + steps * counts[..., None] * ulp[..., cols])
        worst[name] = {
            "largest_delta": scale,
            "err_over_largest_delta": float(err[..., cols][live].max()
                                            / max(scale, 1e-30)),
            "err_over_allowed": float(
                (err[..., cols] / np.maximum(allowed, 1e-30))[live].max()),
        }
    w0_err = abs(got_w0 - want_w0) / max(abs(want_w0), 1e-12)
    ok = (loss_err <= float(mix["loss_rtol"])
          and all(w["err_over_allowed"] <= 1.0 for w in worst.values())
          and w0_err <= float(mix["loss_rtol"]))
    return {**out, "ok": bool(ok), "loss_rel_err": loss_err,
            "w0_rel_err": w0_err, "rows": worst,
            "reference_losses": want_losses.tolist()}


# ------------------------------------------------------------- the window


class Window:
    """Reads the measured call's log lines on the benchmark's clock."""

    def __init__(self, ctx: Context, cache_misses):
        mix = ctx.cell.mix
        self.seconds = ctx.seconds
        self.warm_lines = int(mix["warm_lines"])
        self.trace_dir = ctx.trace_dir
        self.trace_after, self.trace_seconds = trace_span(mix, ctx.seconds)
        self._misses = cache_misses
        self.seen = 0
        self.t_open = None
        self.lines: list[tuple] = []        # (t, step, loss), opener first
        self.misses_open = self.misses_close = None
        self.traced = None                  # {"seconds", "steps"}
        self._trace_open = None             # (t, step) while profiling

    def on_doc(self, now: float, doc: dict) -> None:
        if "step" not in doc or "loss" not in doc:
            return
        self.seen += 1
        if self.t_open is None:
            if self.seen >= self.warm_lines:
                self.misses_open = self._misses()
                self.t_open = now = time.perf_counter()
                self.lines.append((now, doc["step"], doc["loss"]))
            return
        if now > self.t_open + self.seconds:
            self.misses_close = self._misses()
            self.stop_trace(now, doc["step"])
            raise WindowClosed
        self.lines.append((now, doc["step"], doc["loss"]))
        if self.trace_dir is None or self.traced is not None:
            return
        # Both ends of the profiled span sit right after a fetched loss:
        # the device is idle there and whole steps lie between them.
        if self._trace_open is None:
            if now >= self.t_open + self.trace_after:
                start_trace(self.trace_dir)
                self._trace_open = (time.perf_counter(), doc["step"])
        elif now >= self._trace_open[0] + self.trace_seconds:
            self.stop_trace(now, doc["step"])

    def stop_trace(self, now: float, step: int) -> None:
        if self._trace_open is None:
            return
        import jax

        jax.profiler.stop_trace()
        t0, step0 = self._trace_open
        self._trace_open = None
        self.traced = {"seconds": now - t0, "steps": step - step0}


def run(ctx: Context) -> Result:
    import jax

    from fm_spark_tpu.utils import compile_cache

    cell, mix = ctx.cell, ctx.cell.mix
    chips = jax.device_count()
    cfg = registry_config(cell.config)
    training = cell.config["training"]
    batch = training["batch_per_chip"] * chips
    notes: dict = {}
    split = {"backend_s": round(time.perf_counter() - ctx.t_start, 2)}

    traffic_ok = traffic_matches(ctx.seed, cell.config["model"]["num_fields"])
    t = time.perf_counter()
    check = check_against_reference(ctx, cfg, chips)
    split["check_s"] = round(time.perf_counter() - t, 2)
    notes["check"] = check
    notes["traffic_matches_program"] = traffic_ok

    window = Window(ctx, lambda: compile_cache.cache_stats()["misses"])
    t = time.perf_counter()
    try:
        run_cli(train_argv(cfg.name, rows=int(mix["rows"]),
                           batch_per_chip=training["batch_per_chip"],
                           seed=ctx.seed, steps=int(mix["steps"]),
                           log_every=int(mix["log_every"])),
                window.on_doc)
    except WindowClosed:
        pass
    else:
        raise RuntimeError(
            f"cli train ended after {mix['steps']} steps before the "
            f"{ctx.seconds:g} s window closed")
    gc.collect()
    split["load_place_warm_s"] = round(window.t_open - t, 2)
    setup_s = window.t_open - ctx.t_start
    notes["setup_split"] = split

    t_open, step_open, _ = window.lines[0]
    t_last, step_last, _ = window.lines[-1]
    steps = step_last - step_open
    strides = {b[1] - a[1] for a, b in zip(window.lines, window.lines[1:])}
    losses = [loss for _, _, loss in window.lines]
    bad_lines = sum(not math.isfinite(x) for x in losses)
    compiles = window.misses_close - window.misses_open
    rate = (steps * batch / (t_last - t_open) / chips
            if steps > 0 else float("nan"))
    correct = (check["ok"] and traffic_ok and compiles == 0
               and bad_lines == 0 and steps > 0
               and strides <= {int(mix["log_every"])})
    notes.update(window_lines=len(window.lines), window_steps=steps,
                 window_span_s=round(t_last - t_open, 4),
                 log_strides=sorted(strides), loss_first=losses[0],
                 loss_last=losses[-1], compiles_in_window=compiles)
    return Result(
        correct=bool(correct), attempted=int(steps),
        failed=int(bad_lines * int(mix["log_every"])), setup_s=setup_s,
        end_to_end={"train_samples_per_s_per_chip": rate},
        counters={"compile_misses": compiles},
        log={"steps": steps, "batch": batch, "chips": chips},
        traced=window.traced, notes=notes)
