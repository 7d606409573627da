"""One micro-batch's execute (ms): median ``serve/batch`` (pad, dispatch,
device, the copy back) over the window's batches."""

from benchmark import program_spans


def read(run):
    return program_spans.score_median_ms(run, "serve/batch")
