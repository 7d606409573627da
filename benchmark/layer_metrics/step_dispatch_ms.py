"""Time for the jitted step call to return (ms): median
``train/dispatch``."""

from benchmark import program_spans


def read(run):
    return program_spans.train_ms(run, "train/dispatch")
