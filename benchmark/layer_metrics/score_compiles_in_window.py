"""``compiles_in_window`` of a scoring cell."""

from benchmark.layer_metrics import compiles_in_window


def read(run):
    return compiles_in_window.read(run)
