"""From the end of the cell's own ``setup/run`` to the window's first
instant (s): the first dispatches (trace, lower, cache read, first
execution) and the warm lines or the warm traffic."""

from benchmark import setup_spans


def read(run):
    return setup_spans.warmup_s(run)
