"""Length of the cell's own ``setup/run`` (s): ``cli train`` from its
entry to the instant before its loop's first step, or the engine from
its construction to the end of its first ``warmup()``. Also leaves the
whole set-up's breakdown on stderr."""

from benchmark import setup_spans


def read(run):
    setup_spans.log_summary(run)
    return setup_spans.program_s(run)
