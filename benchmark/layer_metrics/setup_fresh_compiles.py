"""``compile/backend`` intervals without ``cache_hit`` before the window:
XLA compilations the persistent cache did not answer. 0 in a warm run."""

from benchmark import setup_spans


def read(run):
    return setup_spans.fresh_compiles(run)
