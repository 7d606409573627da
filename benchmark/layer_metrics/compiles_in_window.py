"""Compile-cache misses (fresh XLA compilations) between the window's
first and last instant; has to be 0."""


def read(run):
    return run.counters.get("compile_misses")
