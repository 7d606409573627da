"""Rows the coalescer put into one dispatch, mean over the window:
``serve.rows_total / serve.batches_total``."""


def read(run):
    batches = run.counters.get("serve.batches_total")
    if not batches:
        return None
    return run.counters["serve.rows_total"] / batches
