"""Share of dispatched rows that were bucket padding (%):
``padded / (rows + padded)``."""


def read(run):
    rows = run.counters.get("serve.rows_total")
    if not rows:
        return None
    padded = run.counters.get("serve.padded_rows_total", 0.0)
    return 100.0 * padded / (rows + padded)
