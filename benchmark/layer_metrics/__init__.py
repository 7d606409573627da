"""One reader per per-layer metric: ``read(run) -> float | None``.

``run`` is the harness's record of one traced run (run.py ``LayerRun``);
a reader that finds nothing to read returns None and the harness leaves
the metric out of the line.
"""
