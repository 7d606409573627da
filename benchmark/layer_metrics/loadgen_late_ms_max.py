"""The latest the benchmark's generator sent any request (ms). One stall
of the host shows here and not in the 99th percentile; a run whose value
is over a batch time (~27 ms) had its tails moved by it."""


def read(run):
    return run.log.get("stats", {}).get("late_max_ms")
