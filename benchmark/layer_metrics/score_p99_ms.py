"""Request time from the due instant, 99th percentile (ms). Per-layer,
not end-to-end: on the chip's shared host a stall of 0.1-1.6 s, the
generator's as much as the engine's, falls into one run in two and moves
every tail by 10-35% on unchanged code (PERF.md, PR 22); no bound of at
most 10% can hold it."""


def read(run):
    return run.log.get("stats", {}).get("p99_ms")
