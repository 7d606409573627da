"""How late the benchmark's own generator sent, 99th percentile (ms):
send instant - due instant. A caveat, not a lever: above 1 ms the run's
latencies are partly the generator's."""


def read(run):
    return run.log.get("stats", {}).get("late_p99_ms")
