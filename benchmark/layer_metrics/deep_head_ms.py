"""Device self time of the dense head's operations (forward, the pullback,
Adam) in the profiled span over the steps logged in it, mean over the
chips (ms). The DeepFM driver reads it from the trace before the harness
deletes it (``benchmark/deep_trace.py``); a run whose program or trace
states no head has nothing here."""


def read(run):
    head = run.log.get("deep_head")
    if not head or not run.traced or not run.traced.get("steps"):
        return None
    return 1e3 * head["seconds"] / run.traced["steps"]
