"""Device-busy time in the profiled span over the steps logged in it,
mean over the chips (ms)."""


def read(run):
    if run.trace is None or not run.traced or not run.traced.get("steps"):
        return None
    return 1e3 * run.trace["busy_s"] / run.traced["steps"]
