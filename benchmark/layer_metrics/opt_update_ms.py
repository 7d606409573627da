"""Device self time of the table update (coalesce, gather, rule, write:
the program's ``opt/*`` named scopes) in the profiled span over the steps
logged in it, mean over the chips (ms). The AdaGrad driver reads it from
the trace before the harness deletes it (``benchmark/opt_trace.py``); a
run whose program or trace states no such scope has nothing here."""


def read(run):
    update = run.log.get("opt_update")
    if not update or not run.traced or not run.traced.get("steps"):
        return None
    return 1e3 * update["seconds"] / run.traced["steps"]
