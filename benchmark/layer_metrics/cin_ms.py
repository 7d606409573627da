"""Device self time of the xDeepFM step's Compressed Interaction Network
(the Hadamard products, their contraction with the kernels, sum pooling
and the output weight, forward and pullback: the scopes ``cin/outer``,
``cin/compress``, ``cin/pool``) in the profiled span over the steps logged
in it, mean over the chips (ms). The xDeepFM driver reads it from the
trace before the harness deletes it (``benchmark/cin_trace.py``); a run
whose program or trace states no such scope has nothing here."""


def read(run):
    cin = run.log.get("cin")
    if not cin or not run.traced or not run.traced.get("steps"):
        return None
    return 1e3 * cin["seconds"] / run.traced["steps"]
