"""Device time in collective operations (all-to-all, all-reduce,
all-gather, collective-permute, by XLA op name) over device-busy time,
mean over the chips (%)."""


def read(run):
    if run.trace is None or not run.trace["busy_s"]:
        return None
    return 100.0 * run.trace["collective_s"] / run.trace["busy_s"]
