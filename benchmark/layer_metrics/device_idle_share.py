"""Share of the profiled span in which no operation ran on the device,
mean over the chips (%): ``1 - union of device-op intervals / span``."""


def read(run):
    if run.trace is None or not run.traced:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.traced["seconds"])
