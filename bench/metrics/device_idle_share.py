"""device_idle_share (%, layer: device): the share of the traced request
in which no operation ran on the device — 1 minus the union of the
device-op intervals over the request's span, the mean over devices."""
from bench import devtrace


def read(run):
    if run.trace is None or not run.trace["devices"]:
        return None
    lo, hi = run.trace_window
    idle = [1 - devtrace.total(devtrace.busy(d, (lo, hi))) / (hi - lo)
            for d in run.trace["devices"].values()]
    return 100.0 * sum(idle) / len(idle)
