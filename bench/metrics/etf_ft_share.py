"""etf_ft_share (%, layer: decision kernels): device time of the two
`etf_ft` Pallas kernels (search and push) over device busy time, in the
traced request and over all devices. Missing when no kernel event
matches the patterns in `bench/etf_ft.py`."""
from bench import devtrace, etf_ft


def read(run):
    ev = etf_ft.events(run)
    if not ev or not any(calls for _, calls in ev.values()):
        return None
    busy = sum(devtrace.total(devtrace.busy(d, run.trace_window))
               for d in run.trace["devices"].values())
    return 100.0 * sum(ns for ns, _ in ev.values()) / busy
