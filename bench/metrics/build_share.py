"""build_share (%, layer: host build): the benchmark's host span around
the program's workload build (`workloads.build_workload` and the fault
plans) over the traced request."""
from bench import devtrace


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    spans = [(s, s + d) for n, s, d in run.trace["host"]
             if n == "bench.build"]
    return 100.0 * devtrace.total(devtrace.clip(spans, (lo, hi))) / (hi - lo)
