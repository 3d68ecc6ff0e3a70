"""What the program records about itself in the engine's telemetry
records (`run_batch(telemetry=...)`), which the harness collects for the
traced request: host spans (`spans`: name, parent, start and end on the
program's `time.perf_counter_ns()`) and the sweep loop's phase counters
(`phase_trips`, `fault_eval_trips`, `fault_fire_trips`).

A program that records none of these leaves the metrics that read them
out: every function here returns None then, never 0. Span durations are
on the program's clock and the traced request's span on the profiler's;
both count nanoseconds, so their ratio is a share.
"""


def span_ns(run, name: str):
    """Total duration of the program's spans `name` in the traced
    request, over every record; None where no record holds one."""
    found = [sp["end_ns"] - sp["start_ns"]
             for rec in run.telemetry or () for sp in rec.get("spans", ())
             if sp["name"] == name]
    return sum(found) if found else None


def span_share(run, name: str):
    """`span_ns` over the traced request (`bench.request`), in %."""
    if run.trace_window is None:
        return None
    ns = span_ns(run, name)
    if ns is None:
        return None
    lo, hi = run.trace_window
    return 100.0 * ns / (hi - lo)


def counter(run, key: str):
    """The counter `key` summed over the traced request's records; None
    where a record lacks it."""
    recs = run.telemetry or ()
    if not recs or any(key not in rec for rec in recs):
        return None
    return sum(rec[key] for rec in recs)
