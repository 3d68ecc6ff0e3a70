"""trip_us (us, layer: device loop): device busy time of the traced
request per trip of the sweep's while loop, on the mean device — the mean
over devices of the busy time over the mean over devices of the trips.
The engine's telemetry gives trips summed over lanes (each lane counts
its shard's trips), so a device's trips are its lanes' share of them."""
from bench import devtrace


def read(run):
    if run.trace is None or not run.trace["devices"] or not run.telemetry:
        return None
    busy = [devtrace.total(devtrace.busy(d, run.trace_window))
            for d in run.trace["devices"].values()]
    lane_trips = sum(r["lane_trips"] for r in run.telemetry)
    lanes = sum(r["lanes"] for r in run.telemetry)
    trips_per_device = lane_trips / lanes
    return (sum(busy) / len(busy)) / 1e3 / trips_per_device
