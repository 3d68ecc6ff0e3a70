"""lane_occupancy (%, layer: sweep engine): of the loop trips each lane
was carried through, the share in which it still had work — the engine's
telemetry (`run_batch(telemetry=...)`: active_trips over lane_trips) for
the traced request. The rest is lanes waiting for the slowest lane of
their shard."""


def read(run):
    recs = run.telemetry
    if not recs:
        return None
    lane_trips = sum(r["lane_trips"] for r in recs)
    return 100.0 * sum(r["active_trips"] for r in recs) / lane_trips
