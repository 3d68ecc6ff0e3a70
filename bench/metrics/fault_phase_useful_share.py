"""fault_phase_useful_share (%, layer: device loop (fault phases)): of the
sweep loop's trips that evaluated the kill and deadline bodies
(`fault_eval_trips`), the share on which some lane of the shard fired a
kill or a deadline drop (`fault_fire_trips`), summed over the traced
request's records. The rest is fault work a shard-level skip could save.
Missing where the program has no such counters or evaluated no body."""
from bench import program_telemetry


def read(run):
    evaluated = program_telemetry.counter(run, "fault_eval_trips")
    fired = program_telemetry.counter(run, "fault_fire_trips")
    if not evaluated or fired is None:
        return None
    return 100.0 * fired / evaluated
