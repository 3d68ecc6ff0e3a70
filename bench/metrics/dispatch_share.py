"""dispatch_share (%, layer: dispatch): the program's `run_batch.dispatch`
spans — from the engine call (dispatch mode, plan capabilities, the jitted
sweep) to its return, before the device has finished — over the traced
request. Missing where the program records no spans."""
from bench import program_telemetry


def read(run):
    return program_telemetry.span_share(run, "run_batch.dispatch")
