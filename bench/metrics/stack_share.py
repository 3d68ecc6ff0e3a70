"""stack_share (%, layer: host staging): the program's `run_batch.stack`
spans — fault-plan validation and `stack_workloads` of the request's
lanes, on the host — over the traced request. Missing where the program
records no spans."""
from bench import program_telemetry


def read(run):
    return program_telemetry.span_share(run, "run_batch.stack")
