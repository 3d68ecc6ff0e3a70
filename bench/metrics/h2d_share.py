"""h2d_share (%, layer: host to device): the program's
`run_batch.to_device` spans — the stacked workloads and fault plans
copied to the device, up to `block_until_ready` on them — over the
traced request. Missing where the program records no spans."""
from bench import program_telemetry


def read(run):
    return program_telemetry.span_share(run, "run_batch.to_device")
