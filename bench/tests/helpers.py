"""A cell at a tiny size on the CPU, driven through the harness's run
with the look for a chip skipped."""
import faulthandler
import json
import os
import time

from bench import harness, traffic

TINY = {"mix_ids": [0, 5, 17, 33], "rate_ids": [0, 7, 13], "frames": 6}
SEED = 2**31 + 77


def tiny_cell(name):
    cell = harness.load_cell(name)
    cell.traffic = dict(cell.traffic, **TINY)
    return cell


def pending_x4_cell(size=TINY):
    """`soc19.das_grid_x4`, not yet in `BENCHMARK.json` (never measured
    on four chips): DAS over the soc19 grid, 140 lanes a chip. It shares
    the configuration, and so the check limits, of `soc19.etf_grid`."""
    cell = harness.load_cell("soc19.etf_grid")
    cell.name, cell.chips = "soc19.das_grid_x4", 4
    cell.traffic = dict(traffic.load("das_grid_x4"), **size)
    return cell


def run_tiny(name, trace=False, seconds=0.3, cell=None):
    import jax
    cell = cell or tiny_cell(name)
    devs = jax.devices()[:cell.chips]
    assert len(devs) == cell.chips
    try:
        return harness.run(cell, SEED, seconds, trace, devs,
                           time.perf_counter(), jax)
    finally:
        faulthandler.cancel_dump_traceback_later()


def fanout_config():
    """soc19 with a sixth application whose root fans out to 24 tasks,
    and a ready queue of 32 slots to hold them."""
    with open(os.path.join(harness.HERE, "configs", "soc19.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "soc19_fanout"
    cfg["apps"]["fanout"] = ([["sync", [], 8.0]] + [["fft", [0], 8.0]] * 24
                             + [["demod", list(range(1, 25)), 2.0]])
    cfg["platform"]["ready_queue_slots"] = 32
    return cfg
