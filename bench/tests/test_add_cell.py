"""A cell, a traffic mix, a per-layer metric and a whole deployment are
added with new files and `BENCHMARK.json` entries: the harness finds them
by name, and no file that was there changes."""
import copy
import hashlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np

from bench import harness, refsim, traffic
from bench.tests.helpers import SEED, fanout_config

SCRIPT = r"""
import json, sys, time, faulthandler
import jax
from bench import harness
cell = harness.load_cell(sys.argv[1])
res = harness.run(cell, 4242, 0.2, True, jax.devices()[:1],
                  time.perf_counter(), jax)
faulthandler.cancel_dump_traceback_later()
print(json.dumps(res))
"""

# Where the program takes no `apps` / `ready_slots` parameter yet, the
# run gives them to it the only way the engine reads them today: by the
# module values they stand for, set around each call (`dfg`'s graph
# tables while a workload is built, `simulator.R_MAX` while the sweep is
# traced). Where the program has the parameters, the harness passes them
# and nothing is set.
ADAPTER = r"""
import inspect
from repro.core import dfg, simulator as sim, workloads


# `fn` with a keyword-only parameter `name`: `values(kw)` takes it out of
# the call and says which module values to set around it
def _around(fn, name, values):
    def call(*args, **kw):
        new = values(kw)
        if new is None:
            return fn(*args, **kw)
        mod, new = new
        old = {k: getattr(mod, k) for k in new}
        vars(mod).update(new)
        try:
            return fn(*args, **kw)
        finally:
            vars(mod).update(old)
    sig = inspect.signature(fn)
    call.__signature__ = sig.replace(parameters=[
        *sig.parameters.values(),
        inspect.Parameter(name, inspect.Parameter.KEYWORD_ONLY,
                          default=None)])
    return call


def _graphs(kw):
    apps = kw.pop("apps", None)
    if apps is None:
        return None
    names = tuple(f"app{i}" for i in range(len(apps)))
    return dfg, {
        "APPS": dict(zip(names, apps)), "APP_NAMES": names,
        "N_APPS": len(apps),
        "MAX_APP_TASKS": max(g.n_tasks for g in apps),
        "MAX_PREDS": max(len(p) for g in apps for p in g.preds),
        "MAX_SUCCS": max(len(s) for g in apps for s in g.succs()),
        "MAX_ROOTS": max(sum(not p for p in g.preds) for g in apps)}


def _slots(kw):
    slots = kw.pop("ready_slots", None)
    return None if slots is None else (sim, {"R_MAX": slots})


if "apps" not in inspect.signature(workloads.build_workload).parameters:
    workloads.build_workload = _around(workloads.build_workload, "apps",
                                       _graphs)
    workloads.default_suite = _around(workloads.default_suite, "apps",
                                      _graphs)
if "ready_slots" not in inspect.signature(sim.run_batch).parameters:
    sim.run_batch = _around(sim.run_batch, "ready_slots", _slots)
"""


def _digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def _copy_bench(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return _digest(tmp_path / "bench")


def _add_entries(tmp_path, **entries):
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    for key, items in entries.items():
        bench[key] += items
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))


def _run_cell(tmp_path, name, prelude=""):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(tmp_path),
                                           os.path.join(harness.ROOT,
                                                        "src")]))
    p = subprocess.run([sys.executable, "-c", prelude + SCRIPT, name],
                       cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_cell_from_files_alone(tmp_path):
    before = _copy_bench(tmp_path)

    b = tmp_path / "bench"
    (b / "traffic" / "lut_small.json").write_text(json.dumps(
        {"mode": "LUT", "grid": "paper_grid", "mix_ids": [2, 9],
         "rate_ids": [1, 12], "frames": 5}))
    (b / "checks" / "soc19.lut_small.json").write_text(json.dumps(
        {"sample_lanes": 4,
         "limits": {"lane_gap_median": 1e-3, "lane_gap_p75": 1e-3}}))
    (b / "metrics" / "lanes_per_request.py").write_text(
        "def read(run):\n    return float(run.lanes)\n")
    _add_entries(
        tmp_path,
        workloads=[{"name": "soc19.lut_small", "config": "soc19",
                    "traffic": "lut_small", "chips": 1,
                    "why": "a test cell"}],
        per_layer=[{"name": "lanes_per_request", "unit": "lanes",
                    "better": "higher", "source": "host_clock",
                    "layer": "sweep engine", "moves": "events_per_s",
                    "workloads": ["soc19.lut_small"]}])

    res = _run_cell(tmp_path, "soc19.lut_small")
    assert res["correct"] is True
    assert res["attempted"] >= 4
    assert res["metrics"]["lanes_per_request"]["value"] == 4.0

    after = _digest(tmp_path / "bench")
    assert {k: v for k, v in after.items() if k in before} == before


FANOUT_GRID = {"source": "a test grid over six apps",
               "mixes": [[0, 0, 0, 0, 0, 1], [1 / 6] * 6,
                         [0.5, 0, 0, 0, 0, 0.5]],
               "rates_mbps": [62.5, 125.0]}
FANOUT_TRAFFIC = {"mode": "ETF", "grid": "fanout_grid", "frames": 6}


def test_deployment_from_files_alone(tmp_path):
    """A configuration with other task graphs and another ready-queue
    size, its grid, traffic and check: the run sweeps the configuration's
    graphs (24 tasks ready at once, none dropped) and agrees with the
    reference."""
    before = _copy_bench(tmp_path)

    b = tmp_path / "bench"
    (b / "configs" / "soc19_fanout.json").write_text(
        json.dumps(fanout_config()))
    (b / "data" / "fanout_grid.json").write_text(json.dumps(FANOUT_GRID))
    (b / "traffic" / "etf_fanout.json").write_text(
        json.dumps(FANOUT_TRAFFIC))
    (b / "checks" / "soc19_fanout.etf_fanout.json").write_text(json.dumps(
        {"sample_lanes": 6,
         "limits": {"lane_gap_median": 1e-3, "lane_gap_p75": 0.02}}))
    (b / "metrics" / "fanout_occupancy.py").write_text(
        "def read(run):\n"
        "    return 100.0 * sum(r['active_trips'] for r in run.telemetry)"
        " / sum(r['lane_trips'] for r in run.telemetry)\n")
    _add_entries(
        tmp_path,
        configs=[{"name": "soc19_fanout", "source": "a test deployment",
                  "file": "bench/configs/soc19_fanout.json", "reduced": [],
                  "why": "a sixth app with a 24-way fan-out"}],
        workloads=[{"name": "soc19_fanout.etf_fanout",
                    "config": "soc19_fanout", "traffic": "etf_fanout",
                    "chips": 1, "why": "a test cell"}],
        per_layer=[{"name": "fanout_occupancy", "unit": "%",
                    "better": "higher", "source": "program_counter",
                    "layer": "sweep engine", "moves": "events_per_s",
                    "workloads": ["soc19_fanout.etf_fanout"]}])

    res = _run_cell(tmp_path, "soc19_fanout.etf_fanout", prelude=ADAPTER)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 6
    assert 0 < res["metrics"]["fanout_occupancy"]["value"] <= 100

    after = _digest(tmp_path / "bench")
    assert {k: v for k, v in after.items() if k in before} == before


def test_fanout_needs_its_ready_queue():
    """The deployment above is no test of the queue size unless 16 slots,
    the soc19 queue, would drop its fan-out: so says the reference."""
    cfg = fanout_config()
    tr = dict(FANOUT_TRAFFIC,
              mixes=np.asarray(FANOUT_GRID["mixes"], np.float32),
              rates=np.asarray(FANOUT_GRID["rates_mbps"], np.float32))
    lane = traffic.request(tr, cfg, SEED, 0)[0]        # the fan-out app
    drops = {}
    for slots in (32, 16):
        cfg = copy.deepcopy(cfg)
        cfg["platform"]["ready_queue_slots"] = slots
        plat = refsim.Platform(cfg)
        wl = refsim.build(plat, lane.mix, lane.rate_mbps, lane.frames,
                          lane.seed)
        drops[slots] = refsim.simulate("ETF", plat, wl)["ready_drop"]
    assert drops[32] == 0 and drops[16] > 0
