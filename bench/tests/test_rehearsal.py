"""Every cell's request loop at a tiny size on the CPU, and the command
itself where there is no chip or no program."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness
from bench.tests.helpers import pending_x4_cell, run_tiny

with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]
DEVICE_METRICS = [m["name"] for m in BENCH["per_layer"]
                  if m["source"] == "device_trace"]


@pytest.mark.parametrize("name", CELLS)
def test_traced_request_loop_on_cpu(name):
    res = run_tiny(name, trace=True)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] >= 12
    assert res["device"]["platform"] == "cpu"
    # host span and telemetry metrics read; device metrics are absent
    assert {"build_share", "lane_occupancy"} <= set(res["metrics"])
    assert not set(DEVICE_METRICS) & set(res["metrics"])
    assert "busy_s" not in res["device"]
    assert 0 < res["metrics"]["lane_occupancy"]["value"] <= 100
    assert list(res)[-1] == "checks"


def test_pending_four_chip_cell_on_four_cpu_devices():
    res = run_tiny(None, trace=True, cell=pending_x4_cell())
    assert res["correct"] is True
    assert res["device"]["count"] == 4
    assert 0 < res["metrics"]["lane_occupancy"]["value"] <= 100


def test_end_to_end_metrics_on_cpu():
    res = run_tiny("soc19.etf_grid")
    assert res["correct"] is True
    assert set(res["metrics"]) == {"events_per_s", "setup_s"}
    assert res["metrics"]["events_per_s"]["value"] > 0


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "soc19.etf_grid",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_fails_without_a_tpu():
    p = _command(harness.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_command_fails_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
