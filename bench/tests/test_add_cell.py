"""A cell, a traffic mix and a per-layer metric are added with new files
and one `workloads` entry: the harness finds them by name, and no file
that was there changes."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

from bench import harness

SCRIPT = r"""
import json, sys, time, faulthandler
import jax
from bench import harness
cell = harness.load_cell("soc19.lut_small")
res = harness.run(cell, 4242, 0.2, True, jax.devices()[:1],
                  time.perf_counter(), jax)
faulthandler.cancel_dump_traceback_later()
print(json.dumps(res))
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


def test_cell_from_files_alone(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digest(tmp_path / "bench")

    b = tmp_path / "bench"
    (b / "traffic" / "lut_small.json").write_text(json.dumps(
        {"mode": "LUT", "grid": "paper_grid", "mix_ids": [2, 9],
         "rate_ids": [1, 12], "frames": 5}))
    (b / "checks" / "soc19.lut_small.json").write_text(json.dumps(
        {"sample_lanes": 4,
         "limits": {"lane_gap_median": 1e-3, "lane_gap_p75": 1e-3}}))
    (b / "metrics" / "lanes_per_request.py").write_text(
        "def read(run):\n    return float(run.lanes)\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "soc19.lut_small", "config": "soc19",
                               "traffic": "lut_small", "chips": 1,
                               "why": "a test cell"})
    bench["per_layer"].append({"name": "lanes_per_request", "unit": "lanes",
                               "better": "higher", "source": "host_clock",
                               "layer": "sweep engine",
                               "moves": "events_per_s",
                               "workloads": ["soc19.lut_small"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(tmp_path),
                                           os.path.join(harness.ROOT,
                                                        "src")]))
    p = subprocess.run([sys.executable, "-c", SCRIPT], cwd=tmp_path,
                       env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is True
    assert res["attempted"] >= 4
    assert res["metrics"]["lanes_per_request"]["value"] == 4.0

    after = _digest(tmp_path / "bench")
    assert {k: v for k, v in after.items() if k in before} == before
