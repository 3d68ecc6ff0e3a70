"""The general traffic generator: scenario specs for each request.

A traffic file (`bench/traffic/<name>.json`) holds only parameters:

  mode      scheduler under test: "LUT", "ETF" or "DAS"
  grid      a file under `bench/data/` with the `mixes` and `rates_mbps`
  mix_ids   which mixes of the grid (null: all), rate_ids likewise
  frames    frames per scenario

A request holds one scenario (lane) per (mix, rate) pair of the grid,
mix-major. Every lane's workload seed, and with a fault regime its fault
plan, is drawn from (`--seed`, request index), so the same seed gives the
same requests and no two requests repeat a scenario. The warm-up request
has the same lanes with one frame each: the same shapes and the same
compiled program, a fraction of the work.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass(frozen=True)
class Lane:
    mix: tuple          # application fractions (float32 values)
    rate_mbps: float
    frames: int
    seed: int           # workload seed (arrival draws)
    plan: Optional[dict] = None     # fault plan arrays, see `fault_plan`


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        traffic = json.load(f)
    with open(os.path.join(HERE, "data", f"{traffic['grid']}.json")) as f:
        grid = json.load(f)
    traffic["mixes"] = np.asarray(grid["mixes"], np.float32)
    traffic["rates"] = np.asarray(grid["rates_mbps"], np.float32)
    return traffic


def grid_cells(traffic: dict) -> list:
    mix_ids = traffic.get("mix_ids") or range(len(traffic["mixes"]))
    rate_ids = traffic.get("rate_ids") or range(len(traffic["rates"]))
    return [(m, r) for m in mix_ids for r in rate_ids]


def fault_plan(regime: dict, n_pes: int, n_clusters: int,
               seed: int) -> dict:
    """One scenario's plan under the configuration's failure regime:
    `permanent` PE failures at uniform instants within the horizon, every
    other one (starting with the first) repaired `repair_after` horizons
    later, and `transient` glitches on uniformly drawn PEs."""
    rng = np.random.RandomState(seed)
    horizon = float(regime["horizon_us"])
    fail = np.full(n_pes, np.inf, np.float32)
    repair = np.full(n_pes, np.inf, np.float32)
    trans = np.full((n_pes, int(regime["transient_slots"])), np.inf,
                    np.float32)
    lo, hi = regime["repair_after"]
    pes = rng.choice(n_pes, size=min(int(regime["permanent"]), n_pes),
                     replace=False)
    for j, pe in enumerate(pes):
        at = float(rng.uniform(0.0, horizon))
        fail[pe] = np.float32(at)
        if j % 2 == 0:
            repair[pe] = np.float32(at + float(rng.uniform(lo, hi)) * horizon)
    for _ in range(int(regime["transient"])):
        pe = int(rng.randint(n_pes))
        at = float(rng.uniform(0.0, horizon))
        free = np.where(~np.isfinite(trans[pe]))[0]
        if free.size == 0:
            raise ValueError(f"PE {pe}: more glitches than transient slots")
        trans[pe, free[0]] = np.float32(at)
    return {"fail_at": fail, "repair_at": repair, "transient_at": trans,
            "slowdown": np.ones(n_clusters, np.float32),
            "max_retries": int(regime["max_retries"]),
            "deadline_us": float(regime["deadline_us"])}


def request(traffic: dict, cfg: dict, seed: int, index: int,
            warm: bool = False) -> List[Lane]:
    """The lanes of request `index` of a run with `seed`."""
    cells = grid_cells(traffic)
    ss = np.random.SeedSequence([int(seed), int(index)])
    wl_seeds, plan_seeds = (s.generate_state(len(cells))
                            for s in ss.spawn(2))
    frames = 1 if warm else int(traffic["frames"])
    regime = cfg.get("faults")
    n_pes = sum(cfg["platform"]["pes_per_cluster"])
    n_clusters = len(cfg["platform"]["clusters"])
    lanes = []
    for (m, r), ws, ps in zip(cells, wl_seeds, plan_seeds):
        plan = (fault_plan(regime, n_pes, n_clusters, int(ps))
                if regime is not None else None)
        lanes.append(Lane(tuple(traffic["mixes"][m].tolist()),
                          float(traffic["rates"][r]), frames, int(ws), plan))
    return lanes
