"""The comparison that decides `correct`.

After the window closes, a sample of the lanes that the window's requests
finished, drawn from the seed and always holding the lane that retired
the most events, is simulated again by the float64 reference
(`refsim.py`) from the lanes' specs. Each lane's gap is the worst of

  * the relative gap of the mean frame latency (`avg_exec_us`),
  * the relative gap of the total energy (tasks + scheduler),
  * the relative gap of the events retired (`n_iters`, the numerator of
    `events_per_s`),
  * the gap of slow-scheduler decisions as a share of the tasks,

and is infinite when the lane did not finish every task of the workload
the reference built, or read NaN. The numbers compared are the median and
the 75th percentile of the lane gaps over the sample; each has the limit
in the cell's file under `bench/checks/`, set from the readings recorded
there. Not the maximum: a near-tie that float32 breaks the other way
sends a lane down another schedule (another PE, or a deadline drop one
frame early), and those lanes' gaps reach tens of percent with no fault
in the program (`PERF.md`, PR 12).
"""
from __future__ import annotations

import json
import math
import os
from typing import Dict, List, Sequence

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# per-lane fields the program's result is read for, kept from every request
FIELDS = ("avg_exec_us", "total_energy_uj", "n_iters", "n_slow", "n_done",
          "stall_reason", "ready_drop")


def load(cell: str) -> dict:
    with open(os.path.join(HERE, "checks", f"{cell}.json")) as f:
        return json.load(f)


def draw(seed: int, lanes_per_request: Sequence[int], longest: tuple,
         n: int) -> List[tuple]:
    """(request, lane) pairs to compare: `longest` and n - 1 others drawn
    without replacement from the seed."""
    pairs = [(k, j) for k, m in enumerate(lanes_per_request)
             for j in range(m)]
    rng = np.random.default_rng([int(seed), 7])
    rest = [p for p in pairs if p != tuple(longest)]
    take = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [tuple(longest)] + [rest[i] for i in sorted(take)]


def lane_gap(prog: Dict[str, float], ref: Dict[str, float]) -> float:
    if int(prog["n_done"]) != ref["n_tasks"]:
        return math.inf

    def rel(a, b):
        return abs(float(a) - b) / abs(b) if b else abs(float(a))

    gaps = [rel(prog["avg_exec_us"], ref["avg_exec_us"]),
            rel(prog["total_energy_uj"], ref["total_energy_uj"]),
            rel(prog["n_iters"], ref["events"]),
            abs(int(prog["n_slow"]) - ref["n_slow"]) / ref["n_tasks"]]
    return math.inf if any(math.isnan(g) for g in gaps) else max(gaps)


def numbers(gaps: Sequence[float]) -> Dict[str, float]:
    # "higher": an order statistic, never an interpolation (gaps may be inf)
    return {"lane_gap_median": float(np.percentile(gaps, 50,
                                                   method="higher")),
            "lane_gap_p75": float(np.percentile(gaps, 75, method="higher"))}


def verdict(nums: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(nums[k] <= limits[k] for k in limits)
