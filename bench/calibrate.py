"""Readings for the limits of `check.py`, on the chip, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds <n> [--first <seed>]

For each seed, one request of the cell at its full size goes through the
program (set-up once, as in a run); the check's sample of its lanes is
compared with the float64 reference, which gives the program's readings,
and the same lanes are simulated by the reference in bfloat16 — the
control, put in the program's place — which gives the control's. Prints
one JSON line per seed and a summary: the lower reading of each number
is the largest the program gives, the upper the smallest the control
gives. The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ProcessPoolExecutor
from multiprocessing import get_context

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import check, harness, traffic as traffic_mod  # noqa: E402


def _ref(args):
    cell, lane, precision = args
    return harness.reference(cell, lane, precision)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first", type=int, default=2**31 + 1000)
    ap.add_argument("--workers", type=int, default=8)
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    sys.path.insert(0, harness.SRC)
    cell = harness.load_cell(args.workload)
    import jax
    harness.enable_compile_cache(jax)
    devs = harness.find_devices(jax, cell.chips)
    prog = harness.Program(cell, len(devs))
    harness.request(jax, prog, traffic_mod.request(
        cell.traffic, cell.cfg, args.first, 0, warm=True))
    harness.log(f"set-up {time.perf_counter() - t_start!r} s")
    lows, highs = [], []
    pool = ProcessPoolExecutor(args.workers, mp_context=get_context("spawn"))
    with pool:
        for seed in range(args.first, args.first + args.seeds):
            lanes = traffic_mod.request(cell.traffic, cell.cfg, seed, 0)
            t0 = time.perf_counter()
            rows = harness.request(jax, prog, lanes)
            t_req = time.perf_counter() - t0
            pairs = harness.sample_lanes(cell, seed, [rows])
            picked = [lanes[j] for _, j in pairs]
            ref = list(pool.map(_ref, [(cell, l, "float64")
                                       for l in picked]))
            ctl = list(pool.map(_ref, [(cell, l, "bfloat16")
                                       for l in picked]))
            gaps = [check.lane_gap({f: rows[f][j] for f in check.FIELDS}, r)
                    for (_, j), r in zip(pairs, ref)]
            cgaps = [check.lane_gap({"avg_exec_us": c["avg_exec_us"],
                                     "total_energy_uj": c["total_energy_uj"],
                                     "n_iters": c["events"],
                                     "n_slow": c["n_slow"],
                                     "n_done": c["n_done"]}, r)
                     for c, r in zip(ctl, ref)]
            low, high = check.numbers(gaps), check.numbers(cgaps)
            lows.append(low)
            highs.append(high)
            print(json.dumps({"seed": seed, "request_s": t_req,
                              "events": int(rows["n_iters"].sum()),
                              "max_lane_events": int(rows["n_iters"].max()),
                              "program": low, "control": high,
                              "lane_gaps": gaps}), flush=True)
    summary = {k: {"lower": max(x[k] for x in lows),
                   "upper": min(x[k] for x in highs)} for k in lows[0]}
    print(json.dumps({"workload": cell.name, "seeds": args.seeds,
                      "first": args.first, "readings": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
