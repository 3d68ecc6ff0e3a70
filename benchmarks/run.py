"""Benchmark harness entry point: one section per paper table/figure plus
the beyond-paper serving and roofline benchmarks. Prints
``name,us_per_call,derived`` CSV lines with --csv; --json PATH additionally
writes a machine-readable `BENCH_sweep.json`-style record (per-section wall
time, each section's returned metrics, and the derived DAS speedup / EDP
reductions vs LUT and ETF) so the perf trajectory is comparable across PRs.

    PYTHONPATH=src python -m benchmarks.run [--csv] [--json PATH]
                                            [--only fig2,fig3,...]
                                            [--resume DIR]

--resume DIR checkpoints every sweep's chunks into DIR (atomic
write-temp + rename); re-running the same command after a crash or
SIGKILL resumes from the completed chunks and produces byte-identical
results. The --json record gains a "campaign" block (retries, timeouts,
OOM shrink events, stall trips, chunk reuse, per-chunk wall time), and
the record itself is written atomically.

Environment: REPRO_BENCH_INSTANCES (default 60) scales workload size;
REPRO_BENCH_FULL=0 opts out of the full 40 mixes x 14 rates grid;
REPRO_BENCH_BATCH / REPRO_BENCH_DEVICES control sweep chunking and
scenario-axis sharding; REPRO_BENCH_CAMPAIGN_DIR / REPRO_BENCH_WATCHDOG_S
/ REPRO_BENCH_STEP_BUDGET configure the crash-safe campaign layer (see
benchmarks.common).
"""
from __future__ import annotations

import argparse
import time
import traceback

from benchmarks import (faults, fig2, fig3, heuristic, overhead,
                        roofline_table, serving_das, summary40, table2)

SECTIONS = [
    ("fig2", "Fig.2: exec time + EDP, 3 workloads x 4 schedulers", fig2.run),
    ("fig3", "Fig.3: DAS decision mix + scheduling energy", fig3.run),
    ("table2", "Table II: classifier accuracy/storage", table2.run),
    ("summary40", "40-workload summary claims", summary40.run),
    ("heuristic", "static-threshold heuristic comparison", heuristic.run),
    ("overhead", "scheduling overhead anchors", overhead.run),
    ("faults", "fault-injection degradation curves", faults.run),
    ("serving_das", "beyond-paper: DAS serving dispatch", serving_das.run),
    ("roofline", "dry-run roofline table", roofline_table.run),
]


def _jsonable(obj):
    """Best-effort JSON coercion for numpy scalars/arrays in section
    results; anything else degrades to its repr rather than crashing the
    record write at the end of a long run."""
    import numpy as np
    if isinstance(obj, (np.floating, np.integer, np.bool_)):
        return obj.item()
    if isinstance(obj, np.ndarray):
        return obj.tolist()
    return repr(obj)


def _derived(results: dict) -> dict:
    """Headline DAS-vs-baseline metrics (paper IV-C), lifted from the
    summary40 section when it ran: speedup and EDP reduction vs ETF at low
    rates and vs LUT at high rates."""
    s40 = results.get("summary40", {}).get("result")
    if not isinstance(s40, dict):
        return {}
    keys = ("speedup_vs_etf_low", "edp_red_vs_etf_low",
            "speedup_vs_lut_high", "edp_red_vs_lut_high",
            "das_matches_best_frac")
    return {k: s40[k] for k in keys if k in s40}


def _env_record() -> dict:
    import os

    import jax

    from benchmarks import common
    return {
        "backend": jax.default_backend(),
        "n_devices": jax.device_count(),
        "bench_devices": os.environ.get("REPRO_BENCH_DEVICES"),
        "batch_size": common.batch_size(),
        "full_grid": common.FULL,
        "n_instances": common.N_INSTANCES,
        "train_grid": [len(common.TRAIN_MIXES), len(common.TRAIN_RATES)],
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--csv", action="store_true",
                    help="emit name,us_per_call,derived CSV lines")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write per-section wall times + metrics to PATH")
    ap.add_argument("--only", default=None)
    ap.add_argument("--resume", default=None, metavar="DIR",
                    help="checkpoint sweep chunks into DIR and resume any "
                         "completed chunks from a previous (killed) run")
    args = ap.parse_args(argv)
    only = set(args.only.split(",")) if args.only else None

    from benchmarks import common
    from repro.core import compile_cache
    compile_cache.enable()
    if args.resume:
        common.set_campaign_dir(args.resume)

    t00 = time.time()
    failures = []
    results = {}
    for name, title, fn in SECTIONS:
        if only and name not in only:
            continue
        print(f"\n{'='*72}\n== {name}: {title}\n{'='*72}")
        t0 = time.time()
        try:
            out = fn(csv=args.csv)
            results[name] = {"wall_s": round(time.time() - t0, 3),
                             "result": out}
        except Exception as e:
            failures.append((name, e))
            results[name] = {"wall_s": round(time.time() - t0, 3),
                             "error": f"{type(e).__name__}: {e}"}
            traceback.print_exc()
        print(f"-- {name} done in {time.time()-t0:.0f}s")
    total = time.time() - t00
    print(f"\nall benchmarks done in {total:.0f}s; "
          f"{len(failures)} failures")
    if args.json:
        record = {
            "total_s": round(total, 3),
            "env": _env_record(),
            "derived": _derived(results),
            "campaign": common.campaign_stats(),
            "sections": results,
        }
        # atomic write (temp + rename): a crash mid-dump never leaves a
        # truncated BENCH_sweep.json behind
        from repro.core import campaign
        campaign.atomic_write_json(args.json, record, default=_jsonable)
        print(f"wrote {args.json}")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
