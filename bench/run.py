"""Benchmark of the DAS sweep engine: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
                         --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the cell
asks for (`BENCHMARK.json`). The last line of standard output is one JSON
object: `correct`, `attempted`, `failed`, `metrics` (the cell's end-to-end
metrics, or with `--trace 1` its per-layer metrics), `device`, with
`--trace 1` a `breakdown`, and last the `checks` that decided `correct`.
Without a TPU, or with fewer chips than the cell asks for, it exits with
code 2 and prints no result; a compile inside the measured window exits
with code 3.
"""
import time

T_START = time.perf_counter()   # set-up is measured from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
# the TPU runtime would log to a fixed directory under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def seed_arg(raw: str) -> int:
    v = int(raw)
    if v < 0:
        raise argparse.ArgumentTypeError("--seed must be >= 0")
    return v


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=seed_arg, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench import harness
    return harness.main(args, T_START)


if __name__ == "__main__":
    sys.exit(main())
