"""Chip smoke test: the DAS sweep engine end to end on a TPU.

    python chip_smoke.py               # one chip (the default)
    python chip_smoke.py --four-chips  # the sharded sweep on four chips

The one-chip run drives the deployment the repository supports at full
width — the paper's 19-PE SoC under its 40 application mixes x 14 data
rates, 60 frames each (560 scenarios) — through the normal entry points:

  1. build the 560-scenario suite;
  2. label it with the batched oracle (`oracle.generate`: one MODE_ORACLE
     and one MODE_ETF sweep);
  3. fit the DAS policy (`das.fit_policy`);
  4. sweep LUT, ETF, ETF-ideal and DAS through `run_batch` with the
     default (Pallas) decision kernels, pinned to one device and one
     560-lane chunk.

It fails — non-zero exit, no result line — when JAX finds no TPU, when
the Pallas kernels did not run natively (`DISPATCH_COUNT`), when any
scenario stalls, drops a ready task or leaves work unfinished, when any
`SimResult` field of the four sweeps differs bitwise from the same sweeps
with `kernels="xla"`, or when LUT / ETF / ETF-ideal disagree with the
float64 reference simulator (`core/ref_sim.py`) on cells (mix 0, 1, 4, 5)
x (rate 0, 9, 13) beyond the tolerances of `ref_sim.disagreements`. That
comparison runs at the differential test's 10-frame length, where those
tolerances hold. At 60 frames a few pairs fall outside them on the CPU
as well: in each, the first decision or completion the two simulators
order differently is a float64 tie, or a near-tie within 0.05 float32
ulps, that float32 rounding breaks the other way, and the placements
that follow cascade from it. The 60-frame comparison is therefore
printed, not enforced.

`--four-chips` runs only the sharded path: the full-grid ETF and DAS
sweeps on one device and on four, which must agree bitwise, with the
four-device lanes spread over all four devices. DAS there uses a fixed
depth-2 tree, so no oracle phase runs.

Compile and sweep seconds are printed for information only. The last
line of standard output is one JSON object naming the device. A run
still going after `DEADLINE_S` dumps every thread's stack to standard
error and exits with code 1: a hung device call cannot be interrupted
from Python, and the sweep in progress is the last line printed.
"""
from __future__ import annotations

import argparse
import faulthandler
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

N_INSTANCES = 60
REF_INSTANCES = 10      # the differential test's stream length
REF_MIXES = (0, 1, 4, 5)
REF_RATES = (0, 9, 13)
LOW_RATES = (0, 1, 2)
HIGH_RATES = (11, 12, 13)
# a one-chip run takes about 150 s on a TPU v5e, compile included; five
# times that is a hang
DEADLINE_S = 780


class SmokeFailure(Exception):
    pass


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class _CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (its own
    `/jax/core/compile/*` duration events)."""

    def __init__(self, jax):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.total += duration


def _sweep(sim, clock, label, mode, stacked, params, *, devices, kernels,
           tree=None, telemetry=None):
    """One `run_batch` sweep in a single chunk, timed to completion."""
    import jax
    print(f"  {label:34s}", end="", flush=True)
    c0, t0 = clock.total, time.perf_counter()
    res = jax.block_until_ready(sim.run_batch(
        mode, stacked, params, tree=tree,
        batch_size=stacked.task_type.shape[0], devices=devices,
        kernels=kernels, telemetry=telemetry))
    wall = time.perf_counter() - t0
    comp = clock.total - c0
    print(f" compile {comp:8.3f} s   sweep {wall - comp:8.3f} s"
          "   (informational)")
    return res


def _health(sim, np, label, res, n_tasks) -> None:
    """Every scenario drained its workload: no stall, no dropped ready
    task, no dropped job, every task done."""
    bad = {
        "stalled": np.asarray(res.stalled).astype(bool),
        "stall_reason": np.asarray(res.stall_reason) != sim.STALL_NONE,
        "ready_drop": np.asarray(res.ready_drop) != 0,
        "dropped_jobs": np.asarray(res.n_dropped_jobs) != 0,
        "unfinished": np.asarray(res.n_done) != n_tasks,
    }
    for what, mask in bad.items():
        _check(not mask.any(), f"{label}: {int(mask.sum())} scenario(s) "
               f"{what}, first at index {int(np.argmax(mask))}")


def _bitwise(sim, np, label, a, b) -> None:
    for name in sim.SimResult._fields:
        x, y = np.asarray(getattr(a, name)), np.asarray(getattr(b, name))
        _check(x.shape == y.shape and x.tobytes() == y.tobytes(),
               f"{label}: field {name} differs")


def _dispatch_ok(kops) -> None:
    dc = dict(kops.DISPATCH_COUNT)
    print(f"  dispatch counts: {dc}")
    _check(dc["etf_pallas"] > 0 and dc["push_pallas"] > 0,
           "the Pallas decision kernels were never dispatched natively")
    stray = {k: v for k, v in dc.items()
             if (k.endswith("_interpret") or k == "etf_ft_ref_fallback")
             and v > 0}
    _check(not stray, f"interpret-mode or fallback dispatches: {stray}")


def one_chip(jax, np, clock) -> None:
    from repro.core import das, oracle, ref_sim, simulator as sim, workloads
    from repro.kernels.etf_ft import ops as kops

    t0 = time.perf_counter()
    suite = workloads.default_suite(n_instances=N_INSTANCES)
    n_mix, n_rate = suite.mixes.shape[0], len(suite.rates)
    cells = [(mi, ri) for mi in range(n_mix) for ri in range(n_rate)]
    stacked = suite.build_many(cells)
    n_tasks = np.asarray(stacked.n_tasks)
    params = sim.make_params()
    print(f"phase 1: built {len(cells)} scenarios ({n_mix} mixes x {n_rate} "
          f"rates x {N_INSTANCES} frames) in "
          f"{time.perf_counter() - t0:.3f} s (host)")

    print("phase 2: batched oracle labelling")
    oracle_runs = {}

    def runner(mode, s, p, bs):
        res = _sweep(sim, clock, f"oracle {sim.MODE_NAMES[mode]}", mode, s,
                     p, devices=1, kernels="pallas")
        _health(sim, np, f"oracle {sim.MODE_NAMES[mode]}", res,
                np.asarray(s.n_tasks))
        oracle_runs[mode] = res
        return res

    ds = oracle.generate(suite, params, batch_size=len(cells), runner=runner)
    print(f"  {len(ds)} samples, S-label fraction {ds.labels.mean()!r}")

    print("phase 3: fit the DAS policy")
    pol = das.fit_policy(ds)
    print(f"  features {list(pol.feature_ids)}, train accuracy "
          f"{pol.train_accuracy!r}, test accuracy {pol.test_accuracy!r}")

    print("phase 4: LUT / ETF / ETF-ideal / DAS sweeps, Pallas vs XLA")
    modes = [(sim.MODE_LUT, None), (sim.MODE_ETF, None),
             (sim.MODE_ETF_IDEAL, None), (sim.MODE_DAS, pol.tree)]
    res = {}
    for mode, tree in modes:
        name = sim.MODE_NAMES[mode]
        res[mode] = _sweep(sim, clock, f"{name} pallas", mode, stacked,
                           params, devices=1, kernels="pallas", tree=tree)
        _health(sim, np, f"{name} pallas", res[mode], n_tasks)
    _dispatch_ok(kops)
    for mode, tree in modes:
        name = sim.MODE_NAMES[mode]
        ref = _sweep(sim, clock, f"{name} xla", mode, stacked, params,
                     devices=1, kernels="xla", tree=tree)
        _bitwise(sim, np, f"{name} pallas vs xla", res[mode], ref)
    _bitwise(sim, np, "oracle ETF vs ETF sweep", oracle_runs[sim.MODE_ETF],
             res[sim.MODE_ETF])
    print("  Pallas and XLA sweeps bitwise identical in every SimResult "
          "field (LUT, ETF, ETF-ideal, DAS)")

    print("phase 5: float64 reference (core/ref_sim.py)")
    t0 = time.perf_counter()
    ref_cells = [(mi, ri) for mi in REF_MIXES for ri in REF_RATES]
    small = workloads.default_suite(n_instances=REF_INSTANCES)
    small_stacked = small.build_many(ref_cells)
    long_off = []
    for mode in (sim.MODE_LUT, sim.MODE_ETF, sim.MODE_ETF_IDEAL):
        name = sim.MODE_NAMES[mode]
        short = _sweep(sim, clock, f"{name} {REF_INSTANCES} frames", mode,
                       small_stacked, params, devices=1, kernels="pallas")
        for k, (mi, ri) in enumerate(ref_cells):
            wl = small.build(mi, ri)
            problems = ref_sim.disagreements(
                sim.result_at(short, k), ref_sim.simulate_ref(mode, wl),
                int(wl.n_tasks))
            _check(not problems, f"{name} (mix {mi}, rate {ri}, "
                   f"{REF_INSTANCES} frames) vs ref_sim: {problems}")
            # the same cells at full length, reported and not enforced
            # (see the module docstring)
            wl = suite.build(mi, ri)
            problems = ref_sim.disagreements(
                sim.result_at(res[mode], mi * n_rate + ri),
                ref_sim.simulate_ref(mode, wl), int(wl.n_tasks))
            if problems:
                long_off.append((name, mi, ri, problems))
    n_pairs = 3 * len(ref_cells)
    print(f"  {n_pairs} (mode, cell) pairs at {REF_INSTANCES} frames agree "
          f"within the differential-test tolerances "
          f"({time.perf_counter() - t0:.3f} s)")
    print(f"  at {N_INSTANCES} frames (informational): "
          f"{n_pairs - len(long_off)}/{n_pairs} pairs within them; outside:")
    for name, mi, ri, problems in long_off:
        print(f"    {name} (mix {mi}, rate {ri}): {problems}")

    avg = {m: np.asarray(r.avg_exec_us, np.float64) for m, r in res.items()}
    edp = {m: np.asarray(r.edp, np.float64) for m, r in res.items()}
    lo = [mi * n_rate + ri for mi in range(n_mix) for ri in LOW_RATES]
    hi = [mi * n_rate + ri for mi in range(n_mix) for ri in HIGH_RATES]
    L, E, D = sim.MODE_LUT, sim.MODE_ETF, sim.MODE_DAS
    derived = {
        "speedup_vs_etf_low": float(np.mean(avg[E][lo] / avg[D][lo])),
        "edp_red_vs_etf_low": float(np.mean(1 - edp[D][lo] / edp[E][lo])),
        "speedup_vs_lut_high": float(np.mean(avg[L][hi] / avg[D][hi])),
        "edp_red_vs_lut_high": float(np.mean(1 - edp[D][hi] / edp[L][hi])),
        "das_matches_best_frac": float(np.mean(
            avg[D] <= np.minimum(avg[L], avg[E]) * 1.02)),
    }
    print("derived (DAS on the paper's feature pair, exec-time labels; "
          f"all 560 cells for das_matches_best_frac): {json.dumps(derived)}")


def four_chips(jax, np, clock) -> None:
    import jax.numpy as jnp

    from repro.core import simulator as sim, workloads
    from repro.kernels.etf_ft import ops as kops

    _check(len(jax.devices()) >= 4,
           f"--four-chips needs 4 devices, JAX sees {len(jax.devices())}")
    suite = workloads.default_suite(n_instances=N_INSTANCES)
    cells = [(mi, ri) for mi in range(suite.mixes.shape[0])
             for ri in range(len(suite.rates))]
    stacked = suite.build_many(cells)
    n_tasks = np.asarray(stacked.n_tasks)
    params = sim.make_params()
    # fixed depth-2 tree on the data rate, so DAS takes both schedulers
    tree = sim.DTree(feat=jnp.array([sim.FEAT_RATE, 1, 1], jnp.int32),
                     thr=jnp.array([500.0, 4.0, 6.0], jnp.float32),
                     leaf=jnp.array([0, 1, 0, 1], jnp.int32))
    print(f"sharded sweeps over {len(cells)} scenarios: 4 devices vs 1")
    for mode, t in ((sim.MODE_ETF, None), (sim.MODE_DAS, tree)):
        name = sim.MODE_NAMES[mode]
        r1 = _sweep(sim, clock, f"{name} devices=1", mode, stacked, params,
                    devices=1, kernels="pallas", tree=t)
        _health(sim, np, f"{name} devices=1", r1, n_tasks)
        tel = []
        r4 = _sweep(sim, clock, f"{name} devices=4", mode, stacked, params,
                    devices=4, kernels="pallas", tree=t, telemetry=tel)
        _check(tel and all(rec["devices"] == 4 for rec in tel),
               f"{name}: 4-device sweep ran on {[r['devices'] for r in tel]}"
               " devices")
        _bitwise(sim, np, f"{name} 4 devices vs 1", r4, r1)
        print(f"  {name}: lanes spread over 4 devices; bitwise identical to "
              "the 1-device sweep in every SimResult field")
    _dispatch_ok(kops)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device vs 1-device sharded check")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: FAIL: no repro package under {SRC}; run this "
              "from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)

    import jax
    import numpy as np

    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: FAIL: JAX found no devices: {e}", file=sys.stderr)
        return 1
    if devs[0].platform != "tpu":
        print(f"chip_smoke: FAIL: no TPU found (JAX sees platform "
              f"{devs[0].platform!r}); this smoke test runs only on the chip",
              file=sys.stderr)
        return 1
    # progress lines reach a pipe as they are printed, so a run cut by the
    # deadline shows the sweep it was in
    sys.stdout.reconfigure(line_buffering=True)
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)
    from repro.core import compile_cache
    print(f"device_kind {devs[0].device_kind!r}, {len(devs)} device(s); "
          f"compile cache {compile_cache.enable()}")

    clock = _CompileClock(jax)
    t0 = time.perf_counter()
    try:
        (four_chips if args.four_chips else one_chip)(jax, np, clock)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    finally:
        faulthandler.cancel_dump_traceback_later()
    print(f"total {time.perf_counter() - t0:.3f} s, of which compile "
          f"{clock.total:.3f} s (informational)")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
