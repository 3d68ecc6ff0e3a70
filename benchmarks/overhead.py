"""Scheduling-overhead microbenchmark (paper I / IV-C anchors): per-decision
latency and energy of LUT, ETF, the DAS classifier, plus the measured
wall-time of the ETF finish-time search (jnp oracle and fused XLA
everywhere, the native Pallas kernels on a TPU)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro.core import simulator as sim, soc
from repro.kernels.etf_ft import kernel as ek, ops as eo, ref as er


def _time_us(f, *args, reps=20):
    """Warm once (compile), then report mean wall time per call in us."""
    jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(f(*args))
    return (time.perf_counter() - t0) / reps * 1e6


def run(csv=False):
    pol = common.das_policy()
    res = common.eval_cell(5, 12, sim.MODE_DAS, tree=pol.tree)
    n = max(int(res.n_decisions), 1)
    rows = {
        "LUT_ns": float(soc.LUT_LATENCY_US) * 1e3,
        "LUT_nJ": float(soc.LUT_ENERGY_UJ) * 1e3,
        "ETF_ns_q8": float(soc.etf_latency_us(8)) * 1e3,
        "DAS_heavy_ns": float(res.sched_time_us) / n * 1e3,
        "DAS_heavy_nJ": float(res.sched_energy_uj) / n * 1e3,
    }

    # ETF finish-time search wall-time, batch of 64 decisions: the jnp
    # oracle and the fused XLA formulation everywhere, the Pallas kernels
    # only where they run natively (a TPU). Interpreter timings say
    # nothing about the chip, so they are never taken. These shapes
    # compile for the chip (`tests/test_tpu_compile.py`).
    B, R, P = 64, 64, 19
    key = jax.random.PRNGKey(0)
    avail = jax.random.uniform(key, (B, R, P)) * 10
    free = jax.random.uniform(key, (B, P)) * 10
    ex = jax.random.uniform(key, (B, R, P)) * 5
    now = jnp.zeros((B,))
    slot_ok = jnp.ones((B, R), bool)
    alive = jnp.ones((B, P), bool)
    native = eo.kernel_mode("auto") == "pallas"
    rows["etf_ft_jnp_us_per_batch64"] = _time_us(
        jax.jit(er.etf_ft_reference), avail, free, ex, now)
    # scenario-batched masked variant (the decision hot path the
    # simulator routes through under REPRO_SIM_KERNELS)
    rows["etf_ft_masked_xla_us_per_batch64"] = _time_us(
        jax.jit(er.etf_ft_masked_reference),
        avail, free, ex, now, slot_ok, alive)
    if native:
        rows["etf_ft_pallas_us_per_batch64"] = _time_us(
            ek.etf_ft_search, avail, free, ex, now)
        rows["etf_ft_masked_pallas_us_per_batch64"] = _time_us(
            ek.etf_ft_search_masked, avail, free, ex, now, slot_ok, alive)

    for k, v in rows.items():
        if csv:
            print(f"overhead,{v:.1f},{k}")
        else:
            print(f"  {k:28s} {v:10.1f}")
    print(f"  paper anchors: LUT 6 ns / 2.3 nJ; DAS heavy ~65 ns / 27.2 nJ")
    return rows


if __name__ == "__main__":
    run()
