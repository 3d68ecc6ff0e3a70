"""Sharded + padded sweep engine (`sim.run_batch`).

Per-scenario results must be bit-exact vs the sequential `sim.run` path
for every mode, with and without a stacked `FaultPlan`, and invariant to
`batch_size`, device count, and final-chunk padding.

On a plain run this exercises the padded chunking path on however many
devices the process sees (usually one). CI re-runs this module under
`XLA_FLAGS=--xla_force_host_platform_device_count=4` in both jobs so the
real multi-device `shard_map` path is exercised on CPU-only runners.
"""
import jax
import numpy as np
import pytest

from repro.core import faults as flt, simulator as sim, workloads

PARAMS = sim.make_params()
SUITE = workloads.default_suite(n_instances=6)
# 5 scenarios: every chunk size below leaves a ragged, padded final chunk,
# and 5 never divides a forced 4-device shard evenly
CELLS = [(0, 0), (1, 7), (5, 13), (3, 5), (4, 9)]
WLS = [SUITE.build(mi, ri) for mi, ri in CELLS]
N_DEV = len(jax.devices())

ALL_MODES = [sim.MODE_LUT, sim.MODE_ETF, sim.MODE_ETF_IDEAL, sim.MODE_DAS,
             sim.MODE_ORACLE, sim.MODE_THRESHOLD]
SCALARS = ("avg_exec_us", "total_energy_uj", "edp", "n_decisions",
           "n_fast", "n_slow", "n_done", "task_energy_uj",
           "sched_energy_uj", "n_iters")
FAULT_SCALARS = ("n_faults", "n_retries", "reexec_us", "n_dropped_jobs",
                 "n_dropped_tasks", "recovery_us", "n_recovered")


def _mixed_tree() -> sim.DTree:
    import jax.numpy as jnp
    return sim.DTree(feat=jnp.array([sim.FEAT_RATE, 1, 1], jnp.int32),
                     thr=jnp.array([500.0, 4.0, 6.0], jnp.float32),
                     leaf=jnp.array([0, 1, 0, 1], jnp.int32))


def _assert_cell_equal(rs, rk, fields, ctx):
    for name in fields:
        a = np.asarray(getattr(rs, name))
        b = np.asarray(getattr(rk, name))
        assert np.array_equal(a, b), (ctx, name, a, b)
    np.testing.assert_array_equal(np.asarray(rs.finish),
                                  np.asarray(rk.finish), err_msg=str(ctx))
    np.testing.assert_array_equal(np.asarray(rs.pe_of),
                                  np.asarray(rk.pe_of), err_msg=str(ctx))


@pytest.mark.parametrize("mode", ALL_MODES)
def test_sharded_padded_matches_run(mode):
    """batch_size=2 over all devices: padded + (when multi-device)
    sharded chunks, bit-exact vs the per-scenario sequential path."""
    tree = _mixed_tree() if mode == sim.MODE_DAS else None
    rb = sim.run_batch(mode, WLS, PARAMS, tree=tree, rate_threshold=500.0,
                       batch_size=2, devices=N_DEV)
    for k, wl in enumerate(WLS):
        rs = sim.run(mode, wl, PARAMS, tree=tree, rate_threshold=500.0)
        _assert_cell_equal(rs, sim.result_at(rb, k), SCALARS, (mode, k))


def test_invariant_to_batch_size_devices_and_padding():
    """The same sweep through every chunking/sharding configuration —
    including sizes that force pad widths 0..B-1 — is one result."""
    tree = _mixed_tree()
    ref = sim.run_batch(sim.MODE_DAS, WLS, PARAMS, tree=tree, devices=1)
    for bs in (1, 2, 3, 5, None):
        for dev in sorted({1, N_DEV}):
            r = sim.run_batch(sim.MODE_DAS, WLS, PARAMS, tree=tree,
                              batch_size=bs, devices=dev)
            for name in SCALARS:
                np.testing.assert_array_equal(
                    np.asarray(getattr(ref, name)),
                    np.asarray(getattr(r, name)),
                    err_msg=f"batch_size={bs} devices={dev} field={name}")
            np.testing.assert_array_equal(np.asarray(ref.finish),
                                          np.asarray(r.finish),
                                          err_msg=f"bs={bs} dev={dev}")


@pytest.mark.parametrize("mode", [sim.MODE_LUT, sim.MODE_DAS])
def test_stacked_fault_plans_sharded(mode):
    """A stacked per-scenario FaultPlan threads through the padded,
    sharded chunks bit-exactly (pad lanes replay the last plan, results
    sliced off)."""
    tree = _mixed_tree() if mode == sim.MODE_DAS else None
    plans = [flt.random_plan(s) for s in range(len(WLS))]
    rb = sim.run_batch(mode, WLS, PARAMS, tree=tree, rate_threshold=500.0,
                       plan=flt.stack_plans(plans), batch_size=2,
                       devices=N_DEV)
    for k, (wl, pl) in enumerate(zip(WLS, plans)):
        rs = sim.run(mode, wl, PARAMS, tree=tree, rate_threshold=500.0,
                     plan=pl)
        _assert_cell_equal(rs, sim.result_at(rb, k),
                           SCALARS + FAULT_SCALARS, (mode, k))


def test_shared_plan_sharded():
    """An unbatched (shared) plan is replicated across shards, not
    sliced; the healthy plan keeps the fault path bit-identical."""
    plan = flt.healthy_plan()
    rb = sim.run_batch(sim.MODE_ETF, WLS, PARAMS, plan=plan, batch_size=3,
                       devices=N_DEV)
    for k, wl in enumerate(WLS):
        rs = sim.run(sim.MODE_ETF, wl, PARAMS, plan=plan)
        _assert_cell_equal(rs, sim.result_at(rb, k),
                           SCALARS + FAULT_SCALARS, k)


@pytest.mark.parametrize("mode", ALL_MODES)
def test_stacked_fault_plans_kernels_on(mode):
    """Stacked per-scenario FaultPlans through the kernel-backed decision
    path (`kernels="xla"`), sharded + padded: bit-exact vs the inline
    sequential path for every mode."""
    tree = _mixed_tree() if mode == sim.MODE_DAS else None
    plans = [flt.random_plan(s) for s in range(len(WLS))]
    rb = sim.run_batch(mode, WLS, PARAMS, tree=tree, rate_threshold=500.0,
                       plan=flt.stack_plans(plans), batch_size=2,
                       devices=N_DEV, kernels="xla")
    for k, (wl, pl) in enumerate(zip(WLS, plans)):
        rs = sim.run(mode, wl, PARAMS, tree=tree, rate_threshold=500.0,
                     plan=pl, kernels="off")
        _assert_cell_equal(rs, sim.result_at(rb, k),
                           SCALARS + FAULT_SCALARS, (mode, k))


def test_dead_pe_degraded_etf_tie_breaks_kernels_on():
    """Kill whole clusters at t=0 so the degraded ETF search runs against
    a mostly-dead PE mask: the kernel path must pick the same first-
    global-minimum (slot, pe) as the inline path — the tie-break case the
    masked argmin is most likely to get wrong."""
    plan = flt.fail_cluster(flt.healthy_plan(), 0, at=0.0)
    plan = flt.fail_cluster(plan, 2, at=0.0)
    plan = flt.fail_pes(plan, [9, 10, 11], at=50.0)
    dead_from_t0 = np.where(np.asarray(plan.pe_fail_at) == 0.0)[0]
    for wl in WLS[:3]:
        r0 = sim.run(sim.MODE_ETF, wl, PARAMS, plan=plan, kernels="off")
        rx = sim.run(sim.MODE_ETF, wl, PARAMS, plan=plan, kernels="xla")
        rp = sim.run(sim.MODE_ETF, wl, PARAMS, plan=plan,
                     kernels="pallas-interpret")
        # the alive mask constrained choices: never-alive PEs never chosen
        pe_of = np.asarray(r0.pe_of)
        assert not np.isin(pe_of[pe_of >= 0], dead_from_t0).any()
        assert int(r0.n_done) > 0
        for name in sim.SimResult._fields:
            a = np.asarray(getattr(r0, name))
            assert a.tobytes() == np.asarray(getattr(rx, name)).tobytes(), \
                ("xla", name)
            assert a.tobytes() == np.asarray(getattr(rp, name)).tobytes(), \
                ("pallas", name)


def test_multi_device_mesh_really_shards():
    """Under XLA_FLAGS=--xla_force_host_platform_device_count=N this is
    the test that proves the multi-device path ran (the others pass on one
    device too)."""
    if N_DEV < 2:
        pytest.skip("single-device process; CI runs this with 4 host "
                    "devices via XLA_FLAGS")
    ref = sim.run_batch(sim.MODE_LUT, WLS, PARAMS, devices=1)
    tel = []
    shd = sim.run_batch(sim.MODE_LUT, WLS, PARAMS, batch_size=len(WLS),
                        devices=N_DEV, telemetry=tel)
    # every chunk's lanes were spread over all N_DEV devices
    assert [t["devices"] for t in tel] == [N_DEV] * len(tel)
    np.testing.assert_array_equal(np.asarray(ref.avg_exec_us),
                                  np.asarray(shd.avg_exec_us))
    np.testing.assert_array_equal(np.asarray(ref.finish),
                                  np.asarray(shd.finish))


def test_devices_knob_validation():
    with pytest.raises(ValueError, match="out of range"):
        sim.run_batch(sim.MODE_LUT, WLS, PARAMS, devices=N_DEV + 1)
    with pytest.raises(ValueError, match="not an integer"):
        import os
        os.environ["REPRO_BENCH_DEVICES"] = "lots"
        try:
            sim._resolve_devices(None)
        finally:
            del os.environ["REPRO_BENCH_DEVICES"]


def test_devices_env_knob(monkeypatch):
    monkeypatch.setenv("REPRO_BENCH_DEVICES", "1")
    r = sim.run_batch(sim.MODE_LUT, WLS, PARAMS, batch_size=2)
    ref = sim.run_batch(sim.MODE_LUT, WLS, PARAMS, devices=1)
    np.testing.assert_array_equal(np.asarray(ref.avg_exec_us),
                                  np.asarray(r.avg_exec_us))
