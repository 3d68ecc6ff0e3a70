"""Batched simulator path: stack_workloads / simulate_batch / run_batch.

Covers the PR-6 tentpole: per-scenario results of the vmapped sweep must
match the sequential `sim.run` path (bit-for-bit on CPU), oracle generation
must be identical through either path, chunking must not change results,
and the deadlock guard must terminate instead of spinning to `max_iters`.
"""
import numpy as np
import pytest

from repro.core import oracle, simulator as sim, workloads

PARAMS = sim.make_params()
SUITE = workloads.default_suite(n_instances=8)
CELLS = [(0, 0), (0, 13), (5, 0), (5, 13)]
WLS = [SUITE.build(mi, ri) for mi, ri in CELLS]

ALL_MODES = [sim.MODE_LUT, sim.MODE_ETF, sim.MODE_ETF_IDEAL, sim.MODE_DAS,
             sim.MODE_ORACLE, sim.MODE_THRESHOLD]

SCALARS = ("avg_exec_us", "total_energy_uj", "edp", "n_decisions",
           "n_fast", "n_slow", "n_done", "task_energy_uj",
           "sched_energy_uj")


def _mixed_tree() -> sim.DTree:
    """A depth-2 tree that actually splits on rate (some F, some S)."""
    import jax.numpy as jnp
    return sim.DTree(feat=jnp.array([sim.FEAT_RATE, 1, 1], jnp.int32),
                     thr=jnp.array([500.0, 4.0, 6.0], jnp.float32),
                     leaf=jnp.array([0, 1, 0, 1], jnp.int32))


# ---------------------------------------------------------------------------
# stack_workloads
# ---------------------------------------------------------------------------
def test_stack_workloads_shapes_and_values():
    stacked = workloads.stack_workloads(WLS)
    for name, field in zip(workloads.FlatWorkload._fields, stacked):
        assert field.shape[0] == len(WLS), name
        for k, wl in enumerate(WLS):
            np.testing.assert_array_equal(field[k], getattr(wl, name))


def test_stack_workloads_rejects_shape_mismatch():
    other = workloads.default_suite(n_instances=4).build(0, 0)
    with pytest.raises(ValueError, match="shape mismatch"):
        workloads.stack_workloads([WLS[0], other])


def test_build_many_matches_build():
    stacked = SUITE.build_many(CELLS)
    for k, wl in enumerate(WLS):
        np.testing.assert_array_equal(stacked.task_type[k], wl.task_type)
        np.testing.assert_array_equal(stacked.inst_arrival[k],
                                      wl.inst_arrival)


# ---------------------------------------------------------------------------
# batched vs sequential equivalence (all six modes)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", ALL_MODES)
def test_run_batch_matches_sequential(mode):
    tree = _mixed_tree() if mode == sim.MODE_DAS else None
    thr = 500.0
    rb = sim.run_batch(mode, WLS, PARAMS, tree=tree, rate_threshold=thr)
    for k, wl in enumerate(WLS):
        rs = sim.run(mode, wl, PARAMS, tree=tree, rate_threshold=thr)
        rk = sim.result_at(rb, k)
        for name in SCALARS:
            a = np.asarray(getattr(rs, name))
            b = np.asarray(getattr(rk, name))
            assert np.array_equal(a, b), (name, a, b)
        np.testing.assert_array_equal(np.asarray(rs.log_feat),
                                      np.asarray(rk.log_feat))
        np.testing.assert_array_equal(np.asarray(rs.finish),
                                      np.asarray(rk.finish))
        np.testing.assert_array_equal(np.asarray(rs.pe_of),
                                      np.asarray(rk.pe_of))


def test_run_batch_chunking_is_invariant():
    full = sim.run_batch(sim.MODE_LUT, WLS, PARAMS)
    # batch sizes that exercise no-pad, ragged-pad, and per-scenario
    # chunking; devices=1 pins the sharding knob for determinism
    for bs in (1, 2, 3):
        chunked = sim.run_batch(sim.MODE_LUT, WLS, PARAMS, batch_size=bs,
                                devices=1)
        for name in SCALARS:
            np.testing.assert_array_equal(np.asarray(getattr(full, name)),
                                          np.asarray(getattr(chunked, name)),
                                          err_msg=f"batch_size={bs} {name}")


def test_ragged_final_chunk_does_not_retrace():
    """n=8 with batch_size=5 pads the final chunk [3] -> [5]: the whole
    sweep must reuse ONE compiled executable (the pre-padding engine
    traced a second program for the remainder shape), and the padded
    results must match the unchunked sweep."""
    wls = WLS + WLS
    before = sim.TRACE_COUNT["simulate_batch"]
    chunked = sim.run_batch(sim.MODE_LUT, wls, PARAMS, batch_size=5,
                            devices=1)
    assert sim.TRACE_COUNT["simulate_batch"] - before <= 1
    full = sim.run_batch(sim.MODE_LUT, wls, PARAMS)
    for name in SCALARS:
        np.testing.assert_array_equal(np.asarray(getattr(full, name)),
                                      np.asarray(getattr(chunked, name)),
                                      err_msg=name)
    np.testing.assert_array_equal(np.asarray(full.finish),
                                  np.asarray(chunked.finish))


def test_run_batch_per_scenario_threshold():
    """`rate_threshold` with a leading [S] axis sweeps per scenario."""
    import jax.numpy as jnp
    wls = [WLS[1], WLS[1]]  # same high-rate scenario twice
    # never-slow vs always-slow (rate_est is 0 before two arrivals, so the
    # always-slow threshold must be <= 0)
    thr = jnp.array([1e9, 0.0], jnp.float32)
    r = sim.run_batch(sim.MODE_THRESHOLD, wls, PARAMS, rate_threshold=thr)
    assert int(r.n_slow[0]) == 0
    assert int(r.n_slow[1]) == int(r.n_decisions[1])


def test_run_batch_per_scenario_trees():
    """`tree` with a leading [S] axis selects a tree per scenario."""
    import jax
    fast = sim.always_fast_tree()
    slow = fast._replace(leaf=fast.leaf + 1)  # all leaves -> S
    trees = jax.tree_util.tree_map(lambda a, b: np.stack([a, b]), fast, slow)
    wls = [WLS[2], WLS[2]]
    r = sim.run_batch(sim.MODE_DAS, wls, PARAMS, tree=sim.DTree(
        *[np.asarray(x) for x in trees]))
    assert int(r.n_slow[0]) == 0
    assert int(r.n_slow[1]) == int(r.n_decisions[1])


# ---------------------------------------------------------------------------
# kernel-backed decision path (PR-10): REPRO_SIM_KERNELS on, bit-exact
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", ALL_MODES)
def test_run_batch_kernels_xla_matches_sequential(mode):
    """The fused-XLA decision path (`kernels="xla"`) must be bit-exact vs
    the inline-jnp sequential path for every mode — same first-global-min
    argmin tie-break, same push-time contribution max."""
    tree = _mixed_tree() if mode == sim.MODE_DAS else None
    rb = sim.run_batch(mode, WLS, PARAMS, tree=tree, rate_threshold=500.0,
                       kernels="xla")
    for k, wl in enumerate(WLS):
        rs = sim.run(mode, wl, PARAMS, tree=tree, rate_threshold=500.0,
                     kernels="off")
        rk = sim.result_at(rb, k)
        for name in SCALARS:
            assert np.array_equal(np.asarray(getattr(rs, name)),
                                  np.asarray(getattr(rk, name))), \
                (mode, k, name)
        np.testing.assert_array_equal(np.asarray(rs.finish),
                                      np.asarray(rk.finish))
        np.testing.assert_array_equal(np.asarray(rs.pe_of),
                                      np.asarray(rk.pe_of))


@pytest.mark.parametrize("mode", [sim.MODE_ETF, sim.MODE_DAS])
def test_run_kernels_pallas_interpret_matches(mode):
    """The Pallas kernels (interpret mode off-TPU — the TPU kernel's
    semantics) agree bit-exactly with the inline path. Sequential runs
    only: interpret mode pays a Python visit per grid step."""
    tree = _mixed_tree() if mode == sim.MODE_DAS else None
    wl = WLS[1]
    r0 = sim.run(mode, wl, PARAMS, tree=tree, rate_threshold=500.0,
                 kernels="off")
    rp = sim.run(mode, wl, PARAMS, tree=tree, rate_threshold=500.0,
                 kernels="pallas-interpret")
    for name in sim.SimResult._fields:
        a, b = np.asarray(getattr(r0, name)), np.asarray(getattr(rp, name))
        assert a.tobytes() == b.tobytes(), (mode, name, a, b)


def test_run_batch_kernels_telemetry():
    """`telemetry=[]` collects one record per dispatch: allocated vs
    active lane-trips, retired events, an occupancy in (0, 1], the loop's
    phase counters and the chunk's spans (tests/test_telemetry_spans.py
    holds the spans and counters to their meaning)."""
    tel = []
    r = sim.run_batch(sim.MODE_ETF, WLS, PARAMS, batch_size=2, devices=1,
                      kernels="xla", telemetry=tel)
    assert len(tel) == 2  # ceil(4/2) chunks
    assert sum(t["events"] for t in tel) == int(np.asarray(r.n_iters).sum())
    for t in tel:
        assert t["lanes"] == 2
        assert t["devices"] == 1
        assert 0 < t["active_trips"] <= t["lane_trips"]
        assert 0 < t["occupancy"] <= 1.0
        trips = t["lane_trips"] // t["lanes"]
        assert 0 < t["phase_trips"]["decide"] <= trips
        assert t["fault_eval_trips"] == t["fault_fire_trips"] == 0
        names = {sp["name"] for sp in t["spans"]}
        assert {"run_batch.dispatch", "run_batch.fetch"} <= names


def test_kernels_no_retrace_across_two_sweeps():
    """With kernels on, a second same-shape sweep must add ZERO retraces
    — the dispatch mode is a static jit arg, so flipping nothing reuses
    the warm executable."""
    cells_b = [(1, 1), (2, 3), (3, 5), (4, 7)]
    wls_b = [SUITE.build(mi, ri) for mi, ri in cells_b]
    sim.run_batch(sim.MODE_ETF, WLS, PARAMS, batch_size=2, devices=1,
                  kernels="xla")  # warm
    before = dict(sim.TRACE_COUNT)
    sim.run_batch(sim.MODE_ETF, wls_b, PARAMS, batch_size=2, devices=1,
                  kernels="xla")
    assert sim.TRACE_COUNT == before, (before, sim.TRACE_COUNT)


# ---------------------------------------------------------------------------
# oracle: batched == sequential, bit for bit
# ---------------------------------------------------------------------------
def test_oracle_generate_batched_equals_sequential():
    kw = dict(mix_indices=[0, 5], rate_indices=[0, 7], metric="avg_exec_us")
    ds_b = oracle.generate(SUITE, PARAMS, batched=True, batch_size=3, **kw)
    ds_s = oracle.generate(SUITE, PARAMS, batched=False, **kw)
    np.testing.assert_array_equal(ds_b.features, ds_s.features)
    np.testing.assert_array_equal(ds_b.labels, ds_s.labels)
    np.testing.assert_array_equal(ds_b.groups, ds_s.groups)
    np.testing.assert_array_equal(ds_b.rates, ds_s.rates)


# ---------------------------------------------------------------------------
# deadlock guard (PR-6 bugfix): stalls terminate, they don't spin
# ---------------------------------------------------------------------------
def _unschedulable(wl: workloads.FlatWorkload) -> workloads.FlatWorkload:
    """Instance 0 arrives but its roots are never released: its tasks can
    never become ready, so the run can't complete."""
    n_roots = np.array(wl.inst_n_roots)
    n_roots[0] = 0
    return wl._replace(inst_n_roots=n_roots)


def test_unschedulable_workload_stalls_early():
    wl = _unschedulable(WLS[0])
    r = sim.run(sim.MODE_LUT, wl, PARAMS)
    T = wl.task_type.shape[0]
    I = wl.inst_arrival.shape[0]
    max_iters = 3 * T + I + 64
    assert bool(r.stalled)
    assert int(r.n_done) < int(wl.n_tasks)
    # the old guard set now=now and spun until max_iters
    assert int(r.n_iters) < max_iters - 32
    # decision+completion per done task, arrivals, and <= one advance
    # between consecutive events
    assert int(r.n_iters) <= 3 * int(r.n_done) + 2 * I + 16


def test_healthy_workload_does_not_stall():
    r = sim.run(sim.MODE_LUT, WLS[0], PARAMS)
    assert not bool(r.stalled)
    assert int(r.n_done) == int(WLS[0].n_tasks)
