"""Fault injection, retry and graceful degradation (PR-7 tentpole).

Invariants under a `faults.FaultPlan`:
  * the healthy plan is the identity — bit-identical results to running
    without a plan, for every scheduler mode;
  * a fully-dead accelerator cluster degrades its task types to the CPU
    clusters and every job still completes;
  * retry exhaustion and per-job deadlines drop jobs instead of stalling,
    with consistent accounting;
  * the batched (vmapped) path is bit-exact with per-scenario `sim.run`
    when plans ride the scenario axis;
  * no completed task ever occupies a PE inside its dead window
    (hypothesis property, skips without the package);
  * the independent float64 reference simulator agrees under faults.
"""
import numpy as np
import pytest

from hyp_compat import hypothesis, st
from repro.core import faults, ref_sim, simulator as sim, soc, workloads

PARAMS = sim.make_params()
SUITE = workloads.default_suite(n_instances=8)
WL = SUITE.build(5, 6)

ALL_MODES = [sim.MODE_LUT, sim.MODE_ETF, sim.MODE_ETF_IDEAL, sim.MODE_DAS,
             sim.MODE_ORACLE, sim.MODE_THRESHOLD]
# fields that exist without fault injection (must be plan-invariant)
BASE_FIELDS = sim.SimResult._fields[:21]
FAULT_COUNTERS = ("n_faults", "n_retries", "reexec_us", "n_dropped_jobs",
                  "n_dropped_tasks", "recovery_us", "n_recovered")

FFT_PES = np.where(soc.PE_CLUSTER == soc.FFT_ACC)[0]
FFT_TYPES = [i for i, n in enumerate(soc.TASK_TYPE_NAMES)
             if n in ("fft", "ifft")]


def _tree():
    import jax.numpy as jnp
    return sim.DTree(feat=jnp.array([sim.FEAT_RATE, 1, 1], jnp.int32),
                     thr=jnp.array([500.0, 4.0, 6.0], jnp.float32),
                     leaf=jnp.array([0, 1, 0, 1], jnp.int32))


def _assert_results_equal(a, b, fields=sim.SimResult._fields):
    for name in fields:
        va, vb = np.asarray(getattr(a, name)), np.asarray(getattr(b, name))
        assert np.array_equal(va, vb, equal_nan=True), name


# ---------------------------------------------------------------------------
# healthy plan == no plan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", ALL_MODES)
def test_healthy_plan_is_identity(mode):
    kw = {"tree": _tree()} if mode == sim.MODE_DAS else {}
    if mode == sim.MODE_THRESHOLD:
        kw["rate_threshold"] = 600.0
    r0 = sim.run(mode, WL, PARAMS, **kw)
    r1 = sim.run(mode, WL, PARAMS, plan=faults.healthy_plan(), **kw)
    _assert_results_equal(r0, r1, BASE_FIELDS)
    for name in FAULT_COUNTERS:
        assert float(np.asarray(getattr(r1, name))) == 0.0, name
    assert not np.asarray(r1.job_dropped).any()


# ---------------------------------------------------------------------------
# graceful degradation: dead accelerator cluster -> CPU fallback
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", [sim.MODE_LUT, sim.MODE_ETF, sim.MODE_DAS])
def test_dead_fft_cluster_degrades_to_cpu(mode):
    plan = faults.fail_cluster(faults.healthy_plan(), soc.FFT_ACC, at=0.0)
    kw = {"tree": _tree()} if mode == sim.MODE_DAS else {}
    r = sim.run(mode, WL, PARAMS, plan=plan, **kw)
    healthy = sim.run(mode, WL, PARAMS, **kw)
    assert int(r.n_done) == int(WL.n_tasks)
    assert not bool(r.stalled)
    assert int(r.n_dropped_jobs) == 0
    pe_of = np.asarray(r.pe_of)[: int(WL.n_tasks)]
    assert not np.isin(pe_of, FFT_PES).any(), "task placed on a dead PE"
    tt = np.asarray(WL.task_type)[: int(WL.n_tasks)]
    fft_tasks = np.isin(tt, FFT_TYPES)
    assert fft_tasks.any()
    # fft work fell back to the CPU clusters => strictly slower on average
    assert float(r.avg_exec_us) > float(healthy.avg_exec_us)


def test_cluster_slowdown_stretches_exec():
    plan = faults.slow_cluster(faults.healthy_plan(), soc.LITTLE, 3.0)
    r = sim.run(sim.MODE_LUT, WL, PARAMS, plan=plan)
    healthy = sim.run(sim.MODE_LUT, WL, PARAMS)
    assert int(r.n_done) == int(WL.n_tasks)
    assert float(r.avg_exec_us) > float(healthy.avg_exec_us)


# ---------------------------------------------------------------------------
# retries, exhaustion, deadlines
# ---------------------------------------------------------------------------
def _transient_storm(times, pes=None, retries=0):
    plan = faults.with_retries(faults.healthy_plan(), retries)
    for pe in (range(soc.N_PES) if pes is None else pes):
        for t in times:
            plan = faults.add_transient(plan, int(pe), float(t))
    return plan


def test_transient_kills_and_recovers():
    plan = _transient_storm([1.0, 3.0], retries=4)
    r = sim.run(sim.MODE_ETF, WL, PARAMS, plan=plan)
    assert int(r.n_done) == int(WL.n_tasks)
    assert not bool(r.stalled)
    assert int(r.n_faults) > 0
    assert int(r.n_retries) == int(r.n_faults)  # budget never exhausted
    assert int(r.n_dropped_jobs) == 0
    assert int(r.n_recovered) > 0
    assert float(r.recovery_us) > 0
    assert float(r.reexec_us) >= 0


def test_retry_exhaustion_drops_jobs_and_terminates():
    plan = _transient_storm([1.0, 3.0], retries=0)
    r = sim.run(sim.MODE_ETF, WL, PARAMS, plan=plan)
    # every kill immediately exhausts the zero budget -> job drops
    assert int(r.n_faults) > 0
    assert int(r.n_retries) == 0
    assert int(r.n_dropped_jobs) > 0
    assert int(r.n_dropped_tasks) >= int(r.n_dropped_jobs)
    assert not bool(r.stalled)
    # dropped tasks count toward termination: the loop converges
    assert int(r.n_done) == int(WL.n_tasks)
    assert int(np.asarray(r.job_dropped).sum()) == int(r.n_dropped_jobs)


def test_deadline_drops_late_jobs():
    plan = faults.with_deadline(faults.healthy_plan(), 2.0)
    r = sim.run(sim.MODE_LUT, WL, PARAMS, plan=plan)
    assert int(r.n_dropped_jobs) > 0
    assert not bool(r.stalled)
    assert int(r.n_done) == int(WL.n_tasks)
    # dropped instances are excluded from the latency average
    inst = np.asarray(r.inst_exec_us)[: int(WL.n_insts)]
    dropped = np.asarray(r.job_dropped)[: int(WL.n_insts)]
    assert np.isnan(inst[dropped]).all()
    kept = inst[~dropped]
    if kept.size:
        assert np.isfinite(kept).all()
        assert (kept <= 2.0 + 1e-3).all()


# ---------------------------------------------------------------------------
# batched path bit-exactness under plans
# ---------------------------------------------------------------------------
PLANS = [
    faults.healthy_plan(),
    faults.fail_cluster(faults.healthy_plan(), soc.FFT_ACC, 0.0),
    faults.fail_pes(faults.with_retries(faults.healthy_plan(), 2),
                    [0, 8, 12], 2.0, repair_at=6.0),
    faults.with_deadline(
        faults.slow_cluster(faults.healthy_plan(), soc.BIG, 2.0), 40.0),
]


@pytest.mark.parametrize("mode", [sim.MODE_LUT, sim.MODE_ETF, sim.MODE_DAS])
def test_batched_matches_sequential_with_stacked_plans(mode):
    cells = [(0, 3), (5, 6), (5, 13), (1, 9)]
    wls = [SUITE.build(mi, ri) for mi, ri in cells]
    kw = {"tree": _tree()} if mode == sim.MODE_DAS else {}
    batched = sim.run_batch(mode, workloads.stack_workloads(wls), PARAMS,
                            plan=faults.stack_plans(PLANS), **kw)
    for k, (wl, plan) in enumerate(zip(wls, PLANS)):
        seq = sim.run(mode, wl, PARAMS, plan=plan, **kw)
        _assert_results_equal(sim.result_at(batched, k), seq)


def test_batched_shared_plan_and_chunking():
    plan = PLANS[2]
    wls = [SUITE.build(5, ri) for ri in (0, 4, 8, 13)]
    stacked = workloads.stack_workloads(wls)
    full = sim.run_batch(sim.MODE_ETF, stacked, PARAMS, plan=plan)
    chunked = sim.run_batch(sim.MODE_ETF, stacked, PARAMS, plan=plan,
                            batch_size=2)
    _assert_results_equal(full, chunked)
    for k, wl in enumerate(wls):
        seq = sim.run(sim.MODE_ETF, wl, PARAMS, plan=plan)
        _assert_results_equal(sim.result_at(full, k), seq)


# ---------------------------------------------------------------------------
# property: the availability mask is always respected
# ---------------------------------------------------------------------------
@hypothesis.settings(max_examples=10, deadline=None)
@hypothesis.given(st.integers(min_value=0, max_value=10_000))
def test_completed_tasks_never_occupy_dead_pes(seed):
    """No completed task's final run [start, finish) may overlap its PE's
    dead window [fail_at, repair_at)."""
    plan = faults.random_plan(seed, n_fail=3, n_transient=4,
                              t_horizon_us=60.0, max_retries=3)
    r = sim.run(sim.MODE_ETF, WL, PARAMS, plan=plan)
    assert not bool(r.stalled)
    nt = int(WL.n_tasks)
    done = np.asarray(r.finish)[:nt] > -np.inf
    done &= ~np.asarray(r.job_dropped)[np.asarray(WL.inst_id)[:nt]]
    pe_of = np.asarray(r.pe_of)[:nt]
    tt = np.asarray(WL.task_type)[:nt]
    exec_pe = np.asarray(PARAMS.exec_pe)  # slowdown is 1.0 in random_plan
    finish = np.asarray(r.finish)[:nt]
    start = finish - exec_pe[tt, np.clip(pe_of, 0, None)]
    fail = np.asarray(plan.pe_fail_at)[np.clip(pe_of, 0, None)]
    repair = np.asarray(plan.pe_repair_at)[np.clip(pe_of, 0, None)]
    overlap = done & (start < repair) & (fail < finish - 1e-6)
    assert not overlap.any(), np.where(overlap)[0][:5]


# ---------------------------------------------------------------------------
# reference-simulator differential under faults
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", [sim.MODE_LUT, sim.MODE_ETF])
@pytest.mark.parametrize("plan_idx", [1, 2])
def test_reference_sim_agrees_under_faults(mode, plan_idx):
    plan = PLANS[plan_idx]
    r_jax = sim.run(mode, WL, PARAMS, plan=plan)
    r_ref = ref_sim.simulate_ref(mode, WL, plan=plan)
    assert int(r_jax.n_done) == r_ref["n_done"]
    for name in ("n_faults", "n_retries", "n_dropped_jobs",
                 "n_dropped_tasks", "n_recovered"):
        assert int(np.asarray(getattr(r_jax, name))) == r_ref[name], name
    nt = int(WL.n_tasks)
    fin_jax = np.asarray(r_jax.finish)[:nt]
    fin_ref = r_ref["finish"][:nt]
    ok = np.isfinite(fin_jax) & np.isfinite(fin_ref)
    diff = np.abs(fin_jax[ok] - fin_ref[ok])
    assert (diff <= 1e-3 * max(1.0, float(np.abs(fin_ref[ok]).max()))
            ).mean() >= 0.98
    assert float(r_jax.avg_exec_us) == pytest.approx(
        r_ref["avg_exec_us"], rel=1e-3, abs=1e-3, nan_ok=True)


# ---------------------------------------------------------------------------
# validation errors
# ---------------------------------------------------------------------------
def test_validate_plan_rejects_malformed():
    with pytest.raises(ValueError, match="repair"):
        faults.validate_plan(faults.fail_pes(
            faults.healthy_plan(), [0], at=5.0, repair_at=1.0))
    with pytest.raises(ValueError, match="slowdown"):
        faults.validate_plan(faults.slow_cluster(
            faults.healthy_plan(), soc.BIG, 0.5))
    with pytest.raises(ValueError, match="max_retries"):
        faults.validate_plan(faults.with_retries(faults.healthy_plan(), -1))
    with pytest.raises(ValueError, match="trailing dim"):
        faults.validate_plan(faults.healthy_plan(n_pes=7))
    # run() rejects a batched plan; run_batch rejects a mis-sized one
    stacked = faults.stack_plans([faults.healthy_plan()] * 2)
    with pytest.raises(ValueError):
        sim.run(sim.MODE_LUT, WL, PARAMS, plan=stacked)
    with pytest.raises(ValueError):
        sim.run_batch(sim.MODE_LUT,
                      workloads.stack_workloads([WL, WL, WL]),
                      PARAMS, plan=stacked)


def test_validate_workload_rejects_malformed():
    wl = SUITE.build(0, 0)
    tt = np.array(wl.task_type)
    tt[2] = soc.N_TASK_TYPES
    with pytest.raises(ValueError, match="task_type"):
        workloads.validate_workload(wl._replace(task_type=tt))
    kb = np.array(wl.out_kb)
    kb[1] = -1.0
    with pytest.raises(ValueError, match="out_kb"):
        workloads.validate_workload(wl._replace(out_kb=kb))
    pr, npred = np.array(wl.preds), np.array(wl.n_preds)
    pr[1, 0], npred[1] = 1, 1  # self-dependency = 1-cycle
    with pytest.raises(ValueError, match="cycle"):
        workloads.validate_workload(wl._replace(preds=pr, n_preds=npred))


def test_validate_config_rejects_malformed():
    import dataclasses
    cfg = soc.default_soc()
    bad_lut = np.array(cfg.lut_cluster)
    bad_lut[0] = soc.FFT_ACC  # scrambler cannot run on the FFT accelerator
    with pytest.raises(ValueError, match="lut_cluster"):
        soc.validate_config(dataclasses.replace(cfg, lut_cluster=bad_lut))
    bad_power = np.array(cfg.cluster_power)
    bad_power[0] = -1.0
    with pytest.raises(ValueError, match="cluster_power"):
        soc.validate_config(dataclasses.replace(cfg, cluster_power=bad_power))


# ---------------------------------------------------------------------------
# plan-builder edge cases (hypothesis properties; skip without the package)
# ---------------------------------------------------------------------------
def test_stack_plans_rejects_zero_length():
    with pytest.raises(ValueError, match="at least one"):
        faults.stack_plans([])


@hypothesis.settings(max_examples=8, deadline=None)
@hypothesis.given(st.integers(min_value=0, max_value=10_000),
                  st.integers(min_value=1, max_value=4))
def test_stack_plans_slices_back_bit_exact(seed, n):
    """Stacking then indexing scenario k recovers plan k exactly, and the
    stacked plan still validates (leading axes are allowed)."""
    plans = [faults.random_plan(seed + k) for k in range(n)]
    stacked = faults.stack_plans(plans)
    assert faults.is_batched(stacked)
    faults.validate_plan(stacked)
    for k, p in enumerate(plans):
        for name, field in zip(faults.FaultPlan._fields, stacked):
            np.testing.assert_array_equal(
                np.asarray(field)[k], np.asarray(getattr(p, name)),
                err_msg=f"{name}[{k}]")


@hypothesis.settings(max_examples=6, deadline=None)
@hypothesis.given(st.floats(min_value=0.0, max_value=50.0,
                            allow_nan=False))
def test_all_pes_dead_finite_deadline_never_stalls(at):
    """Every PE permanently dead at `at` with a finite job deadline: the
    simulator must terminate by dropping, never by deadlocking."""
    plan = faults.with_deadline(
        faults.fail_pes(faults.healthy_plan(), range(soc.N_PES), at=at),
        2000.0)
    r = sim.run(sim.MODE_ETF, WL, PARAMS, plan=plan)
    assert not bool(r.stalled)
    assert int(r.stall_reason) == sim.STALL_NONE
    n_jobs = int(np.asarray(WL.inst_id).max()) + 1
    assert int(np.asarray(r.job_dropped).sum()) == int(r.n_dropped_jobs)
    if at == 0.0:
        assert int(r.n_dropped_jobs) == n_jobs  # nothing could ever run


def test_all_pes_dead_infinite_deadline_is_a_deadlock_stall():
    """The same scenario without a deadline cannot make progress and must
    be *reported* as a deadlock stall, not spin forever."""
    plan = faults.fail_pes(faults.healthy_plan(), range(soc.N_PES), at=0.0)
    r = sim.run(sim.MODE_ETF, WL, PARAMS, plan=plan)
    assert bool(r.stalled)
    assert int(r.stall_reason) == sim.STALL_DEADLOCK
    assert int(r.n_done) == 0


@hypothesis.settings(max_examples=8, deadline=None)
@hypothesis.given(st.integers(min_value=0, max_value=10_000))
def test_retry_budget_zero_never_retries(seed):
    """max_retries=0: a fault's kill is final — no re-enqueues, and each
    fault can take down at most the one job it interrupted."""
    plan = faults.random_plan(seed, n_fail=3, n_transient=4,
                              t_horizon_us=20.0, max_retries=0)
    r = sim.run(sim.MODE_ETF, WL, PARAMS, plan=plan)
    assert not bool(r.stalled)
    assert int(r.n_retries) == 0
    assert int(r.n_dropped_jobs) <= int(r.n_faults)


# ---------------------------------------------------------------------------
# static capability gating: plans that can never kill / drop skip those
# phases at trace time, bit-exactly
# ---------------------------------------------------------------------------
def test_plan_capabilities_flags():
    hp = faults.healthy_plan()
    assert faults.plan_capabilities(hp) == (False, False, False)
    p0 = faults.fail_pes(hp, [0, 1], at=0.0)
    # fail at t=0 can kill nothing (assignments need assign_t < tau)
    assert faults.plan_capabilities(p0) == (True, False, False)
    pt = faults.fail_pes(hp, [0], at=25.0)
    assert faults.plan_capabilities(pt) == (True, True, False)
    pd = faults.with_deadline(hp, 1e4)
    assert faults.plan_capabilities(pd) == (False, False, True)
    tr = faults.add_transient(hp, 3, at=40.0)
    assert faults.plan_capabilities(tr) == (False, True, False)


@pytest.mark.parametrize("mode", [sim.MODE_LUT, sim.MODE_ETF, sim.MODE_DAS])
def test_gated_kill_phase_bit_exact_vs_full_machinery(mode):
    """A fail-at-t=0 plan traces without the kill/drop machinery
    (`can_kill=False`). Adding one finite transient far past the makespan
    forces the FULL machinery back in while firing nothing — both
    specializations must agree bit-for-bit, sequential and batched."""
    kw = {"tree": _tree()} if mode == sim.MODE_DAS else {}
    base = faults.fail_cluster(faults.healthy_plan(), soc.FFT_ACC, at=0.0)
    armed = faults.add_transient(base, 0, at=1e30)   # finite, never fires
    assert faults.plan_capabilities(base) == (True, False, False)
    assert faults.plan_capabilities(armed) == (True, True, False)
    r_gated = sim.run(mode, WL, PARAMS, plan=base, **kw)
    r_full = sim.run(mode, WL, PARAMS, plan=armed, **kw)
    _assert_results_equal(r_gated, r_full)

    wl_b = workloads.stack_workloads([WL] * 3)
    rb_g = sim.run_batch(mode, wl_b, PARAMS,
                         plan=faults.stack_plans([base] * 3),
                         batch_size=2, **kw)
    rb_f = sim.run_batch(mode, wl_b, PARAMS,
                         plan=faults.stack_plans([armed] * 3),
                         batch_size=2, **kw)
    _assert_results_equal(rb_g, rb_f)
    _assert_results_equal(r_gated, sim.result_at(rb_g, 1))


def test_gated_deadline_phase_bit_exact_when_slack():
    """A deadline far beyond the makespan (finite -> full machinery) vs no
    deadline (gated) on an otherwise identical degraded plan: nothing
    drops, results identical."""
    base = faults.fail_cluster(faults.healthy_plan(), soc.FFT_ACC, at=0.0)
    slack = faults.with_deadline(base, 1e30)
    assert faults.plan_capabilities(slack)[2]
    r_gated = sim.run(sim.MODE_ETF, WL, PARAMS, plan=base)
    r_full = sim.run(sim.MODE_ETF, WL, PARAMS, plan=slack)
    assert int(r_full.n_dropped_jobs) == 0
    _assert_results_equal(r_gated, r_full)


# ---------------------------------------------------------------------------
# `_drop_instance`'s dense masked writes against the gated-scatter form
# ---------------------------------------------------------------------------
def _scatter_drop(p, wl, s, inst, active=None):
    """`_drop_instance` written with gated scatters (test-only oracle):
    every task-length write is `x.at[where(mask, idx, OOB)].op(v,
    mode="drop")`, and the per-PE fields scatter tasks onto their PEs."""
    import jax.numpy as jnp
    T = s.status.shape[0]
    P = s.pe_free.shape[0]
    ar = jnp.arange(T)
    inst = jnp.maximum(inst, 0)
    victim = (wl.inst_id == inst) & wl.task_valid & (s.status < 4)
    if active is not None:
        victim &= active
    n_v = victim.sum().astype(jnp.int32)
    runn = victim & (s.status == 3)
    pe = jnp.maximum(s.pe_of, 0)
    exec_total = jnp.where(runn, s.finish - s.start, 0.0)
    executed = jnp.where(runn, jnp.clip(s.now - s.start, 0.0, exec_total),
                         0.0)
    unexec = exec_total - executed
    pe_ix = jnp.where(runn, pe, P)
    pe_busy = s.pe_busy.at[pe_ix].add(-unexec, mode="drop")
    e_back = (jnp.where(runn, unexec * p.pe_power[pe], 0.0)).sum()
    pe_hit = jnp.zeros(P, bool).at[pe_ix].set(True, mode="drop")
    surv = (s.status == 3) & ~victim
    surv_fin = jnp.full(P, sim._NEG).at[jnp.where(surv, pe, P)].max(
        s.finish, mode="drop")
    pe_free = jnp.where(pe_hit, jnp.maximum(surv_fin, s.now), s.pe_free)
    vix = jnp.where(victim, ar, T)
    fin_run = s.fin_run.at[jnp.where(runn, ar, s.fin_run.shape[0])].set(
        sim._INF, mode="drop")
    in_q = s.ready_ids >= 0
    is_v = jnp.where(in_q, victim[jnp.maximum(s.ready_ids, 0)], False)
    keep = in_q & ~is_v
    perm = jnp.argsort((~keep).astype(jnp.int32))
    new_cnt = keep.sum().astype(jnp.int32)
    ids_p = jnp.where(jnp.arange(sim.R_MAX) < new_cnt, s.ready_ids[perm], -1)
    gate = sim._gate
    return s._replace(
        status=s.status.at[vix].set(5, mode="drop"),
        finish=s.finish.at[vix].set(sim._NEG, mode="drop"),
        fin_run=fin_run, fin_seg=fin_run.reshape(-1, sim.SEG).min(axis=1),
        start=s.start.at[vix].set(sim._INF, mode="drop"),
        assign_t=s.assign_t.at[vix].set(sim._INF, mode="drop"),
        pe_busy=pe_busy, pe_free=pe_free,
        task_energy=gate(active, s.task_energy - e_back, s.task_energy),
        n_running=s.n_running - runn.sum().astype(jnp.int32),
        n_done=s.n_done + n_v,
        n_dropped_tasks=s.n_dropped_tasks + n_v,
        ready_ids=gate(active, ids_p, s.ready_ids),
        ready_avail=gate(active, s.ready_avail[perm], s.ready_avail),
        ready_exec=gate(active, s.ready_exec[perm], s.ready_exec),
        ready_cnt=gate(active, new_cnt, s.ready_cnt),
        inst_rem=sim._gset(active, s.inst_rem, inst, 0),
        job_dropped=sim._gset(active, s.job_dropped, inst, True),
    )


def _random_drop_state(seed: int, case: str):
    """(state, inst): a mid-run state with the victim instance `inst`
    shaped by `case` — `stacked` puts its running tasks two or three to
    a PE, `done` leaves most of it finished or dropped already, `fifo`
    queues some of it in the ready FIFO, `mixed` draws everything."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    T, P = WL.task_type.shape[0], soc.N_PES
    valid = np.asarray(WL.task_valid)
    inst_id = np.asarray(WL.inst_id)
    inst = int(rng.integers(int(WL.n_insts)))
    mine = np.where(valid & (inst_id == inst))[0]
    status = rng.choice(np.array([0, 2, 3, 4, 5], np.int8), size=T,
                        p=[0.2, 0.2, 0.3, 0.2, 0.1])
    if case == "stacked":
        status[mine] = 3
        status[mine[rng.random(mine.size) < 0.2]] = 4
    elif case == "done":
        status[mine] = rng.choice(np.array([4, 5], np.int8), mine.size)
        status[mine[0]] = 3
    elif case == "fifo":
        status[mine] = rng.choice(np.array([2, 3], np.int8), mine.size)
    status[~valid] = 0
    sched = (status == 3) | (status == 4)
    pe_of = np.where(sched, rng.integers(P, size=T), -1).astype(np.int32)
    if case == "stacked":
        run_mine = mine[status[mine] == 3]
        pe_of[run_mine] = rng.choice(rng.choice(P, 3, replace=False),
                                     run_mine.size)
    now = np.float32(rng.uniform(5.0, 50.0))
    start = np.where(sched, rng.uniform(0.0, 60.0, T), np.inf)
    finish = np.where(sched, start + rng.uniform(0.05, 5.0, T), np.inf)
    finish = np.where(status == 5, -np.inf, finish).astype(np.float32)
    start = start.astype(np.float32)
    s0 = sim._init_state(WL, P)
    fin_run = np.full(s0.fin_run.shape, np.inf, np.float32)
    fin_run[:T] = np.where(status == 3, finish, np.inf)
    queued = np.where(status == 2)[0]
    rng.shuffle(queued)
    queued = queued[:sim.R_MAX]
    ready_ids = np.full(sim.R_MAX, -1, np.int32)
    ready_ids[:queued.size] = queued
    s = s0._replace(
        now=now, status=status, pe_of=pe_of, start=start, finish=finish,
        fin_run=fin_run, fin_seg=fin_run.reshape(-1, sim.SEG).min(axis=1),
        assign_t=np.where(status == 3, start - 0.5, np.inf).astype(
            np.float32),
        pe_busy=rng.uniform(0.0, 100.0, P).astype(np.float32),
        pe_free=rng.uniform(0.0, 60.0, P).astype(np.float32),
        ready_ids=ready_ids, ready_cnt=np.int32(queued.size),
        ready_avail=rng.uniform(0.0, 60.0, (sim.R_MAX, P)).astype(
            np.float32),
        ready_exec=rng.uniform(0.05, 5.0, (sim.R_MAX, P)).astype(
            np.float32),
        n_running=np.int32((status == 3).sum()),
        n_done=np.int32((status >= 4).sum()),
        task_energy=np.float32(rng.uniform(100.0, 200.0)),
        inst_rem=rng.integers(0, 20, np.asarray(WL.inst_arrival).shape[0]
                              ).astype(np.int32))
    return jax.tree_util.tree_map(jnp.asarray, s), np.int32(inst)


def _assert_drop_matches(dense, oracle):
    for name in sim.SimState._fields:
        a, b = np.asarray(getattr(dense, name)), np.asarray(
            getattr(oracle, name))
        assert np.array_equal(a, b, equal_nan=True), name


def _lost_per_pe(s, inst):
    """[P] running tasks of `inst` each PE loses to an active drop."""
    victim = (np.asarray(WL.inst_id) == inst) & np.asarray(WL.task_valid) \
        & (np.asarray(s.status) < 4)
    runn = victim & (np.asarray(s.status) == 3)
    return np.bincount(np.asarray(s.pe_of)[runn], minlength=soc.N_PES)


@pytest.mark.parametrize("active", [None, True, False])
@pytest.mark.parametrize("case", ["stacked", "done", "fifo", "mixed"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_drop_instance_dense_matches_scatter_form(case, active, seed):
    import jax.numpy as jnp
    s, inst = _random_drop_state(seed, case)
    act = None if active is None else jnp.asarray(active)
    dense = sim._drop_instance(PARAMS, WL, s, inst, active=act)
    oracle = _scatter_drop(PARAMS, WL, s, inst, active=act)
    if case == "stacked" and active is not False:
        # several tails come off one PE: the roll-back keeps task order
        assert _lost_per_pe(s, inst).max() >= 2
    if case == "fifo":
        assert (np.asarray(WL.inst_id)[np.asarray(s.ready_ids)[
            : int(s.ready_cnt)]] == inst).any()
    _assert_drop_matches(dense, oracle)
    if active is False:
        # an inactive gate is the identity
        for name in sim.SimState._fields:
            assert np.array_equal(np.asarray(getattr(dense, name)),
                                  np.asarray(getattr(s, name)),
                                  equal_nan=True), name


def test_drop_instance_dense_matches_scatter_form_vmapped():
    """The batched engine's form: one lane per state, gates mixed."""
    import jax
    cases = ["stacked", "done", "fifo", "mixed"] * 2
    states = [_random_drop_state(10 + k, c) for k, c in enumerate(cases)]
    s = jax.tree_util.tree_map(lambda *x: np.stack(x),
                               *[st_ for st_, _ in states])
    insts = np.array([i for _, i in states], np.int32)
    active = np.arange(len(cases)) % 3 != 2
    wl = workloads.stack_workloads([WL] * len(cases))

    def lanes(fn):
        return jax.vmap(lambda w, st_, i, a: fn(PARAMS, w, st_, i, a))(
            wl, s, insts, active)

    dense, oracle = lanes(sim._drop_instance), lanes(_scatter_drop)
    for k in range(len(cases)):
        _assert_drop_matches(jax.tree_util.tree_map(lambda x: x[k], dense),
                             jax.tree_util.tree_map(lambda x: x[k], oracle))
