"""Jittable discrete-event DSSoC simulator (DS3-style) in pure JAX.

One `lax.while_loop` iteration handles exactly one of, in priority order:
  1. a task completion whose finish time is due (finish <= now),
  2. a frame (application-instance) arrival that is due,
  3. one scheduling decision if the ready queue is non-empty,
  4. otherwise advance simulated time to the next event.

Scheduling overhead is modeled faithfully to the paper: the scheduler is a
serial resource (`sched_free`); each decision occupies it for the policy's
latency and burns the policy's energy; a scheduled task cannot start before
its decision completes.

Modes
-----
  MODE_LUT        fast scheduler only (paper's F)
  MODE_ETF        slow scheduler only (paper's S, Algorithm 1)
  MODE_ETF_IDEAL  ETF with zero scheduling overhead (paper's ETF-ideal)
  MODE_DAS        depth-2 decision tree preselects F or S per decision
  MODE_ORACLE     run both schedulers per decision, follow F, log agreement
                  (paper's "first execution" for oracle generation)
  MODE_THRESHOLD  static data-rate threshold picks F or S (paper's heuristic)

The whole simulation jits; `simulate` is wrapped in `jax.jit` with the mode
and capacity constants static.

Batched sweeps
--------------
The (workload-mix x data-rate) grids behind the paper's Fig. 2 / Table 2 /
40-workload summary all run the same jitted loop over same-shape workloads,
so the scenario axis vmaps: `stack_workloads` (workloads.py) stacks a suite's
`FlatWorkload`s into a leading axis and `simulate_batch` / `run_batch` map
`simulate` over it (`SimParams` held constant; `tree` / `rate_threshold`
optionally per-scenario for DAS / threshold sweeps). Every `SimResult` field
gains a leading scenario axis; `result_at` slices one scenario back out.
`run_batch` additionally chunks the axis into fixed-shape, padded chunks
(one compiled executable per sweep), shards each chunk across devices
(`devices=` / `REPRO_BENCH_DEVICES`, see DESIGN.md "Sharded sweeps") and
streams all chunks through the device queue before one blocking fetch.

Fault injection and graceful degradation
----------------------------------------
Passing a `faults.FaultPlan` (`plan=` on `simulate` / `run` / `run_batch`)
threads a fault model through the same event loop, adding three event
classes between completions and arrivals:

  kill      a permanent PE failure or transient glitch revokes every
            assignment made on that PE before the fault instant; the task
            re-enters the FIFO tail (bounded by `plan.max_retries`, after
            which its whole job is dropped),
  deadline  a job (application instance) still incomplete `deadline_us`
            after its arrival is dropped with full accounting instead of
            spinning toward the `stalled` guard,
  drop      (inside kill/deadline) cancels every unfinished task of a job
            and purges them from the ready queue.

Schedulers degrade rather than fail: the LUT falls back to the most
energy-efficient *healthy* cluster for the task type (accelerated tasks
degrade to the CPU clusters when their accelerator is fully dead), ETF
masks dead PEs out of its earliest-finish-time search, and a decision is
only taken when the chosen scheduler has a feasible (task, PE) pair —
otherwise time advances to the next event, which now includes repairs,
fault instants and job deadlines. Cluster slowdown factors stretch the
cached exec rows at ready-queue push time.

`plan=None` (the default) traces the exact pre-fault computation — zero
overhead and bit-identical results — and `plan=faults.healthy_plan()`
runs the fault path with nothing failing, which the tests assert is also
bit-identical. Batched sweeps accept a plan with a leading scenario axis
(`faults.stack_plans`), batching fault scenarios like `tree` /
`rate_threshold`.
"""
from __future__ import annotations

import contextlib
import functools
import os
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec

from repro.core import faults as flt
from repro.core import soc
from repro.core.workloads import FlatWorkload, FRAME_KBITS, stack_workloads
from repro.kernels.etf_ft import ops as _kops

MODE_LUT = 0
MODE_ETF = 1
MODE_ETF_IDEAL = 2
MODE_DAS = 3
MODE_ORACLE = 4
MODE_THRESHOLD = 5

MODE_NAMES = {
    MODE_LUT: "LUT",
    MODE_ETF: "ETF",
    MODE_ETF_IDEAL: "ETF-ideal",
    MODE_DAS: "DAS",
    MODE_ORACLE: "oracle",
    MODE_THRESHOLD: "threshold",
}

# Ready-queue capacity (compact buffer). The queue fully drains before
# simulated time advances (decisions outrank the advance branch), so depth
# is bounded by simultaneous task releases, not workload size — measured
# max is 12 across the 40x14 suite at 60 instances. 16 leaves headroom and
# keeps the per-decision [R, MP, P] availability tensor small;
# `ready_drop` counts overflows and the tests assert it stays 0.
R_MAX = 16
SEG = 32            # fin_run segment size for the two-level next-completion
#   search: `fin_seg[k] == fin_run[k*SEG:(k+1)*SEG].min()` is maintained
#   incrementally, so the hot loop reduces over [T/SEG] instead of [T].
RING = 8            # data-rate shift register entries (paper: 8x16bit)
N_FEATURES = 62     # performance-counter feature bank size (paper Table I)
_INF = jnp.float32(jnp.inf)
# The phases of one `_masked_step` super-step, in its priority order: the
# order of `SimState.fired` and `BatchTelemetry.phase_trips`, and the names
# of the `jax.named_scope`s that tag each phase's ops in a profile.
PHASES = ("completion", "kill", "deadline", "arrival", "decide", "advance")
_NEG = jnp.float32(-jnp.inf)


class SimParams(NamedTuple):
    """Device-side hardware tables (from `soc.SoCConfig`)."""

    exec_pe: jax.Array        # [n_types, P] f32 (inf = cannot run)
    pe_cluster: jax.Array     # [P] i32
    pe_power: jax.Array       # [P] f32
    lut_cluster: jax.Array    # [n_types] i32
    cluster_pe_mask: jax.Array  # [C, P] bool
    us_per_kb: jax.Array      # [] f32
    cluster_energy: jax.Array  # [n_types, C] f32 (inf = cannot run); ranks
    #   the LUT's per-type fallback order when clusters die.


def make_params(cfg: soc.SoCConfig | None = None) -> SimParams:
    cfg = cfg or soc.default_soc()
    soc.validate_config(cfg)
    return SimParams(
        exec_pe=jnp.asarray(cfg.exec_on_pe()),
        pe_cluster=jnp.asarray(cfg.pe_cluster),
        pe_power=jnp.asarray(cfg.cluster_power[cfg.pe_cluster]),
        lut_cluster=jnp.asarray(cfg.lut_cluster),
        cluster_pe_mask=jnp.asarray(cfg.cluster_pe_mask),
        us_per_kb=jnp.float32(cfg.us_per_kb),
        cluster_energy=jnp.asarray(cfg.task_energy),
    )


class DTree(NamedTuple):
    """Depth-2 decision tree over the feature vector (3 internal nodes).

    node 0 is the root; node 1 is the left child (feature < thr), node 2 the
    right child. Leaves: [LL, LR, RL, RR], value 1 => use the slow scheduler.
    """

    feat: jax.Array    # [3] i32 feature indices
    thr: jax.Array     # [3] f32 thresholds
    leaf: jax.Array    # [4] i32 in {0, 1}

    def predict(self, f: jax.Array) -> jax.Array:
        # Every node's test is evaluated and the path selected with
        # one-hot masks, not gathers: on a TPU v5e the batched DAS
        # program with the gather form (`f[feat[node]]`, `leaf[idx]`)
        # and the Pallas decision kernels never finished at 140 lanes
        # (it did at 560); this form does at both. Adding zeros is
        # exact, so the selected values are the features themselves.
        hot = self.feat[:, None] == jnp.arange(f.shape[-1])       # [3, F]
        val = jnp.where(hot, f[None, :], 0).sum(axis=1)           # [3]
        right = val >= self.thr
        rightc = jnp.where(right[0], right[2], right[1])
        idx = jnp.where(right[0], 2, 0) + rightc.astype(jnp.int32)
        return jnp.where(jnp.arange(4) == idx, self.leaf, 0).sum()


def always_fast_tree() -> DTree:
    return DTree(feat=jnp.zeros(3, jnp.int32), thr=jnp.full(3, jnp.inf),
                 leaf=jnp.zeros(4, jnp.int32))


class SimState(NamedTuple):
    now: jax.Array          # [] f32
    stalled: jax.Array      # [] bool no event can ever become due again
    sched_free: jax.Array   # [] f32 scheduler-core availability
    arr_ptr: jax.Array      # [] i32 next instance to arrive
    n_done: jax.Array       # [] i32
    n_sched: jax.Array      # [] i32 tasks scheduled so far
    status: jax.Array       # [T] i8 0=waiting 2=ready 3=running 4=done
    pred_rem: jax.Array     # [T] i32
    start: jax.Array        # [T] f32
    finish: jax.Array       # [T] f32 (inf until scheduled)
    fin_run: jax.Array      # [Tp] f32 finish while running, else inf.
    #   Incremental mirror of `where(status == 3, finish, inf)` so the hot
    #   loop finds the next completion without rebuilding the mask from
    #   status/finish. Padded to Tp = ceil(T/SEG)*SEG with inf.
    fin_seg: jax.Array      # [Tp/SEG] f32 per-segment min of fin_run.
    #   Invariant: fin_seg[k] == fin_run[k*SEG:(k+1)*SEG].min(); updated by
    #   a scatter-min on assign and a SEG-sized rescan on completion, so
    #   finding the next completion scans [Tp/SEG] + [SEG], not [T].
    n_running: jax.Array    # [] i32 count of status==3 tasks
    pe_of: jax.Array        # [T] i32 (-1 until scheduled)
    pe_free: jax.Array      # [P] f32
    pe_busy: jax.Array      # [P] f32 accumulated busy time
    ready_ids: jax.Array    # [R_MAX] i32 FIFO, -1 = empty
    ready_cnt: jax.Array    # [] i32
    ready_drop: jax.Array   # [] i32 overflow counter (should stay 0)
    ready_avail: jax.Array  # [R_MAX, P] f32 cached availability-with-comm
    #   rows, computed once at push time (`_avail_rows`): a ready task's
    #   preds are all finished, so its availability per PE never changes.
    ready_exec: jax.Array   # [R_MAX, P] f32 cached exec_pe rows.
    #   Rows at slots >= ready_cnt are stale garbage; every consumer masks
    #   on `ready_ids >= 0`.
    task_energy: jax.Array  # [] f32 uJ
    sched_energy: jax.Array  # [] f32 uJ
    sched_time: jax.Array   # [] f32 us of scheduler occupancy
    n_fast: jax.Array       # [] i32
    n_slow: jax.Array       # [] i32
    ring: jax.Array         # [RING] f32 last arrival timestamps
    ring_ptr: jax.Array     # [] i32
    arr_count: jax.Array    # [] i32
    # decision logs (capacity T)
    d_ptr: jax.Array        # [] i32
    log_feat: jax.Array     # [T, N_FEATURES] f32
    log_policy: jax.Array   # [T] i8 (0 fast, 1 slow)
    log_agree: jax.Array    # [T] i8 (oracle: fast/slow decisions identical)
    log_task: jax.Array     # [T] i32
    # fault / degradation state (written only when a FaultPlan is threaded;
    # status gains 5 = dropped with its job)
    pe_alive: jax.Array     # [P] bool live availability mask (refreshed from
    #   the plan's fail/repair windows whenever `now` moves)
    pe_slow: jax.Array      # [P] f32 exec-time multiplier (throttling)
    assign_t: jax.Array     # [T] f32 decision time of the live assignment;
    #   a fault at time tau only revokes assignments with assign_t < tau
    retries: jax.Array      # [T] i32 fault-kill count per task
    kill_t: jax.Array       # [T] f32 time of the last kill (recovery base)
    inst_rem: jax.Array     # [I] i32 unfinished tasks per instance
    job_dropped: jax.Array  # [I] bool instance was dropped
    n_kills: jax.Array      # [] i32 fault events that revoked an assignment
    n_retries: jax.Array    # [] i32 kills that re-enqueued (vs dropped)
    reexec_us: jax.Array    # [] f32 executed work revoked then redone
    n_dropped_tasks: jax.Array  # [] i32
    recovery_us: jax.Array  # [] f32 sum over recovered tasks of
    #   (final finish - last kill time)
    n_recovered: jax.Array  # [] i32 killed tasks that eventually finished
    fired: jax.Array        # [6] i32 phases of `PHASES` the last batched
    #   super-step fired (1) or not (0); telemetry only, never a result


class SimResult(NamedTuple):
    avg_exec_us: jax.Array     # [] f32 mean instance latency
    makespan_us: jax.Array     # [] f32
    total_energy_uj: jax.Array  # [] f32 (task + scheduling energy)
    task_energy_uj: jax.Array
    sched_energy_uj: jax.Array
    sched_time_us: jax.Array
    edp: jax.Array             # [] f32 total energy * avg exec time
    n_decisions: jax.Array     # [] i32
    n_fast: jax.Array
    n_slow: jax.Array
    n_done: jax.Array
    ready_drop: jax.Array
    n_iters: jax.Array         # [] i32 while-loop iterations consumed
    stalled: jax.Array         # [] bool sim gave up (unschedulable tasks)
    inst_exec_us: jax.Array    # [I] f32 per-instance latency (inf = invalid)
    # oracle / analysis logs
    log_feat: jax.Array
    log_policy: jax.Array
    log_agree: jax.Array
    log_task: jax.Array
    finish: jax.Array          # [T] f32
    pe_of: jax.Array           # [T] i32
    # fault / degradation accounting (all zero without a FaultPlan)
    n_faults: jax.Array        # [] i32 kill events (assignment revocations)
    n_retries: jax.Array       # [] i32 kills that re-enqueued the task
    reexec_us: jax.Array       # [] f32 executed work revoked then redone
    n_dropped_jobs: jax.Array  # [] i32 instances dropped (deadline / retries)
    n_dropped_tasks: jax.Array  # [] i32 tasks cancelled with their job
    recovery_us: jax.Array     # [] f32 sum of (finish - last kill) over
    #   killed tasks that eventually completed
    n_recovered: jax.Array     # [] i32 killed tasks that completed anyway
    job_dropped: jax.Array     # [I] bool per-instance drop flags
    # stall diagnostics (appended last: fields[:21] are the stable
    # pre-fault prefix other code indexes by position)
    stall_reason: jax.Array    # [] i32 STALL_NONE / STALL_DEADLOCK /
    #   STALL_BUDGET (iteration cap or `step_budget` hit before draining)


# `SimResult.stall_reason` values
STALL_NONE = 0      # drained the workload (or dropped the remainder)
STALL_DEADLOCK = 1  # no event can ever become due again (`stalled` flag)
STALL_BUDGET = 2    # hit `max_iters` / `step_budget` with work remaining


# ---------------------------------------------------------------------------
# feature bank (paper Table I: task / PE / system counters, 62 total)
# ---------------------------------------------------------------------------
def _features(p: SimParams, wl: FlatWorkload, s: SimState) -> jax.Array:
    now = s.now
    cnt = jnp.minimum(s.arr_count, RING)
    oldest = jnp.where(
        s.arr_count >= RING, s.ring[s.ring_ptr % RING],
        s.ring[0],
    )
    newest = s.ring[(s.ring_ptr - 1) % RING]
    span = jnp.maximum(newest - oldest, 1e-3)
    rate_est = jnp.where(
        cnt >= 2,
        (cnt - 1).astype(jnp.float32) * FRAME_KBITS * 1000.0 / span,
        0.0,
    )  # Mbps

    pe_avail = jnp.maximum(s.pe_free - now, 0.0)              # [P]
    cl_avail = jnp.where(
        p.cluster_pe_mask, pe_avail[None, :], _INF
    ).min(axis=1)                                             # [C]
    util = s.pe_busy / jnp.maximum(now, 1e-3)                 # [P]

    head = s.ready_ids[0]
    head_ok = head >= 0
    h = jnp.maximum(head, 0)
    htype = wl.task_type[h]
    hpreds = wl.preds[h]                                      # [MP]
    hvalid = jnp.arange(hpreds.shape[0]) < wl.n_preds[h]
    pred_cl = jnp.where(
        hvalid & (hpreds >= 0),
        p.pe_cluster[jnp.maximum(s.pe_of[jnp.maximum(hpreds, 0)], 0)],
        -1,
    )
    pred_cl = jnp.pad(pred_cl, (0, max(0, 4 - pred_cl.shape[0])),
                      constant_values=-1)[:4]
    lut_cl = p.lut_cluster[htype]
    lut_pe = p.cluster_pe_mask[lut_cl].argmax()   # first PE of LUT cluster

    def z(x):
        return jnp.where(head_ok, x.astype(jnp.float32), 0.0)

    feats = jnp.concatenate([
        jnp.array([rate_est, s.ready_cnt.astype(jnp.float32)]),
        cl_avail,                                  # 6
        pe_avail,                                  # 19
        util,                                      # 19
        jnp.array([
            z(htype), z(wl.depth[h]), z(wl.app_id[h]), z(wl.out_kb[h]),
            z(p.exec_pe[htype, 0]),                        # exec on big
            z(p.exec_pe[htype, lut_pe]),                   # exec on LUT PE
            z(p.exec_pe[htype, lut_pe] * p.pe_power[lut_pe]),
            z(wl.n_preds[h]),
        ]),
        pred_cl.astype(jnp.float32),               # 4
        jnp.array([
            jnp.maximum(s.sched_free - now, 0.0),
            s.arr_count.astype(jnp.float32),
            s.n_done.astype(jnp.float32)
            / jnp.maximum(wl.n_tasks.astype(jnp.float32), 1.0),
            s.n_running.astype(jnp.float32),
        ]),
    ])
    assert feats.shape == (N_FEATURES,), feats.shape
    return feats


FEAT_RATE = 0           # input data rate (paper's #1 feature)
FEAT_BIG_AVAIL = 2      # earliest availability of the big cluster (#2)
FEAT_NAMES = (
    ["input_data_rate", "ready_queue_len"]
    + [f"cluster_avail_{c}" for c in soc.CLUSTER_NAMES]
    + [f"pe_avail_{i}" for i in range(soc.N_PES)]
    + [f"pe_util_{i}" for i in range(soc.N_PES)]
    + ["head_type", "head_depth", "head_app", "head_out_kb",
       "head_exec_big", "head_exec_lut", "head_energy_lut", "head_n_preds"]
    + [f"head_pred_cluster_{k}" for k in range(4)]
    + ["sched_backlog", "arrivals_so_far", "done_frac", "running_count"]
)


# ---------------------------------------------------------------------------
# scheduler decision helpers
# ---------------------------------------------------------------------------
def _avail_rows(p: SimParams, wl: FlatWorkload, s: SimState,
                tasks: jax.Array, bases: jax.Array,
                kmode: str = "off") -> jax.Array:
    """[K, P] availability (incl. NoC transfer from pred clusters).

    Evaluated once per task at push time: a task enters the ready queue
    only when every predecessor has finished, so pred finish times, pred
    placements, and hence this whole row are constants from then on. The
    rows are cached in `SimState.ready_avail` — recomputing the [R, MP, P]
    tensor at every decision was the single hottest part of the batched
    sweep loop. With `kmode != "off"` the [K, MP, P] contribution max
    routes through the fused push-time kernel (`kernels/etf_ft/ops.py`),
    bitwise identical to the inline tensor.
    """
    t = jnp.maximum(tasks, 0)                       # [K]
    preds = wl.preds[t]                             # [K, MP]
    pv = (jnp.arange(preds.shape[1])[None, :] < wl.n_preds[t][:, None])
    pidx = jnp.maximum(preds, 0)
    pfin = jnp.where(pv, s.finish[pidx], _NEG)      # [K, MP]
    pkb = jnp.where(pv, wl.out_kb[pidx], 0.0)
    pcl = p.pe_cluster[jnp.maximum(s.pe_of[pidx], 0)]          # [K, MP]
    if kmode != "off":
        return _kops.push_rows(pfin, pkb * p.us_per_kb, pcl, pv,
                               p.pe_cluster, bases,
                               p.cluster_pe_mask.shape[0], mode=kmode)
    cross = pcl[:, :, None] != p.pe_cluster[None, None, :]     # [K, MP, P]
    contrib = jnp.where(
        pv[:, :, None],
        pfin[:, :, None] + pkb[:, :, None] * p.us_per_kb * cross,
        _NEG,
    )                                               # [K, MP, P]
    return jnp.maximum(contrib.max(axis=1), bases[:, None])    # [K, P]


def _etf_choice(p: SimParams, wl: FlatWorkload, s: SimState,
                kmode: str = "off"):
    """Earliest-finish-time (task, pe) over the ready buffer (Algorithm 1).

    Pure lookup over the cached `ready_avail` / `ready_exec` rows. With
    `kmode != "off"` the masked finish-time search routes through the
    decision kernel (same first-global-minimum tie-break).
    """
    slot_ok = s.ready_ids >= 0                      # [R]
    if kmode != "off":
        slot, pe, _ = _kops.etf_decide(s.ready_avail, s.pe_free,
                                       s.ready_exec, s.now, slot_ok, None,
                                       mode=kmode)
        return slot, pe
    ft = jnp.maximum(jnp.maximum(s.ready_avail, s.pe_free[None, :]),
                     s.now) + s.ready_exec
    ft = jnp.where(slot_ok[:, None], ft, _INF)
    flat = jnp.argmin(ft)
    slot = flat // ft.shape[1]
    pe = flat % ft.shape[1]
    return slot.astype(jnp.int32), pe.astype(jnp.int32)


def _lut_choice(p: SimParams, wl: FlatWorkload, s: SimState):
    """Fast scheduler: FIFO head -> most-energy-efficient cluster -> its
    earliest-free PE."""
    slot = jnp.int32(0)
    t = jnp.maximum(s.ready_ids[0], 0)
    cl = p.lut_cluster[wl.task_type[t]]
    free = jnp.where(p.cluster_pe_mask[cl], s.pe_free, _INF)
    pe = jnp.argmin(free).astype(jnp.int32)
    return slot, pe


def _lut_choice_degraded(p: SimParams, wl: FlatWorkload, s: SimState):
    """Fault-aware fast scheduler: (slot, pe, feasible).

    Re-ranks clusters by `cluster_energy` restricted to clusters with at
    least one live PE, so a dead accelerator degrades to the next-best
    healthy cluster (ultimately the CPU clusters, which run every type).
    With every PE alive this reduces exactly to `_lut_choice`: the argmin
    over the full energy row *is* the precomputed `lut_cluster` entry
    (same table, same first-minimum tie-break).
    """
    slot = jnp.int32(0)
    t = jnp.maximum(s.ready_ids[0], 0)
    tt = wl.task_type[t]
    cl_alive = (p.cluster_pe_mask & s.pe_alive[None, :]).any(axis=1)  # [C]
    e = jnp.where(cl_alive, p.cluster_energy[tt], _INF)               # [C]
    cl = jnp.argmin(e).astype(jnp.int32)
    ok = (s.ready_ids[0] >= 0) & jnp.isfinite(e[cl])
    free = jnp.where(p.cluster_pe_mask[cl] & s.pe_alive, s.pe_free, _INF)
    pe = jnp.argmin(free).astype(jnp.int32)
    return slot, pe, ok


def _etf_choice_degraded(p: SimParams, wl: FlatWorkload, s: SimState,
                         kmode: str = "off"):
    """Fault-aware ETF: (slot, pe, feasible) with dead PEs masked out of
    the earliest-finish-time search. All-alive == `_etf_choice` exactly."""
    slot_ok = s.ready_ids >= 0                      # [R]
    if kmode != "off":
        return _kops.etf_decide(s.ready_avail, s.pe_free, s.ready_exec,
                                s.now, slot_ok, s.pe_alive, mode=kmode)
    ft = jnp.maximum(jnp.maximum(s.ready_avail, s.pe_free[None, :]),
                     s.now) + s.ready_exec
    ft = jnp.where(slot_ok[:, None] & s.pe_alive[None, :], ft, _INF)
    flat = jnp.argmin(ft)
    slot = flat // ft.shape[1]
    pe = flat % ft.shape[1]
    ok = jnp.isfinite(ft.reshape(-1)[flat])
    return slot.astype(jnp.int32), pe.astype(jnp.int32), ok


def _can_schedule(mode: int, p: SimParams, wl: FlatWorkload, s: SimState,
                  tree: DTree, rate_threshold: jax.Array,
                  kmode: str = "off") -> jax.Array:
    """Whether the scheduler the mode would invoke has a feasible
    (task, PE) pair under the current availability mask (fault path only).

    The fast path considers only the FIFO head, so a head whose every
    capable cluster is dead blocks the queue until a repair or its job's
    deadline drop — head-of-line blocking is part of the degradation
    model. ETF infeasible implies no ready task can run anywhere healthy.
    """
    if mode in (MODE_LUT, MODE_ORACLE):
        return _lut_choice_degraded(p, wl, s)[2]
    if mode in (MODE_ETF, MODE_ETF_IDEAL):
        return _etf_choice_degraded(p, wl, s, kmode)[2]
    # DAS / THRESHOLD: feasibility of the scheduler the policy will pick
    feats = _features(p, wl, s)
    if mode == MODE_DAS:
        use_slow = tree.predict(feats).astype(bool)
    else:
        use_slow = feats[FEAT_RATE] >= rate_threshold
    ok_f = _lut_choice_degraded(p, wl, s)[2]
    ok_s = _etf_choice_degraded(p, wl, s, kmode)[2]
    return jnp.where(use_slow, ok_s, ok_f)


# ---------------------------------------------------------------------------
# state mutations
#
# Each mutation takes an optional `active` gate. `active=None` means
# statically active (the `lax.switch` body, where the branch only runs when
# chosen). A traced `active` gates every update with `where`, which is how
# the batched (`masked=True`) body keeps one-event-per-iteration semantics
# without `lax.switch` — a vmapped switch executes all branches anyway and
# then pays a select over the whole carry (including the [T, F] logs) per
# branch per iteration, which dominated the sweep cost.
# ---------------------------------------------------------------------------
def _gate(active, new, old):
    return new if active is None else jnp.where(active, new, old)


def _gate_i(active) -> jax.Array:
    return jnp.int32(1) if active is None else active.astype(jnp.int32)


def _gset(active, arr, idx, val):
    """Gated row write: `arr[idx] = val` only when `active`.

    Inactive writes are redirected to an out-of-bounds row that
    `mode="drop"` discards, so the whole thing stays a one-row scatter
    XLA can apply in place on the loop carry. The alternative,
    `jnp.where(active, arr.at[idx].set(val), arr)`, materializes a
    full-array select per call — ruinous for the [T, F] decision log
    inside the batched while loop.
    """
    if active is None:
        return arr.at[idx].set(val)
    oob = jnp.where(active, idx, arr.shape[0])
    return arr.at[oob].set(val, mode="drop")


def _gadd(active, arr, idx, val):
    """Gated `arr[idx] += val` (same out-of-bounds trick as `_gset`)."""
    if active is None:
        return arr.at[idx].add(val)
    oob = jnp.where(active, idx, arr.shape[0])
    return arr.at[oob].add(val, mode="drop")


def _gmin(active, arr, idx, val):
    """Gated `arr[idx] = min(arr[idx], val)` (same trick as `_gset`)."""
    if active is None:
        return arr.at[idx].min(val)
    oob = jnp.where(active, idx, arr.shape[0])
    return arr.at[oob].min(val, mode="drop")


def _next_completion(s: SimState):
    """(task, finish) of the earliest-finishing running task.

    Two-level search over the `fin_seg` invariant; the returned index is
    exactly `argmin(fin_run)` (first global minimum: the first segment
    holding the min value wins, then the first index inside it).
    """
    seg = jnp.argmin(s.fin_seg)
    blk = jax.lax.dynamic_slice(s.fin_run, (seg * SEG,), (SEG,))
    t = (seg * SEG + jnp.argmin(blk)).astype(jnp.int32)
    return t, s.fin_seg[seg]


def _push_ready_many(p: SimParams, wl: FlatWorkload, s: SimState,
                     tasks: jax.Array, bases: jax.Array,
                     do_push: jax.Array, rows_avail=None,
                     plan=None, kmode: str = "off") -> SimState:
    """FIFO-push up to K tasks (k ascending), caching their [P] rows.

    Replicates K sequential single-task pushes exactly. Slot assignment:
    with `b_k = ready_cnt + sum_{j<k} do_push_j`, push k lands iff
    `do_push_k & (b_k < R_MAX)` — before the queue saturates every
    accepted push *is* a do_push, so the do_push cumsum equals the
    accepted cumsum, and after saturation both reject everything.
    `rows_avail` lets a caller that knows the availability rows in closed
    form (arrival roots) skip the `_avail_rows` tensor.
    """
    t = jnp.maximum(tasks, 0)                             # [K]
    if rows_avail is None:
        rows_avail = _avail_rows(p, wl, s, t, bases, kmode)   # [K, P]
    rows_exec = p.exec_pe[wl.task_type[t]]                # [K, P]
    if plan is not None:
        # cluster slowdown stretches the cached exec rows at push time
        # (pe_slow is constant per scenario, so the cache stays valid;
        # x1.0 when healthy keeps the healthy plan bit-exact)
        rows_exec = rows_exec * s.pe_slow[None, :]
    want = do_push.astype(jnp.int32)
    before = s.ready_cnt + jnp.cumsum(want) - want        # [K] exclusive
    can = do_push & (before < R_MAX)
    acc = can.astype(jnp.int32)
    slots = s.ready_cnt + jnp.cumsum(acc) - acc           # [K]
    sl = jnp.where(can, slots, R_MAX)                     # drop rejected
    tix = jnp.where(do_push, t, s.status.shape[0])
    return s._replace(
        ready_ids=s.ready_ids.at[sl].set(t, mode="drop"),
        ready_avail=s.ready_avail.at[sl].set(rows_avail, mode="drop"),
        ready_exec=s.ready_exec.at[sl].set(rows_exec, mode="drop"),
        ready_cnt=s.ready_cnt + acc.sum(),
        ready_drop=s.ready_drop + (want - acc).sum(),
        status=s.status.at[tix].set(2, mode="drop"),
    )


def _pop_slot(s: SimState, slot: jax.Array, active=None) -> SimState:
    """Remove `slot` keeping FIFO order (left shift of the tail)."""
    ar = jnp.arange(R_MAX)
    tail = ar >= slot
    shifted = jnp.roll(s.ready_ids, -1)
    ready_ids = jnp.where(tail, shifted, s.ready_ids)
    ready_ids = ready_ids.at[R_MAX - 1].set(
        jnp.where(slot < R_MAX, -1, ready_ids[R_MAX - 1])
    )

    # cached rows shift with the ids; the duplicated last row is stale but
    # its ready_id is -1, so it is masked everywhere
    def shift_rows(a):
        return jnp.where(tail[:, None], jnp.roll(a, -1, axis=0), a)

    return s._replace(
        ready_ids=_gate(active, ready_ids, s.ready_ids),
        ready_avail=_gate(active, shift_rows(s.ready_avail), s.ready_avail),
        ready_exec=_gate(active, shift_rows(s.ready_exec), s.ready_exec),
        ready_cnt=s.ready_cnt - _gate_i(active))


def _assign(p: SimParams, wl: FlatWorkload, s: SimState, slot: jax.Array,
            pe: jax.Array, lat: jax.Array, sched_e: jax.Array,
            is_slow: jax.Array, feats: jax.Array,
            agree: jax.Array, active=None, plan=None) -> SimState:
    task = jnp.maximum(s.ready_ids[slot], 0)
    sched_done = jnp.maximum(s.sched_free, s.now) + lat
    avail = s.ready_avail[slot, pe]
    start = jnp.maximum(jnp.maximum(avail, s.pe_free[pe]),
                        jnp.maximum(sched_done, s.now))
    exec_t = s.ready_exec[slot, pe]
    finish = start + exec_t
    e_task = exec_t * p.pe_power[pe]
    act = _gate_i(active)
    d = s.d_ptr
    # accumulators: gate the summed result, not the addend — selecting the
    # addend to 0.0 blocks the mul+add FMA contraction the unmasked path
    # gets, and the two paths then drift by a ULP per decision
    s = s._replace(
        sched_free=_gate(active, sched_done, s.sched_free),
        status=_gset(active, s.status, task, 3),
        start=_gset(active, s.start, task, start),
        finish=_gset(active, s.finish, task, finish),
        fin_run=_gset(active, s.fin_run, task, finish),
        fin_seg=_gmin(active, s.fin_seg, task // SEG, finish),
        n_running=s.n_running + act,
        pe_of=_gset(active, s.pe_of, task, pe),
        pe_free=_gset(active, s.pe_free, pe, finish),
        pe_busy=_gadd(active, s.pe_busy, pe, exec_t),
        task_energy=_gate(active, s.task_energy + e_task, s.task_energy),
        sched_energy=_gate(active, s.sched_energy + sched_e, s.sched_energy),
        sched_time=_gate(active, s.sched_time + lat, s.sched_time),
        n_fast=s.n_fast + (1 - is_slow) * act,
        n_slow=s.n_slow + is_slow * act,
        n_sched=s.n_sched + act,
        d_ptr=d + act,
        log_feat=_gset(active, s.log_feat, d, feats),
        log_policy=_gset(active, s.log_policy, d, is_slow.astype(jnp.int8)),
        log_agree=_gset(active, s.log_agree, d, agree.astype(jnp.int8)),
        log_task=_gset(active, s.log_task, d, task),
    )
    if plan is not None:
        # a fault at tau revokes live assignments with assign_t < tau, so
        # a decision taken *at* a fault instant is never insta-killed
        s = s._replace(assign_t=_gset(active, s.assign_t, task, s.now))
    return _pop_slot(s, slot, active=active)


def _process_completion(p: SimParams, wl: FlatWorkload,
                        s: SimState, active=None, t=None,
                        plan=None, kmode: str = "off") -> SimState:
    if t is None:
        # earliest-finishing running task; when a completion is due, every
        # task at the minimum of `fin_run` has finish <= now, so this is
        # exactly argmin(where(status==3 & finish<=now, finish, inf))
        t, _ = _next_completion(s)
    act = _gate_i(active)
    s = s._replace(status=_gset(active, s.status, t, 4),
                   fin_run=_gset(active, s.fin_run, t, _INF),
                   n_running=s.n_running - act,
                   n_done=s.n_done + act)
    if plan is not None:
        tt = jnp.maximum(t, 0)
        rec = s.retries[tt] > 0
        if active is not None:
            rec &= active
        s = s._replace(
            inst_rem=_gadd(active, s.inst_rem, wl.inst_id[tt], -1),
            # a previously-killed task finishing anyway: recovery latency
            # is measured from its last kill to its final finish
            recovery_us=_gate(rec, s.recovery_us
                              + (s.finish[tt] - s.kill_t[tt]),
                              s.recovery_us),
            n_recovered=s.n_recovered + jnp.asarray(rec).astype(jnp.int32),
        )
    # restore the fin_seg invariant: rescan only the SEG-sized block of
    # the retired task (reads the post-scatter fin_run)
    seg = t // SEG
    blk = jax.lax.dynamic_slice(s.fin_run, (seg * SEG,), (SEG,))
    s = s._replace(fin_seg=_gset(active, s.fin_seg, seg, blk.min()))

    # all successors at once: they are distinct tasks, so the pred_rem
    # update and the pushes vectorize with no read-after-write hazard
    succ = wl.succs[t]                                    # [MS]
    valid = (jnp.arange(succ.shape[0]) < wl.n_succs[t]) & (succ >= 0)
    if active is not None:
        valid &= active
    sc = jnp.maximum(succ, 0)
    new_rem = s.pred_rem[sc] - 1
    scx = jnp.where(valid, sc, s.pred_rem.shape[0])
    s = s._replace(pred_rem=s.pred_rem.at[scx].set(new_rem, mode="drop"))
    ready_now = valid & (new_rem == 0)
    # availability (base) = max pred finish (all preds are done)
    pr = wl.preds[sc]                                     # [MS, MP]
    pv = jnp.arange(pr.shape[1])[None, :] < wl.n_preds[sc][:, None]
    bases = jnp.where(pv, s.finish[jnp.maximum(pr, 0)], _NEG).max(axis=1)
    return _push_ready_many(p, wl, s, sc, jnp.maximum(bases, s.now),
                            ready_now, plan=plan, kmode=kmode)


def _process_arrival(p: SimParams, wl: FlatWorkload, s: SimState,
                     active=None, plan=None) -> SimState:
    i = s.arr_ptr
    ic = jnp.minimum(i, wl.inst_arrival.shape[0] - 1)
    t_arr = wl.inst_arrival[ic]
    act = _gate_i(active)
    s = s._replace(
        arr_ptr=i + act,
        ring=_gset(active, s.ring, s.ring_ptr % RING, t_arr),
        ring_ptr=s.ring_ptr + act,
        arr_count=s.arr_count + act,
    )
    roots = wl.inst_roots[ic]                             # [MR]
    valid = (jnp.arange(roots.shape[0]) < wl.inst_n_roots[ic]) & (roots >= 0)
    if active is not None:
        valid &= active
    bases = jnp.full(roots.shape[0], t_arr)
    # roots have zero preds by construction, so their availability row is
    # exactly the arrival time on every PE (`_avail_rows` would reduce an
    # all -inf contrib tensor against `bases`)
    rows = jnp.broadcast_to(bases[:, None],
                            (roots.shape[0], s.pe_free.shape[0]))
    return _push_ready_many(p, wl, s, jnp.maximum(roots, 0), bases, valid,
                            rows_avail=rows, plan=plan)


# ---------------------------------------------------------------------------
# fault events (kill / deadline / drop) — only traced when a FaultPlan is
# threaded; `plan=None` callers never reach these.
# ---------------------------------------------------------------------------
def _pending_kill(plan, s: SimState):
    """(due, task, tau): earliest fault instant that revokes a live
    assignment — a running task whose PE has a permanent failure or
    transient glitch at tau with `assign_t < tau <= now`. Ties break to
    the lowest task id (argmin), matching `ref_sim`."""
    taus = flt.kill_times(plan)                         # [P, K]
    # each task's row of `taus`, selected from the P rows by a one-hot:
    # under `vmap` a gather `taus[pe_of]` walks lanes x tasks serially
    on_pe = (jnp.arange(taus.shape[0])[:, None]
             == jnp.maximum(s.pe_of, 0)[None, :])      # [P, T]
    t_taus = jnp.where(on_pe[None], taus.T[:, :, None],
                       _NEG).max(axis=1)                # [K, T]
    running = s.status == 3
    due = (running[None, :] & (s.assign_t[None, :] < t_taus)
           & (t_taus <= s.now))                         # [K, T]
    tau_t = jnp.where(due, t_taus, _INF).min(axis=0)    # [T]
    t = jnp.argmin(tau_t).astype(jnp.int32)
    return due.any(), t, tau_t[t]


def _drop_instance(p: SimParams, wl: FlatWorkload, s: SimState,
                   inst: jax.Array, active=None) -> SimState:
    """Cancel every unfinished task of instance `inst` (deadline miss or
    retry exhaustion). Running work rolls back its unexecuted tail
    (busy time + energy), queued tasks are purged from the FIFO with
    order preserved, and every victim retires as status 5 so the
    termination count (`n_done`) still converges.

    Every task-length write is a dense masked op over the whole row: a
    `where` on the victim mask for the per-task fields, and a [P, T]
    PE-by-task one-hot reduced over tasks for the per-PE fields. Under
    `vmap` a gated scatter would be one serial scatter over lanes x tasks
    updates on every trip, whether or not a lane drops. `pe_busy` takes
    the running victims' tails off one a PE a pass, in task order, so it
    rounds as subtracting them one by one does."""
    T = s.status.shape[0]
    P = s.pe_free.shape[0]
    inst = jnp.maximum(inst, 0)
    victim = (wl.inst_id == inst) & wl.task_valid & (s.status < 4)
    if active is not None:
        victim &= active
    n_v = victim.sum().astype(jnp.int32)

    # roll back the unexecuted tail of running victims; keep the executed
    # prefix (that energy really was burned)
    runn = victim & (s.status == 3)
    pe = jnp.maximum(s.pe_of, 0)
    exec_total = jnp.where(runn, s.finish - s.start, 0.0)
    executed = jnp.where(runn, jnp.clip(s.now - s.start, 0.0, exec_total),
                         0.0)
    unexec = exec_total - executed
    on_pe = jnp.arange(P)[:, None] == pe[None, :]           # [P, T]
    lost = on_pe & runn[None, :]

    # a sum of several tails on one PE could round differently
    def roll_back(c):
        left, busy = c
        rows = on_pe & left[None, :]
        first = rows & (jnp.arange(T)[None, :]
                        == jnp.argmax(rows, axis=1)[:, None])
        return (left & ~first.any(axis=0),
                busy - jnp.where(first, unexec[None, :], 0.0).sum(axis=1))

    _, pe_busy = jax.lax.while_loop(lambda c: c[0].any(), roll_back,
                                    (runn, s.pe_busy))
    e_back = (jnp.where(runn, unexec * p.pe_power[pe], 0.0)).sum()
    # PEs that lost a victim rebuild pe_free from surviving assignments;
    # untouched PEs keep their exact value
    surv = (s.status == 3) & ~victim
    surv_fin = jnp.where(on_pe & surv[None, :], s.finish[None, :],
                         _NEG).max(axis=1)
    pe_free = jnp.where(lost.any(axis=1), jnp.maximum(surv_fin, s.now),
                        s.pe_free)

    status = jnp.where(victim, jnp.int8(5), s.status)
    # -inf keeps dropped tasks out of the makespan / inst_fin maxima
    finish = jnp.where(victim, _NEG, s.finish)
    fin_run = jnp.where(jnp.pad(runn, (0, s.fin_run.shape[0] - T)), _INF,
                        s.fin_run)
    # victims may span many segments: full fin_seg rebuild (exactly the
    # invariant value, so a no-op drop stays bit-identical)
    fin_seg = fin_run.reshape(-1, SEG).min(axis=1)

    # purge victims from the ready FIFO, preserving survivor order
    in_q = s.ready_ids >= 0
    is_v = jnp.where(in_q, victim[jnp.maximum(s.ready_ids, 0)], False)
    keep = in_q & ~is_v
    perm = jnp.argsort((~keep).astype(jnp.int32))  # stable: survivors first
    new_cnt = keep.sum().astype(jnp.int32)
    ids_p = jnp.where(jnp.arange(R_MAX) < new_cnt, s.ready_ids[perm], -1)

    return s._replace(
        status=status, finish=finish, fin_run=fin_run, fin_seg=fin_seg,
        start=jnp.where(victim, _INF, s.start),
        assign_t=jnp.where(victim, _INF, s.assign_t),
        pe_busy=pe_busy, pe_free=pe_free,
        task_energy=_gate(active, s.task_energy - e_back, s.task_energy),
        n_running=s.n_running - runn.sum().astype(jnp.int32),
        n_done=s.n_done + n_v,
        n_dropped_tasks=s.n_dropped_tasks + n_v,
        ready_ids=_gate(active, ids_p, s.ready_ids),
        ready_avail=_gate(active, s.ready_avail[perm], s.ready_avail),
        ready_exec=_gate(active, s.ready_exec[perm], s.ready_exec),
        ready_cnt=_gate(active, new_cnt, s.ready_cnt),
        inst_rem=_gset(active, s.inst_rem, inst, 0),
        job_dropped=_gset(active, s.job_dropped, inst, True),
    )


def _process_kill(plan, p: SimParams, wl: FlatWorkload, s: SimState,
                  t: jax.Array, active=None, kmode: str = "off") -> SimState:
    """Revoke the live assignment of running task `t` at the current time
    (`now` sits exactly on the fault instant: advance stops at every plan
    time). Executed work is wasted (`reexec_us`) but its energy/busy time
    stay; the unexecuted tail rolls back. Within the retry budget the task
    re-enters the FIFO tail at `now`; past it its whole job drops."""
    T = s.status.shape[0]
    t = jnp.maximum(t, 0)
    pe = jnp.maximum(s.pe_of[t], 0)
    exec_total = s.finish[t] - s.start[t]
    executed = jnp.clip(s.now - s.start[t], 0.0, exec_total)
    unexec = exec_total - executed
    act = _gate_i(active)
    exhausted = s.retries[t] >= plan.max_retries
    if active is None:
        rk = ~exhausted
        dr = exhausted
    else:
        rk = active & ~exhausted
        dr = active & exhausted

    others = (s.status == 3) & (s.pe_of == pe) & (jnp.arange(T) != t)
    new_free = jnp.maximum(jnp.where(others, s.finish, _NEG).max(), s.now)

    s = s._replace(
        status=_gset(active, s.status, t, 0),
        start=_gset(active, s.start, t, _INF),
        finish=_gset(active, s.finish, t, _INF),
        fin_run=_gset(active, s.fin_run, t, _INF),
        n_running=s.n_running - act,
        pe_of=_gset(active, s.pe_of, t, -1),
        assign_t=_gset(active, s.assign_t, t, _INF),
        pe_free=_gset(active, s.pe_free, pe, new_free),
        pe_busy=_gadd(active, s.pe_busy, pe, -unexec),
        task_energy=_gate(active, s.task_energy - unexec * p.pe_power[pe],
                          s.task_energy),
        retries=_gadd(active, s.retries, t, 1),
        kill_t=_gset(active, s.kill_t, t, s.now),
        n_kills=s.n_kills + act,
        n_retries=s.n_retries + jnp.asarray(rk).astype(jnp.int32),
        reexec_us=_gate(active, s.reexec_us + executed, s.reexec_us),
    )
    # restore the fin_seg invariant for the killed task's segment
    seg = t // SEG
    blk = jax.lax.dynamic_slice(s.fin_run, (seg * SEG,), (SEG,))
    s = s._replace(fin_seg=_gset(active, s.fin_seg, seg, blk.min()))

    # retry: back to the FIFO tail, availability re-based at now (preds
    # are all done, so the cached row is recomputable)
    s = _push_ready_many(p, wl, s, t[None], s.now[None],
                         jnp.asarray(rk)[None], plan=plan, kmode=kmode)
    # exhausted: the whole job goes
    return _drop_instance(p, wl, s, wl.inst_id[t], active=jnp.asarray(dr))


def _pending_deadline(plan, wl: FlatWorkload, s: SimState):
    """(due, inst): earliest arrived-but-incomplete instance past its
    deadline. Ties break to the lowest instance id."""
    I = wl.inst_arrival.shape[0]
    arrived = jnp.arange(I) < s.arr_ptr
    pend = arrived & wl.inst_valid & (s.inst_rem > 0)
    dl = jnp.where(pend, wl.inst_arrival + plan.deadline_us, _INF)
    due = pend & (dl <= s.now)
    inst = jnp.argmin(jnp.where(due, dl, _INF)).astype(jnp.int32)
    return due.any(), inst


def _next_wakeup(plan, wl: FlatWorkload, s: SimState,
                 fcaps=flt.FULL_CAPS) -> jax.Array:
    """Earliest strictly-future fault instant, repair, or pending job
    deadline — extra advance targets so `now` lands exactly on each fault
    event (a stop with nothing due simply advances again). Targets a
    capability rules out are statically dropped: a time that can never be
    strictly future (or never matters) contributes `inf` to the min, so
    skipping it is exact."""
    can_die, can_kill, has_deadline = fcaps
    parts = []
    if can_die:
        parts += [plan.pe_fail_at, plan.pe_repair_at]
    if can_kill:
        parts.append(plan.transient_at.reshape(-1))
    out = _INF
    if parts:
        times = jnp.concatenate(parts)
        out = jnp.where(times > s.now, times, _INF).min()
    if has_deadline:
        I = wl.inst_arrival.shape[0]
        arrived = jnp.arange(I) < s.arr_ptr
        pend = arrived & wl.inst_valid & (s.inst_rem > 0)
        dl = jnp.where(pend, wl.inst_arrival + plan.deadline_us, _INF)
        out = jnp.minimum(out, jnp.where(dl > s.now, dl, _INF).min())
    return jnp.asarray(out, jnp.float32)


# ---------------------------------------------------------------------------
# the main loop
# ---------------------------------------------------------------------------
def _init_state(wl: FlatWorkload, n_pes: int, pe_slow=None) -> SimState:
    T = wl.task_type.shape[0]
    I = wl.inst_arrival.shape[0]
    Tp = -(-T // SEG) * SEG       # fin_run padded so every segment is full
    inst_cnt = jnp.zeros(I, jnp.int32).at[
        jnp.where(wl.task_valid, wl.inst_id, I)
    ].add(1, mode="drop")
    return SimState(
        now=jnp.float32(0.0), stalled=jnp.array(False),
        sched_free=jnp.float32(0.0),
        arr_ptr=jnp.int32(0), n_done=jnp.int32(0), n_sched=jnp.int32(0),
        status=jnp.zeros(T, jnp.int8),
        pred_rem=wl.n_preds.astype(jnp.int32),
        start=jnp.full(T, _INF), finish=jnp.full(T, _INF),
        fin_run=jnp.full(Tp, _INF),
        fin_seg=jnp.full(Tp // SEG, _INF), n_running=jnp.int32(0),
        pe_of=jnp.full(T, -1, jnp.int32),
        pe_free=jnp.zeros(n_pes, jnp.float32),
        pe_busy=jnp.zeros(n_pes, jnp.float32),
        ready_ids=jnp.full(R_MAX, -1, jnp.int32),
        ready_cnt=jnp.int32(0), ready_drop=jnp.int32(0),
        ready_avail=jnp.zeros((R_MAX, n_pes), jnp.float32),
        ready_exec=jnp.zeros((R_MAX, n_pes), jnp.float32),
        task_energy=jnp.float32(0.0), sched_energy=jnp.float32(0.0),
        sched_time=jnp.float32(0.0),
        n_fast=jnp.int32(0), n_slow=jnp.int32(0),
        ring=jnp.zeros(RING, jnp.float32), ring_ptr=jnp.int32(0),
        arr_count=jnp.int32(0),
        d_ptr=jnp.int32(0),
        log_feat=jnp.zeros((T, N_FEATURES), jnp.float32),
        log_policy=jnp.zeros(T, jnp.int8),
        log_agree=jnp.zeros(T, jnp.int8),
        log_task=jnp.full(T, -1, jnp.int32),
        pe_alive=jnp.ones(n_pes, bool),
        pe_slow=(jnp.ones(n_pes, jnp.float32) if pe_slow is None
                 else jnp.asarray(pe_slow, jnp.float32)),
        assign_t=jnp.full(T, _INF),
        retries=jnp.zeros(T, jnp.int32),
        kill_t=jnp.zeros(T, jnp.float32),
        inst_rem=inst_cnt,
        job_dropped=jnp.zeros(I, bool),
        n_kills=jnp.int32(0), n_retries=jnp.int32(0),
        reexec_us=jnp.float32(0.0), n_dropped_tasks=jnp.int32(0),
        recovery_us=jnp.float32(0.0), n_recovered=jnp.int32(0),
        fired=jnp.zeros(len(PHASES), jnp.int32),
    )


def _decide(mode: int, p: SimParams, wl: FlatWorkload, s: SimState,
            tree: DTree, rate_threshold: jax.Array,
            active=None, plan=None, kmode: str = "off") -> SimState:
    feats = _features(p, wl, s)
    n = s.ready_cnt.astype(jnp.float32)
    etf_lat = soc.etf_latency_us(n)
    etf_e = etf_lat * soc.SCHED_POWER_W

    def lut():
        if plan is None:
            return _lut_choice(p, wl, s)
        return _lut_choice_degraded(p, wl, s)[:2]

    def etf():
        if plan is None:
            return _etf_choice(p, wl, s, kmode)
        return _etf_choice_degraded(p, wl, s, kmode)[:2]

    if mode == MODE_LUT:
        slot, pe = lut()
        return _assign(p, wl, s, slot, pe, jnp.float32(soc.LUT_LATENCY_US),
                       jnp.float32(soc.LUT_ENERGY_UJ), jnp.int32(0), feats,
                       jnp.int32(0), active=active, plan=plan)
    if mode == MODE_ETF:
        slot, pe = etf()
        return _assign(p, wl, s, slot, pe, etf_lat, etf_e, jnp.int32(1),
                       feats, jnp.int32(0), active=active, plan=plan)
    if mode == MODE_ETF_IDEAL:
        slot, pe = etf()
        return _assign(p, wl, s, slot, pe, jnp.float32(0.0), jnp.float32(0.0),
                       jnp.int32(1), feats, jnp.int32(0), active=active,
                       plan=plan)
    if mode == MODE_ORACLE:
        # run both, follow the fast one, log whether they agree
        slot_f, pe_f = lut()
        slot_s, pe_s = etf()
        agree = ((s.ready_ids[slot_f] == s.ready_ids[slot_s])
                 & (pe_f == pe_s)).astype(jnp.int32)
        return _assign(p, wl, s, slot_f, pe_f,
                       jnp.float32(soc.LUT_LATENCY_US),
                       jnp.float32(soc.LUT_ENERGY_UJ), jnp.int32(0), feats,
                       agree, active=active, plan=plan)

    if mode == MODE_DAS:
        use_slow = tree.predict(feats).astype(bool)
        cls_e = jnp.float32(soc.DAS_CLS_ENERGY_UJ)
    elif mode == MODE_THRESHOLD:
        use_slow = feats[FEAT_RATE] >= rate_threshold
        cls_e = jnp.float32(0.0)
    else:  # pragma: no cover
        raise ValueError(f"unknown mode {mode}")

    slot_f, pe_f = lut()
    slot_s, pe_s = etf()
    slot = jnp.where(use_slow, slot_s, slot_f)
    pe = jnp.where(use_slow, pe_s, pe_f)
    lat = jnp.where(use_slow, etf_lat, jnp.float32(soc.LUT_LATENCY_US))
    e = jnp.where(use_slow, etf_e, jnp.float32(soc.LUT_ENERGY_UJ)) + cls_e
    return _assign(p, wl, s, slot, pe, lat, e, use_slow.astype(jnp.int32),
                   feats, jnp.int32(0), active=active, plan=plan)


def _masked_step(mode: int, params: SimParams, s: SimState,
                 wl: FlatWorkload, tree: DTree, rate_threshold: jax.Array,
                 plan, run: jax.Array, kmode: str = "off",
                 fcaps=flt.FULL_CAPS):
    """One super-step of gated phases (no `lax.switch`); returns (s, ev).

    Phases run in the sequential body's priority order (completion >
    arrival > decide > advance), but gates are *re-derived after each
    phase*, so one iteration retires several consecutive events whenever
    they would have fired back-to-back anyway — e.g. the last completion
    at a timestamp, then the arrival due at that timestamp, then the first
    scheduling decision. The retired event *sequence* is exactly the
    switch path's, hence every result field stays bit-identical; only the
    grouping into loop iterations changes, which `ev` (events retired this
    step, 0..4, or 6 with faults) accounts for so `n_iters` still equals
    the sequential count. `ev` is the sum of the phase flags, which the
    step also leaves in `s.fired` (one 0/1 per entry of `PHASES`) for the
    loop's phase counters. `run=False` makes the whole step a no-op, which
    is how the
    batched driver freezes finished lanes. Used under vmap: a vmapped
    switch would execute all branches anyway and then select the *entire*
    carry once per branch, which dominated the sweep cost. Each phase's
    ops sit under a `jax.named_scope` of its name (HLO metadata only).
    """
    I = wl.inst_arrival.shape[0]
    can_die, can_kill, has_deadline = fcaps if plan is not None \
        else flt.NO_CAPS
    with jax.named_scope("completion"):
        if plan is not None and can_die:
            s = s._replace(pe_alive=flt.alive_at(plan, s.now))
        # one two-level search serves completion detection, the completed
        # task index, AND the advance target (the switch path derives all
        # three from status/finish separately — same values, more passes)
        fin_idx, fin_val = _next_completion(s)
        c = run & (fin_val <= s.now)
        s = _process_completion(params, wl, s, active=c, t=fin_idx,
                                plan=plan, kmode=kmode)

        # a completion tie leaves another completion due: everything
        # below must wait for the next iteration then, exactly as the
        # switch would
        next_fin = s.fin_seg.min()
        no_c = ~(next_fin <= s.now)

    # fault phases (priority: completion > kill > deadline > arrival).
    # Gates re-derive after each phase, mirroring the sequential 6-way
    # switch: a second due kill / deadline blocks everything later. A
    # phase the plan's static capabilities rule out (see
    # `faults.plan_capabilities`) is skipped at trace time — its `due`
    # predicate would be identically False, so the skip is exact. A
    # traced phase runs on every trip of every lane, fired or not, so the
    # kill/drop machinery (due-checks, FIFO purges, fin_seg rebuilds,
    # re-push) is written as dense masked passes over the task and PE
    # rows: under `vmap` a gated scatter or gather over a task row would
    # walk lanes x tasks serially on every trip.
    k = dl = jnp.array(False)
    no_k = no_dl = jnp.array(True)
    if plan is not None and can_kill:
        with jax.named_scope("kill"):
            k_due, k_task, _ = _pending_kill(plan, s)
            k = run & no_c & k_due
            s = _process_kill(plan, params, wl, s, k_task, active=k,
                              kmode=kmode)
            no_k = ~_pending_kill(plan, s)[0]
    if plan is not None and has_deadline:
        with jax.named_scope("deadline"):
            dl_due, dl_inst = _pending_deadline(plan, wl, s)
            dl = run & no_c & no_k & dl_due
            s = _drop_instance(params, wl, s, dl_inst, active=dl)
            no_dl = ~_pending_deadline(plan, wl, s)[0]

    def arr_due(st):
        return (st.arr_ptr < wl.n_insts) & (
            wl.inst_arrival[jnp.minimum(st.arr_ptr, I - 1)] <= st.now
        )

    with jax.named_scope("arrival"):
        a = run & no_c & no_k & no_dl & arr_due(s)
        s = _process_arrival(params, wl, s, active=a, plan=plan)

        # same-timestamp arrivals: the next one blocks the decide phase;
        # an arrival can also arm an already-expired deadline
        # (deadline_us ~ 0)
        no_a = ~arr_due(s)
        if plan is not None and has_deadline:
            no_dl = ~_pending_deadline(plan, wl, s)[0]

    with jax.named_scope("decide"):
        can_decide = s.ready_cnt > 0
        if plan is not None and can_die:
            can_decide &= _can_schedule(mode, params, wl, s, tree,
                                        rate_threshold, kmode)
        d = run & no_c & no_k & no_dl & no_a & can_decide
        s = _decide(mode, params, wl, s, tree, rate_threshold, active=d,
                    plan=plan, kmode=kmode)

    # advance when nothing else can fire *after* this trip's phases: a
    # decide leaves finish > now (exec times are positive), so no
    # completion becomes due mid-trip, but it can lower the next finish —
    # recompute the min. Queue emptiness is post-decide. After the final
    # completion the sequential cond exits without reaching do_advance,
    # hence the n_done guard.
    with jax.named_scope("advance"):
        if plan is None or not (can_kill or has_deadline):
            # only a decide touched fin_seg this trip (no kills/drops
            # traced)
            next_fin = jnp.where(d, s.fin_seg.min(), next_fin)
        else:
            # kills / drops also touched fin_seg — recompute
            # unconditionally
            next_fin = s.fin_seg.min()
        if plan is not None and can_die:
            blocked = ~((s.ready_cnt > 0) & _can_schedule(
                mode, params, wl, s, tree, rate_threshold, kmode))
        else:
            blocked = s.ready_cnt == 0
        adv = (run & no_c & no_k & no_dl & no_a & blocked
               & (s.n_done < wl.n_tasks))
        next_arr = jnp.where(
            s.arr_ptr < wl.n_insts,
            wl.inst_arrival[jnp.minimum(s.arr_ptr, I - 1)], _INF,
        )
        nxt = jnp.minimum(next_fin, next_arr)
        if plan is not None and (can_die or can_kill or has_deadline):
            nxt = jnp.minimum(nxt, _next_wakeup(plan, wl, s, fcaps))
        stuck = ~jnp.isfinite(nxt)
        nxt = jnp.where(stuck, s.now, nxt)
        s = s._replace(
            now=jnp.where(adv, jnp.maximum(nxt, s.now), s.now),
            stalled=s.stalled | (adv & stuck),
        )
    flags = [f.astype(jnp.int32) for f in (c, k, dl, a, d, adv)]
    # `ev` is summed here, not from `fired` in the loop: the TPU compiler
    # lays the loop out differently when the events come from the stacked
    # flags (PERF.md §6), so the flags only feed the counters
    ev = flags[0] + flags[1] + flags[2] + flags[3] + flags[4] + flags[5]
    return s._replace(fired=jnp.stack(flags)), ev


def _finalize(wl: FlatWorkload, s: SimState, iters: jax.Array,
              max_iters) -> SimResult:
    I = wl.inst_arrival.shape[0]
    # per-instance latency: segment-max of finish over each instance's tasks
    inst_fin = jnp.full(I, _NEG).at[wl.inst_id].max(
        jnp.where(wl.task_valid, s.finish, _NEG)
    )
    # dropped jobs are excluded from the latency mean (they have no
    # finish); without a FaultPlan `job_dropped` is all-False, so the mask
    # — and hence the mean — is unchanged bit-for-bit
    inst_exec = jnp.where(
        wl.inst_valid & ~s.job_dropped, inst_fin - wl.inst_arrival, jnp.nan
    )
    avg_exec = jnp.nanmean(inst_exec)
    makespan = jnp.where(wl.task_valid, s.finish, _NEG).max()
    total_e = s.task_energy + s.sched_energy
    return SimResult(
        avg_exec_us=avg_exec,
        makespan_us=makespan,
        total_energy_uj=total_e,
        task_energy_uj=s.task_energy,
        sched_energy_uj=s.sched_energy,
        sched_time_us=s.sched_time,
        edp=total_e * avg_exec,
        n_decisions=s.d_ptr,
        n_fast=s.n_fast,
        n_slow=s.n_slow,
        n_done=s.n_done,
        ready_drop=s.ready_drop,
        n_iters=iters,
        stalled=s.stalled,
        inst_exec_us=inst_exec,
        log_feat=s.log_feat,
        log_policy=s.log_policy,
        log_agree=s.log_agree,
        log_task=s.log_task,
        finish=s.finish,
        pe_of=s.pe_of,
        n_faults=s.n_kills,
        n_retries=s.n_retries,
        reexec_us=s.reexec_us,
        n_dropped_jobs=s.job_dropped.sum().astype(jnp.int32),
        n_dropped_tasks=s.n_dropped_tasks,
        recovery_us=s.recovery_us,
        n_recovered=s.n_recovered,
        job_dropped=s.job_dropped,
        # budget exhaustion: the loop stopped at its iteration cap (the
        # natural pathology backstop or an explicit `step_budget`) with
        # work remaining. `>=` because the batched engine's super-steps
        # retire several events per iteration and may overshoot the cap.
        stall_reason=jnp.where(
            s.stalled, jnp.int32(STALL_DEADLOCK),
            jnp.where((iters >= max_iters) & (s.n_done < wl.n_tasks),
                      jnp.int32(STALL_BUDGET), jnp.int32(STALL_NONE))),
    )


def _fault_iter_bound(base, T: int, I: int, n_pes: int, plan):
    """Iteration cap with fault headroom: every retry re-runs up to 4
    events for its task, each PE contributes at most its transient count
    plus fail/repair advance stops, and drops/deadlines retire at most one
    extra event per instance. Traced (depends on `plan.max_retries`)."""
    return (base + 4 * T * (plan.max_retries + 2)
            + n_pes * (flt.MAX_TRANSIENTS + 2) + 2 * I + 64)


def _simulate_impl(mode: int, params: SimParams, wl: FlatWorkload,
                   tree: DTree, rate_threshold: jax.Array,
                   plan=None, step_budget: int | None = None,
                   kernels: str = "off",
                   fcaps: tuple = flt.FULL_CAPS) -> SimResult:
    can_die, can_kill, has_deadline = fcaps if plan is not None \
        else flt.NO_CAPS
    T = wl.task_type.shape[0]
    I = wl.inst_arrival.shape[0]
    n_pes = params.pe_cluster.shape[0]
    max_iters = 3 * T + I + 64
    if plan is not None:
        max_iters = _fault_iter_bound(max_iters, T, I, n_pes, plan)
    if step_budget is not None:
        # device-side budget: a stuck chunk terminates on its own instead
        # of relying on a host watchdog; lanes that hit it report
        # STALL_BUDGET so the campaign layer can retry with a bigger cap
        max_iters = jnp.minimum(jnp.asarray(max_iters, jnp.int32),
                                jnp.int32(step_budget))

    def cond(carry):
        s, it = carry
        return (s.n_done < wl.n_tasks) & ~s.stalled & (it < max_iters)

    def body(carry):
        s, it = carry
        if plan is not None and can_die:
            s = s._replace(pe_alive=flt.alive_at(plan, s.now))
        completion_due = s.fin_seg.min() <= s.now
        arrival_due = (s.arr_ptr < wl.n_insts) & (
            wl.inst_arrival[jnp.minimum(s.arr_ptr, I - 1)] <= s.now
        )
        can_decide = s.ready_cnt > 0

        def do_completion(st):
            return _process_completion(params, wl, st, plan=plan,
                                       kmode=kernels)

        def do_arrival(st):
            return _process_arrival(params, wl, st, plan=plan)

        def do_decide(st):
            return _decide(mode, params, wl, st, tree, rate_threshold,
                           plan=plan, kmode=kernels)

        def do_advance(st):
            next_fin = st.fin_seg.min()
            next_arr = jnp.where(
                st.arr_ptr < wl.n_insts,
                wl.inst_arrival[jnp.minimum(st.arr_ptr, I - 1)], _INF,
            )
            nxt = jnp.minimum(next_fin, next_arr)
            if plan is not None and (can_die or can_kill or has_deadline):
                nxt = jnp.minimum(nxt, _next_wakeup(plan, wl, st, fcaps))
            # deadlock guard: nothing running and nothing left to arrive
            # means no event can ever become due again (unschedulable
            # tasks) — flag the stall so `cond` exits instead of spinning
            # here until `max_iters`.
            stuck = ~jnp.isfinite(nxt)
            nxt = jnp.where(stuck, st.now, nxt)
            return st._replace(now=jnp.maximum(nxt, st.now), stalled=stuck)

        if plan is None:
            branch = jnp.where(
                completion_due, 0,
                jnp.where(arrival_due, 1, jnp.where(can_decide, 2, 3)),
            )
            s = jax.lax.switch(
                branch, [do_completion, do_arrival, do_decide, do_advance],
                s,
            )
            return (s, it + 1)

        # fault path: six branches, priority completion > kill > deadline
        # > arrival > decide > advance; a decision additionally requires
        # the chosen scheduler to have a feasible (task, PE) pair.
        # Phases the plan's static capabilities rule out keep their
        # branch slot but with an identically-False gate and an identity
        # body — the per-iteration pending scans (and the heavy branch
        # bodies) are never traced, and the skip is exact because the
        # gate could never fire anyway (`faults.plan_capabilities`).
        if can_kill:
            k_due, k_task, _ = _pending_kill(plan, s)
        else:
            k_due, k_task = jnp.array(False), jnp.int32(0)
        if has_deadline:
            dl_due, dl_inst = _pending_deadline(plan, wl, s)
        else:
            dl_due, dl_inst = jnp.array(False), jnp.int32(0)
        if can_die:
            can_decide &= _can_schedule(mode, params, wl, s, tree,
                                        rate_threshold, kernels)

        def do_kill(st):
            if not can_kill:
                return st
            return _process_kill(plan, params, wl, st, k_task,
                                 kmode=kernels)

        def do_deadline(st):
            if not has_deadline:
                return st
            return _drop_instance(params, wl, st, dl_inst)

        branch = jnp.where(
            completion_due, 0,
            jnp.where(k_due, 1,
                      jnp.where(dl_due, 2,
                                jnp.where(arrival_due, 3,
                                          jnp.where(can_decide, 4, 5)))),
        )
        s = jax.lax.switch(
            branch,
            [do_completion, do_kill, do_deadline, do_arrival, do_decide,
             do_advance], s,
        )
        return (s, it + 1)

    pe_slow = None if plan is None \
        else flt.pe_slowdown(plan, params.pe_cluster)
    s0 = _init_state(wl, n_pes, pe_slow)
    s, iters = jax.lax.while_loop(cond, body, (s0, jnp.int32(0)))
    return _finalize(wl, s, iters, max_iters)


# `mode` is static (each mode compiles its own loop); everything else is
# traced. Returns a `SimResult` of scalars plus per-task/per-decision logs.
# The single-scenario path keeps the `lax.switch` body: unbatched, a switch
# runs only the taken branch, which beats the masked step's always-on phases.
# `plan=None` vs a `FaultPlan` changes the pytree structure, so each case
# compiles separately and the no-plan trace is untouched by the fault layer.
# `step_budget` is static: it reshapes the loop bound, not the data.
# `kernels` is the resolved `REPRO_SIM_KERNELS` dispatch mode (static: it
# picks which decision primitives get traced); callers resolve it from the
# env at call time so flipping the knob never hits a stale trace.
simulate = jax.jit(_simulate_impl, static_argnums=(0, 6, 7, 8))


# Trace counter for the batched engine, keyed for introspection: tests
# assert that a padded ragged sweep reuses ONE compiled executable instead
# of retracing for the short final chunk (the Python body below only runs
# when jit actually traces).
TRACE_COUNT = {"simulate_batch": 0}


class BatchTelemetry(NamedTuple):
    """Per-lane occupancy counters for one batched-engine call.

    Deliberately NOT part of `SimResult`: these depend on which scenarios
    share a chunk (the scalar-cond loop spins every lane until the whole
    chunk retires), so folding them into the result would break the
    bit-exactness contract between differently-chunked sweeps.
    """
    loop_trips: jax.Array    # [S] while-loop trips of the lane's shard
    active_trips: jax.Array  # [S] trips on which the lane was still live
    # Per shard, broadcast to each of its lanes like `loop_trips`:
    phase_trips: jax.Array       # [S, 6] trips on which any lane of the
    #   shard fired each phase of `PHASES`
    fault_eval_trips: jax.Array  # [S] trips that evaluated the kill /
    #   deadline bodies (every trip where `fcaps` compiled them in)
    fault_fire_trips: jax.Array  # [S] trips on which any lane fired a
    #   kill or a deadline drop


def _simulate_batch_impl(mode, params, wls, tree, rate_threshold, plan,
                         tree_axis, thr_axis, plan_axis, step_budget=None,
                         kernels: str = "off", fcaps: tuple = flt.FULL_CAPS):
    TRACE_COUNT["simulate_batch"] += 1
    # One while loop over explicitly-batched state, vmapping only the
    # per-iteration step. Deliberately NOT `vmap(_simulate_impl)`: batching
    # a `while_loop` makes its cond per-lane, and the batching rule then
    # rewrites the body to `select(cond, body(carry), carry)` — a select
    # over the entire carry (including the [T, F] decision log) every
    # iteration. Here cond stays scalar (`any(running)`), finished lanes
    # are frozen by the step's `run` gate instead, and all per-lane writes
    # remain one-row scatters XLA applies in place.
    S, T = wls.task_type.shape
    I = wls.inst_arrival.shape[1]
    n_pes = params.pe_cluster.shape[0]
    max_iters = 3 * T + I + 64
    if plan is not None:
        # [S] when the plan is batched; `it < max_iters` is elementwise
        max_iters = _fault_iter_bound(max_iters, T, I, n_pes, plan)
    if step_budget is not None:
        max_iters = jnp.minimum(jnp.asarray(max_iters, jnp.int32),
                                jnp.int32(step_budget))

    step = jax.vmap(
        functools.partial(_masked_step, mode, params, kmode=kernels,
                          fcaps=fcaps),
        in_axes=(0, 0, tree_axis, thr_axis, plan_axis, 0),
    )

    def running(s, it):
        return (s.n_done < wls.n_tasks) & ~s.stalled & (it < max_iters)

    _, can_kill, has_deadline = fcaps if plan is not None else flt.NO_CAPS
    fault_bodies = jnp.int32(can_kill or has_deadline)

    def cond(carry):
        s, it = carry[:2]
        return jnp.any(running(s, it))

    def body(carry):
        s, it, act, trips, phase, f_eval, f_fire = carry
        run = running(s, it)
        s, ev = step(s, wls, tree, rate_threshold, plan, run)
        # it counts retired *events*, matching the sequential n_iters
        # (a super-step can retire up to 4, or 6 with faults). A lane
        # within a few of max_iters may overshoot the cap by a couple of
        # events; max_iters is a pathology backstop, so the slack is
        # irrelevant in practice. Everything after `it` is telemetry
        # only — it feeds BatchTelemetry, never the result. The fault
        # bodies run inside `step` on every trip while `fcaps` traces
        # them, so `f_eval` counts here.
        any_fired = jnp.any(s.fired > 0, axis=0)
        return (s, it + ev, act + run.astype(jnp.int32),
                trips + 1, phase + any_fired.astype(jnp.int32),
                f_eval + fault_bodies,
                f_fire + (any_fired[1] | any_fired[2]).astype(jnp.int32))

    if plan is None:
        pe_slow, slow_axis = None, None
    else:
        pe_slow = plan.cluster_slowdown[..., params.pe_cluster]
        slow_axis = 0 if pe_slow.ndim == 2 else None
    s0 = jax.vmap(_init_state, in_axes=(0, None, slow_axis))(
        wls, n_pes, pe_slow)
    zero = jnp.int32(0)
    s, iters, act, trips, phase, f_eval, f_fire = jax.lax.while_loop(
        cond, body,
        (s0, jnp.zeros(S, jnp.int32), jnp.zeros(S, jnp.int32), zero,
         jnp.zeros(len(PHASES), jnp.int32), zero, zero))
    # max_iters is [S] when a batched plan varied it per lane, scalar
    # otherwise; either way every lane sees the same cap as the sequential
    # path, so `stall_reason` stays bit-exact between the two engines
    mi = jnp.asarray(max_iters, jnp.int32)
    mi_axis = 0 if mi.ndim == 1 else None
    res = jax.vmap(_finalize, in_axes=(0, 0, 0, mi_axis))(wls, s, iters, mi)
    # shard counts broadcast to [S] so sharded runs report each lane
    # against its own shard's loop (sum over lanes of loop_trips ==
    # lane-iterations allocated)
    tel = BatchTelemetry(
        loop_trips=jnp.full((S,), trips, jnp.int32), active_trips=act,
        phase_trips=jnp.broadcast_to(phase, (S, len(PHASES))),
        fault_eval_trips=jnp.full((S,), f_eval, jnp.int32),
        fault_fire_trips=jnp.full((S,), f_fire, jnp.int32))
    return res, tel


_simulate_batch = jax.jit(_simulate_batch_impl,
                          static_argnums=(0, 6, 7, 8, 9, 10, 11))


class _Spans:
    """Host spans of one engine call: the call itself (`root`, e.g.
    `run_batch`) and its steps (`<root>.<step>`).

    Every span opens a profiler annotation `repro.<name>`, so xprof and
    Perfetto show it on the device trace's clock, above the device ops it
    caused. When the caller passed a telemetry sink, the span is also kept
    as `{"name", "parent", "start_ns", "end_ns"}` on
    `time.perf_counter_ns()`: the call's own spans go on the first record
    the call appends (`attach`), a chunk's on that chunk's record (`into`).
    """

    def __init__(self, root: str, telemetry: list | None):
        self.root = root
        self.telemetry = telemetry
        self.first = len(telemetry) if telemetry is not None else 0
        self.call = [] if telemetry is not None else None

    @property
    def on(self) -> bool:
        return self.call is not None

    @contextlib.contextmanager
    def __call__(self, step: str | None = None, into: list | None = None):
        name = self.root if step is None else f"{self.root}.{step}"
        with jax.profiler.TraceAnnotation(f"repro.{name}"):
            start = time.perf_counter_ns()
            try:
                yield
            finally:
                if self.on:
                    (self.call if into is None else into).append({
                        "name": name,
                        "parent": None if step is None else self.root,
                        "start_ns": start, "end_ns": time.perf_counter_ns()})

    def attach(self) -> None:
        """Put the call's own spans on the first record it appended."""
        if self.on and len(self.telemetry) > self.first:
            rec = self.telemetry[self.first]
            rec["spans"] = sorted(self.call + rec["spans"],
                                  key=lambda sp: sp["start_ns"])


def _engine_call(spans: _Spans, mode: int, params: SimParams,
                 wls: FlatWorkload, tree: DTree, rate_threshold: jax.Array,
                 plan, step_budget: int | None, kernels: str | None):
    """One unsharded engine call under a `dispatch` span, with its
    telemetry record (and `fetch` span) when a sink is on."""
    own = [] if spans.on else None
    with spans("dispatch", own):
        tree_axis = 0 if tree.feat.ndim == 2 else None
        thr_axis = 0 if getattr(rate_threshold, "ndim", 0) >= 1 else None
        plan_axis = (0 if plan is not None and plan.pe_fail_at.ndim == 2
                     else None)
        fcaps = flt.plan_capabilities(plan) if plan is not None \
            else flt.NO_CAPS
        res, tel = _simulate_batch(mode, params, wls, tree, rate_threshold,
                                   plan, tree_axis, thr_axis, plan_axis,
                                   step_budget, _kops.kernel_mode(kernels),
                                   fcaps)
    if spans.on:
        spans.telemetry.append(_telemetry_record(
            res, tel, len(tel.loop_trips.devices()), spans, own))
    return res


def simulate_batch(mode: int, params: SimParams, wls: FlatWorkload,
                   tree: DTree, rate_threshold: jax.Array,
                   plan=None, step_budget: int | None = None,
                   kernels: str | None = None,
                   telemetry: list | None = None) -> SimResult:
    """`jax.vmap` of `simulate` over a leading scenario axis.

    `wls` is a stacked workload (`workloads.stack_workloads`): every field
    carries a leading `[S]` axis. `params` and `mode` are shared across
    scenarios. `tree` and `rate_threshold` are broadcast when unbatched, or
    swept per-scenario when given a leading `[S]` axis (threshold sweeps,
    per-scenario DAS trees). `plan` batches the same way: a single
    `faults.FaultPlan` is shared, `faults.stack_plans` sweeps one fault
    scenario per lane. Returns a `SimResult` whose every field has a
    leading `[S]` axis; scenario results are bit-identical to running
    `simulate` one scenario at a time on CPU — with or without faults.

    `kernels` overrides the `REPRO_SIM_KERNELS` knob (resolved here, at
    call time, so env flips dispatch correctly). When `telemetry` is a
    list, a per-call record (`_telemetry_record`: occupancy, phase
    counters, and the `simulate_batch` spans) is appended to it.
    """
    spans = _Spans("simulate_batch", telemetry)
    with spans():
        res = _engine_call(spans, mode, params, wls, tree, rate_threshold,
                           plan, step_budget, kernels)
    spans.attach()
    return res


def _telemetry_record(res: SimResult, tel: BatchTelemetry, devices: int,
                      spans: _Spans, own: list) -> dict:
    """Host-side record of one engine call (blocks on `tel`, under a
    `fetch` span); `devices` is how many devices the call's lanes ran on,
    `own` the spans of this call's chunk (the fetch span joins them).

    Counters of the loop's shards (`phase_trips`, `fault_eval_trips`,
    `fault_fire_trips`) are summed over shards, not lanes.
    """
    with spans("fetch", own):
        loop, act, events, phase, f_eval, f_fire = jax.device_get((
            tel.loop_trips, tel.active_trips, res.n_iters,
            tel.phase_trips, tel.fault_eval_trips, tel.fault_fire_trips))
    loop = np.asarray(loop)
    act = np.asarray(act)
    allocated = int(loop.sum())
    # each shard's counts sit on every one of its lanes; shards are equal
    first_lanes = slice(None, None, loop.shape[0] // devices)
    phase = np.asarray(phase)[first_lanes].sum(axis=0)
    return {
        "lanes": int(loop.shape[0]),
        "devices": devices,
        "lane_trips": allocated,            # sum over lanes of shard trips
        "active_trips": int(act.sum()),     # trips with the lane still live
        "events": int(np.asarray(events).sum()),  # retired simulator events
        "occupancy": float(act.sum() / allocated) if allocated else 1.0,
        "phase_trips": {n: int(v) for n, v in zip(PHASES, phase)},
        "fault_eval_trips": int(np.asarray(f_eval)[first_lanes].sum()),
        "fault_fire_trips": int(np.asarray(f_fire)[first_lanes].sum()),
        "spans": own,
    }


def to_device(wl: FlatWorkload) -> FlatWorkload:
    return FlatWorkload(*[jnp.asarray(x) for x in wl])


def result_at(res: SimResult, i: int) -> SimResult:
    """Slice scenario `i` out of a batched `SimResult`."""
    return jax.tree_util.tree_map(lambda x: x[i], res)


def _check_plan(plan, params: SimParams, batched: bool):
    """Validate a user-supplied FaultPlan (host side)."""
    if plan is None:
        return None
    plan = flt.validate_plan(plan, n_pes=params.pe_cluster.shape[0],
                             n_clusters=params.cluster_pe_mask.shape[0])
    if not batched and flt.is_batched(plan):
        raise ValueError("run: got a batched FaultPlan (leading scenario "
                         "axis); use run_batch for plan sweeps")
    return plan


def _plan_to_device(plan):
    return None if plan is None else flt.FaultPlan(
        *[jnp.asarray(x) for x in plan])


def _resolve_devices(devices) -> tuple:
    """Resolve the `devices=` knob (or `REPRO_BENCH_DEVICES`) to a device
    tuple. `None` -> env var if set, else every local device; an int takes
    the first k of `jax.devices()`; a sequence of devices passes through."""
    if devices is None:
        raw = os.environ.get("REPRO_BENCH_DEVICES")
        if raw is not None and raw.strip():
            try:
                devices = int(raw.strip())
            except ValueError:
                raise ValueError(
                    f"REPRO_BENCH_DEVICES={raw!r} is not an integer"
                ) from None
    if devices is None:
        return tuple(jax.devices())
    if isinstance(devices, int):
        avail = jax.devices()
        if not 1 <= devices <= len(avail):
            raise ValueError(
                f"devices={devices} out of range (1..{len(avail)} available)")
        return tuple(avail[:devices])
    return tuple(devices)


@functools.lru_cache(maxsize=None)
def _sharded_batch_fn(mode: int, tree_axis, thr_axis, plan_axis,
                      has_plan: bool, devices: tuple,
                      step_budget: int | None = None,
                      kernels: str = "off", fcaps: tuple = flt.FULL_CAPS):
    """Compiled scenario-sharded batch engine over a fixed device tuple.

    Shards the leading scenario axis of every batched argument across
    `devices` with `jax.shard_map`. Each shard runs its own independent
    masked while loop — lanes never interact, so there is no collective
    in the body and no cross-device sync until the caller fetches:
    per-scenario results are bit-identical regardless of device count.
    Cached per (mode, batched-axes, devices) so every fixed-shape chunk
    of a sweep reuses one executable.
    """
    def call(params, wls, tree, rate_threshold, plan):
        return _simulate_batch_impl(mode, params, wls, tree, rate_threshold,
                                    plan, tree_axis, thr_axis, plan_axis,
                                    step_budget, kernels, fcaps)

    mesh = Mesh(np.array(devices), ("s",))
    sh = PartitionSpec("s")
    rep = PartitionSpec()
    t_spec = sh if tree_axis == 0 else rep
    r_spec = sh if thr_axis == 0 else rep
    if has_plan:
        fn = jax.shard_map(call, mesh=mesh,
                           in_specs=(rep, sh, t_spec, r_spec,
                                     sh if plan_axis == 0 else rep),
                           out_specs=sh, check_vma=False)
        return jax.jit(fn)
    fn = jax.shard_map(
        lambda params, wls, tree, rt: call(params, wls, tree, rt, None),
        mesh=mesh, in_specs=(rep, sh, t_spec, r_spec), out_specs=sh,
        check_vma=False)
    return jax.jit(lambda params, wls, tree, rt, plan:
                   fn(params, wls, tree, rt))


def run(mode: int, wl: FlatWorkload, params: SimParams | None = None,
        tree: DTree | None = None,
        rate_threshold: float = 1e9,
        plan=None, step_budget: int | None = None,
        kernels: str | None = None) -> SimResult:
    """Convenience wrapper (host-side numpy workload ok). `plan` threads
    an optional `faults.FaultPlan` through the simulation; `step_budget`
    caps the event-loop iterations (stall diagnostics in
    `SimResult.stall_reason`); `kernels` overrides `REPRO_SIM_KERNELS`
    (decision-kernel dispatch, resolved at call time)."""
    params = params or make_params()
    tree = tree or always_fast_tree()
    plan = _plan_to_device(_check_plan(plan, params, batched=False))
    fcaps = flt.plan_capabilities(plan) if plan is not None else flt.NO_CAPS
    return simulate(mode, params, to_device(wl), tree,
                    jnp.float32(rate_threshold), plan, step_budget,
                    _kops.kernel_mode(kernels), fcaps)


def run_batch(mode: int, wls, params: SimParams | None = None,
              tree: DTree | None = None,
              rate_threshold=1e9,
              batch_size: int | None = None,
              plan=None,
              devices=None,
              step_budget: int | None = None,
              kernels: str | None = None,
              telemetry: list | None = None) -> SimResult:
    """Sharded, streaming batched sweep over a scenario axis.

    `wls` is either a list of same-shape `FlatWorkload`s or an
    already-stacked workload (leading `[S]` axis on every field).
    `batch_size` chunks the scenario axis so peak memory stays bounded on
    large sweeps — benchmarks wire it to the `REPRO_BENCH_BATCH` env knob.
    `tree` / `rate_threshold` / `plan` (a `faults.FaultPlan`, batched via
    `faults.stack_plans`) may carry a leading `[S]` axis to vary per
    scenario; chunking slices them along with the workloads.

    Every chunk has the same fixed shape: the ragged final chunk is padded
    up to `batch_size` by replaying the last real scenario, and the pad
    lanes are sliced off before return — so a whole sweep (and every sweep
    of the same chunk size) reuses ONE compiled executable instead of
    retracing for the remainder chunk. `devices` (or `REPRO_BENCH_DEVICES`,
    default: all of `jax.devices()`) shards the scenario axis of each chunk
    across devices with `jax.shard_map`; lanes are
    independent, so per-scenario results are bit-identical for any
    `batch_size` and any device count. Chunks are dispatched
    asynchronously and fetched once at the end, overlapping host-side tree
    slicing with device compute.

    `kernels` overrides the `REPRO_SIM_KERNELS` decision-kernel knob
    (resolved here at call time). When `telemetry` is a list, one
    record per chunk (`_telemetry_record`: lane-iterations allocated vs.
    retired, phase counters, spans) is appended to it — out-of-band so
    results stay bit-exact across chunk compositions. The call's own
    spans (`run_batch`, `.stack`, `.to_device`, and the one `.fetch` of a
    multi-chunk sweep) go on its first record; each chunk's `.dispatch`
    and `.fetch` on its own.
    """
    spans = _Spans("run_batch", telemetry)
    with spans():
        res = _run_batch(spans, mode, wls, params, tree, rate_threshold,
                         batch_size, plan, devices, step_budget, kernels)
    spans.attach()
    return res


def _run_batch(spans: _Spans, mode, wls, params, tree, rate_threshold,
               batch_size, plan, devices, step_budget, kernels):
    """`run_batch`'s body, one `spans` step at a time."""
    with spans("stack"):
        if batch_size is not None and batch_size <= 0:
            raise ValueError(
                f"batch_size must be positive, got {batch_size}")
        params = params or make_params()
        tree = tree or always_fast_tree()
        plan = _check_plan(plan, params, batched=True)
        if isinstance(wls, FlatWorkload):
            stacked = wls
        else:
            stacked = stack_workloads(wls)
        n = stacked.task_type.shape[0]
        plan_b = plan is not None and flt.is_batched(plan)
        if plan_b and np.shape(plan.pe_fail_at)[0] != n:
            raise ValueError(
                f"run_batch: batched plan has {np.shape(plan.pe_fail_at)[0]}"
                f" scenarios but the workload has {n}")
    with spans("to_device"):
        stacked = to_device(stacked)
        plan = _plan_to_device(plan)
        if not isinstance(rate_threshold, jax.Array):
            rate_threshold = jnp.float32(rate_threshold)
        if spans.on:
            # time the copy itself, not its enqueue
            jax.block_until_ready((stacked, plan, rate_threshold))

    devs = _resolve_devices(devices)
    D = len(devs)
    # fixed chunk shape: user size clamped to n, rounded up to a device
    # multiple so every shard is equal-sized
    B = n if batch_size is None else min(batch_size, n)
    B = -(-B // D) * D
    if D == 1 and B >= n:
        # single device, single chunk: the plain vmapped engine
        return _engine_call(spans, mode, params, stacked, tree,
                            rate_threshold, plan, step_budget, kernels)

    kern = _kops.kernel_mode(kernels)
    fcaps = flt.plan_capabilities(plan) if plan is not None else flt.NO_CAPS
    tree_b = tree.feat.ndim == 2
    thr_b = rate_threshold.ndim >= 1
    if D > 1:
        dispatch = _sharded_batch_fn(mode, 0 if tree_b else None,
                                     0 if thr_b else None,
                                     0 if plan_b else None,
                                     plan is not None, devs, step_budget,
                                     kern, fcaps)
    else:
        def dispatch(p, w, t, rt, pl):
            return _simulate_batch(mode, p, w, t, rt, pl,
                                   0 if tree_b else None,
                                   0 if thr_b else None,
                                   0 if plan_b else None, step_budget,
                                   kern, fcaps)

    n_pad = -(-n // B) * B
    # pad lanes replay the last real scenario; their results are dropped
    pad_idx = np.minimum(np.arange(n_pad), n - 1)
    chunks, own = [], []
    for lo in range(0, n_pad, B):
        ids = pad_idx[lo:lo + B]
        if ids[-1] == lo + B - 1:          # fully-real chunk: cheap slice
            def sl(x, lo=lo):
                return x[lo:lo + B]
        else:                              # final chunk: padded gather
            def sl(x, ids=ids):
                return x[ids]
        own.append([] if spans.on else None)
        with spans("dispatch", own[-1]):
            part = jax.tree_util.tree_map(sl, stacked)
            t = jax.tree_util.tree_map(sl, tree) if tree_b else tree
            rt = sl(rate_threshold) if thr_b else rate_threshold
            pl = jax.tree_util.tree_map(sl, plan) if plan_b else plan
            chunks.append(dispatch(params, part, t, rt, pl))
    n_devs = [len(tel_c.loop_trips.devices()) for _, tel_c in chunks]
    with spans("fetch"):
        # one blocking fetch for the whole sweep (dispatches above are
        # async)
        chunks = jax.device_get(chunks)
        res = jax.tree_util.tree_map(
            lambda *xs: np.concatenate(xs, axis=0)[:n],
            *[res_c for res_c, _ in chunks])
    if spans.on:
        for (res_c, tel_c), d, o in zip(chunks, n_devs, own):
            spans.telemetry.append(_telemetry_record(res_c, tel_c, d,
                                                     spans, o))
    return res
