"""The plain reference for the benchmark's `correct` check.

A straightforward discrete-event simulator of the configured SoC, written
from the configuration file alone: it imports nothing of the program under
test and takes nothing the program has made. It builds each scenario's
task stream itself from the scenario's spec (mix, rate, frames, seed) and
simulates it in float64 with plain lists and floats.

Event semantics, in priority order on every loop iteration (one event per
iteration, counted in `events`):

  1. the earliest completion that is due (ties: lowest task id);
  2. with a fault plan: the earliest fault instant that revokes a live
     assignment (`assign_t < tau <= now`, lowest task id on ties), then
     the earliest job deadline that has passed (lowest instance id);
  3. the next frame arrival, if due;
  4. one scheduling decision, if the chosen scheduler has a feasible
     (task, PE) pair;
  5. otherwise time advances to the next completion, arrival, fault
     instant, repair or pending deadline.

Schedulers: LUT takes the FIFO head to the most energy-efficient cluster
(with a plan: among clusters with a live PE) and its earliest-free live
PE; ETF scans ready slots in FIFO order and PEs ascending and keeps the
first minimum of the finish time; DAS walks the configured depth-2 tree
over the rate register's estimate and the big cluster's earliest
availability, then runs the scheduler it picked. Each decision occupies
the scheduler core for its latency and burns its energy.

`precision` rounds every stored float to the named type after each
operation. "float64" is the reference; "bfloat16" is the control that
the limits of `check.py` were proven against.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence

import numpy as np

INF = math.inf

# the program's feature bank indices that the DAS tree may test
FEATURES = {0: "input_data_rate", 2: "cluster_avail_big"}


def _rounder(precision: str):
    if precision == "float64":
        return float
    if precision == "float32":
        return lambda x: float(np.float32(x))
    if precision == "bfloat16":
        import ml_dtypes
        bf16 = ml_dtypes.bfloat16
        return lambda x: float(np.float32(x).astype(bf16))
    raise ValueError(f"unknown precision {precision!r}")


class Platform:
    """The configuration's SoC: PEs, clusters, tables, scheduler model."""

    def __init__(self, cfg: dict):
        plat = cfg["platform"]
        self.clusters = list(plat["clusters"])
        self.task_types = list(plat["task_types"])
        self.pe_cluster = [c for c, n in enumerate(plat["pes_per_cluster"])
                           for _ in range(n)]
        self.n_pes = len(self.pe_cluster)
        ex = [[INF if v is None else float(v) for v in row]
              for row in plat["exec_time_us"]]
        self.exec_time = ex                                  # [type][cl]
        self.power = [float(v) for v in plat["cluster_power_w"]]
        self.energy = [[e * self.power[c] if e < INF else INF
                        for c, e in enumerate(row)] for row in ex]
        self.lut_cluster = [min(range(len(row)), key=lambda c: (row[c], c))
                            for row in self.energy]
        self.us_per_kb = float(plat["noc_us_per_kb"])
        self.lut_latency = float(plat["lut_latency_us"])
        self.lut_energy = float(plat["lut_energy_uj"])
        self.cls_energy = float(plat["das_classifier_energy_uj"])
        self.etf_poly = [float(v) for v in plat["etf_latency_us_poly"]]
        self.sched_power = float(plat["scheduler_power_w"])
        self.ready_slots = int(plat["ready_queue_slots"])
        self.ring = int(plat["rate_register_entries"])
        self.frame_kbits = float(cfg["stream"]["frame_kbits"])
        self.apps = cfg["apps"]
        self.app_names = list(self.apps)


class Workload:
    """One scenario's task stream (lists; task ids are a topological
    order, each frame's tasks contiguous)."""

    def __init__(self):
        self.task_type: List[int] = []
        self.inst_id: List[int] = []
        self.out_kb: List[float] = []
        self.preds: List[List[int]] = []
        self.succs: List[List[int]] = []
        self.arrival: List[float] = []
        self.roots: List[List[int]] = []


def build(plat: Platform, mix: Sequence[float], rate_mbps: float,
          frames: int, seed: int) -> Workload:
    """The scenario's frames: a largest-deficit interleave of the apps in
    the mix's proportions, and exponential inter-arrival gaps of mean
    `frame_kbits * 1000 / rate` microseconds drawn from `seed`, the
    first frame at time 0."""
    m = np.asarray(np.asarray(mix, np.float32), np.float64)
    m = m / m.sum()
    counts = np.zeros(len(m))
    order = []
    for i in range(frames):
        a = int(np.argmax(m * (i + 1) - counts))
        order.append(a)
        counts[a] += 1
    rng = np.random.RandomState(seed)
    gaps = rng.exponential(plat.frame_kbits * 1e3 / rate_mbps, size=frames)
    gaps[0] = 0.0
    arrivals = np.cumsum(gaps)

    wl = Workload()
    type_id = {n: k for k, n in enumerate(plat.task_types)}
    for i, a in enumerate(order):
        spec = plat.apps[plat.app_names[a]]
        base = len(wl.task_type)
        roots = []
        for j, (ttype, preds, kb) in enumerate(spec):
            wl.task_type.append(type_id[ttype])
            wl.inst_id.append(i)
            wl.out_kb.append(float(kb))
            wl.preds.append([base + q for q in preds])
            wl.succs.append([])
            if not preds:
                roots.append(base + j)
        for j, (_, preds, _) in enumerate(spec):
            for q in preds:
                wl.succs[base + q].append(base + j)
        wl.arrival.append(float(arrivals[i]))
        wl.roots.append(roots)
    return wl


class Plan:
    """A fault plan as plain floats: per-PE failure and repair instants,
    transient glitch instants, cluster slowdowns, retry budget, deadline."""

    def __init__(self, fail_at, repair_at, transient_at, slowdown,
                 max_retries, deadline_us):
        self.fail_at = [float(v) for v in fail_at]
        self.repair_at = [float(v) for v in repair_at]
        self.transient_at = [[float(v) for v in row] for row in transient_at]
        self.slowdown = [float(v) for v in slowdown]
        self.max_retries = int(max_retries)
        self.deadline_us = float(deadline_us)


def simulate(mode: str, plat: Platform, wl: Workload, *, tree=None,
             plan: Plan | None = None,
             precision: str = "float64") -> Dict:
    """Simulate one scenario under scheduler `mode` ("LUT", "ETF" or
    "DAS"); `tree` is the DAS tree ({"feat", "thr", "leaf"})."""
    if mode not in ("LUT", "ETF", "DAS"):
        raise ValueError(f"unknown mode {mode!r}")
    if mode == "DAS":
        bad = [f for f in tree["feat"] if f not in FEATURES]
        if bad:
            raise ValueError(f"DAS tree tests features {bad}; the reference "
                             f"knows {sorted(FEATURES)}")
    q = _rounder(precision)
    P = plat.n_pes
    pe_cluster = plat.pe_cluster
    pe_power = [q(plat.power[c]) for c in pe_cluster]
    n_tasks = len(wl.task_type)
    n_inst = len(wl.arrival)
    arrival = [q(a) for a in wl.arrival]
    us_per_kb = q(plat.us_per_kb)

    if plan is not None:
        fail_at, repair_at = plan.fail_at, plan.repair_at
        kill_times = [[fail_at[p]] + plan.transient_at[p] for p in range(P)]
        pe_slow = [plan.slowdown[c] for c in pe_cluster]
        fault_times = (fail_at + repair_at
                       + [t for row in kill_times for t in row])
        deadline = plan.deadline_us
    else:
        pe_slow = [1.0] * P

    def exec_on(t: int, pe: int) -> float:
        e = plat.exec_time[wl.task_type[t]][pe_cluster[pe]]
        return e if e == INF else q(q(e) * pe_slow[pe])

    pred_rem = [len(p) for p in wl.preds]
    finish = [INF] * n_tasks
    start = [INF] * n_tasks
    pe_of = [-1] * n_tasks
    status = [0] * n_tasks      # 0 wait, 2 ready, 3 run, 4 done, 5 dropped
    ready_base = [0.0] * n_tasks
    ready: List[int] = []       # FIFO
    pe_free = [0.0] * P
    pe_alive = [True] * P
    now = 0.0
    sched_free = 0.0
    arr_ptr = 0
    n_done = 0
    events = 0
    ready_drop = 0
    n_slow = 0
    task_energy = 0.0
    sched_energy = 0.0
    assign_t = [INF] * n_tasks
    retries = [0] * n_tasks
    inst_rem = [0] * n_inst
    for t in range(n_tasks):
        inst_rem[wl.inst_id[t]] += 1
    job_dropped = [False] * n_inst
    n_kills = 0

    def push(t: int, base: float):
        nonlocal ready_drop
        if len(ready) >= plat.ready_slots:
            ready_drop += 1
            return
        ready_base[t] = base
        status[t] = 2
        ready.append(t)

    def avail(t: int, pe: int) -> float:
        base = ready_base[t]
        for p in wl.preds[t]:
            comm = (q(wl.out_kb[p] * us_per_kb)
                    if pe_cluster[pe_of[p]] != pe_cluster[pe] else 0.0)
            base = max(base, q(finish[p] + comm))
        return base

    def lut_choice():
        tt = wl.task_type[ready[0]]
        if plan is None:
            cl = plat.lut_cluster[tt]
        else:
            live = [c for c in range(len(plat.clusters))
                    if any(pe_alive[p] and pe_cluster[p] == c
                           for p in range(P))]
            best = min(live, key=lambda c: (plat.energy[tt][c], c),
                       default=None)
            if best is None or plat.energy[tt][best] == INF:
                return None
            cl = best
        pes = [p for p in range(P) if pe_cluster[p] == cl and pe_alive[p]]
        return 0, min(pes, key=lambda p: (pe_free[p], p))

    def etf_choice():
        best = (INF, -1, -1)
        for slot, t in enumerate(ready):
            for pe in range(P):
                if not pe_alive[pe]:
                    continue
                e = exec_on(t, pe)
                if e == INF:
                    continue
                ft = q(max(avail(t, pe), pe_free[pe], now) + e)
                if ft < best[0]:
                    best = (ft, slot, pe)
        return None if best[1] < 0 else (best[1], best[2])

    def use_slow() -> bool:
        def feature(f):
            if f == 0:      # rate estimate from the last arrivals
                k = min(arr_ptr, plat.ring)
                if k < 2:
                    return 0.0
                span = max(q(arrival[arr_ptr - 1] - arrival[arr_ptr - k]),
                           1e-3)
                return q((k - 1) * plat.frame_kbits * 1000.0 / span)
            # earliest availability of the big cluster (cluster 0)
            return min(max(q(pe_free[p] - now), 0.0) for p in range(P)
                       if pe_cluster[p] == 0)
        feat, thr, leaf = tree["feat"], tree["thr"], tree["leaf"]
        right = [feature(feat[k]) >= thr[k] for k in range(3)]
        idx = (2 + right[2]) if right[0] else int(right[1])
        return bool(leaf[idx])

    def rollback(victims):
        """Refund the unexecuted tail of running victims and rebuild the
        free time of every PE that lost one."""
        nonlocal task_energy
        hit = set()
        for t in victims:
            if status[t] != 3:
                continue
            pe = pe_of[t]
            total = q(finish[t] - start[t])
            done = min(max(q(now - start[t]), 0.0), total)
            task_energy = q(task_energy - q(q(total - done) * pe_power[pe]))
            hit.add(pe)
        vset = set(victims)
        for pe in hit:
            surv = [finish[u] for u in range(n_tasks)
                    if status[u] == 3 and pe_of[u] == pe and u not in vset]
            pe_free[pe] = max(max(surv, default=-INF), now)

    def drop_instance(i: int):
        nonlocal n_done
        victims = [t for t in range(n_tasks)
                   if wl.inst_id[t] == i and status[t] < 4]
        rollback(victims)
        vset = set(victims)
        ready[:] = [t for t in ready if t not in vset]
        for t in victims:
            status[t] = 5
            finish[t] = -INF
            start[t] = INF
            assign_t[t] = INF
        n_done += len(victims)
        inst_rem[i] = 0
        job_dropped[i] = True

    while n_done < n_tasks:
        if plan is not None:
            pe_alive = [not (fail_at[p] <= now < repair_at[p])
                        for p in range(P)]
        # 1. completions due
        due = [(finish[t], t) for t in range(n_tasks)
               if status[t] == 3 and finish[t] <= now]
        if due:
            events += 1
            _, t = min(due)
            status[t] = 4
            n_done += 1
            inst_rem[wl.inst_id[t]] -= 1
            for s in wl.succs[t]:
                pred_rem[s] -= 1
                if pred_rem[s] == 0:
                    base = max((finish[p] for p in wl.preds[s]), default=now)
                    push(s, max(base, now))
            continue
        if plan is not None:
            # 2a. fault kills due (earliest tau, lowest task id)
            kt, ktau = -1, INF
            for t in range(n_tasks):
                if status[t] != 3:
                    continue
                for tau in kill_times[pe_of[t]]:
                    if assign_t[t] < tau <= now and tau < ktau:
                        ktau, kt = tau, t
            if kt >= 0:
                events += 1
                t = kt
                rollback([t])
                exhausted = retries[t] >= plan.max_retries
                retries[t] += 1
                n_kills += 1
                status[t] = 0
                finish[t] = start[t] = assign_t[t] = INF
                pe_of[t] = -1
                if exhausted:
                    drop_instance(wl.inst_id[t])
                else:
                    push(t, now)
                continue
            # 2b. job deadlines due (earliest deadline, lowest instance id)
            di, ddl = -1, INF
            for i in range(arr_ptr):
                if inst_rem[i] <= 0:
                    continue
                dl = q(arrival[i] + deadline)
                if dl <= now and dl < ddl:
                    ddl, di = dl, i
            if di >= 0:
                events += 1
                drop_instance(di)
                continue
        # 3. arrivals due
        if arr_ptr < n_inst and arrival[arr_ptr] <= now:
            events += 1
            i = arr_ptr
            arr_ptr += 1
            for r in wl.roots[i]:
                push(r, arrival[i])
            continue
        # 4. one scheduling decision, when the chosen scheduler can place
        if ready:
            n = len(ready)
            slow = {"LUT": False, "ETF": True}.get(mode)
            if slow is None:
                slow = use_slow()
            choice = etf_choice() if slow else lut_choice()
            if choice is not None:
                events += 1
                if slow:
                    c0, c1, c2 = plat.etf_poly
                    lat = q(q(q(c0 + q(c1 * n)) + q(q(c2 * n) * n)))
                    e = q(lat * plat.sched_power)
                    n_slow += 1
                else:
                    lat, e = q(plat.lut_latency), q(plat.lut_energy)
                if mode == "DAS":
                    e = q(e + plat.cls_energy)
                slot, pe = choice
                t = ready.pop(slot)
                sched_done = q(max(sched_free, now) + lat)
                sched_free = sched_done
                st = max(avail(t, pe), pe_free[pe], sched_done, now)
                ex = exec_on(t, pe)
                start[t] = st
                finish[t] = q(st + ex)
                pe_of[t] = pe
                pe_free[pe] = finish[t]
                status[t] = 3
                assign_t[t] = now
                task_energy = q(task_energy + q(ex * pe_power[pe]))
                sched_energy = q(sched_energy + e)
                continue
        # 5. advance time
        nxt = arrival[arr_ptr] if arr_ptr < n_inst else INF
        nxt = min([nxt] + [finish[t] for t in range(n_tasks)
                           if status[t] == 3])
        if plan is not None:
            nxt = min([nxt] + [f for f in fault_times if f > now])
            for i in range(arr_ptr):
                if inst_rem[i] > 0:
                    dl = q(arrival[i] + deadline)
                    if dl > now:
                        nxt = min(nxt, dl)
        if nxt == INF:
            break       # nothing can ever become due again: stalled
        events += 1
        now = max(now, nxt)

    inst_fin = [-INF] * n_inst
    for t in range(n_tasks):
        inst_fin[wl.inst_id[t]] = max(inst_fin[wl.inst_id[t]], finish[t])
    lat = [q(inst_fin[i] - arrival[i]) for i in range(n_inst)
           if not job_dropped[i]]
    return {
        "avg_exec_us": sum(lat) / len(lat) if lat else math.nan,
        "total_energy_uj": task_energy + sched_energy,
        "task_energy_uj": task_energy,
        "sched_energy_uj": sched_energy,
        "events": events,
        "n_tasks": n_tasks,
        "n_done": n_done,
        "n_slow": n_slow,
        "n_faults": n_kills,
        "n_dropped_jobs": sum(job_dropped),
        "ready_drop": ready_drop,
    }
