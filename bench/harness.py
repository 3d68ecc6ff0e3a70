"""One run of one cell: set-up, the measured window, the check.

A cell (an entry of `workloads` in `BENCHMARK.json`) names a
configuration (`bench/configs/<config>.json`) and a traffic mix
(`bench/traffic/<traffic>.json`); its metrics are the modules
`bench/metrics/<metric>.py`, each with `read(run) -> float | None`. The
harness finds all of them by name, so a cell is added with files and one
`workloads` entry.

A run is a closed loop with one client. A request hands the program the
specs of one scenario per lane, and the program builds the workloads
(`workloads.build_workload`), sweeps them in one chunk
(`simulator.run_batch`) and returns the results to the host
(`jax.device_get`). The configuration is the one source of what the
program runs: its tables go to the program, its task graphs and
ready-queue size go where the program takes them, and every other value
the reference reads must equal the program's own (`handover`). Set-up
warms the cell's one program with a request of the same shapes and one
frame per lane. The window runs whole requests until `--seconds` have
passed; with `--trace 1` its first request runs under the profiler and
with the engine's occupancy telemetry. After the window the reference
checks a sample of the lanes (`check.py`).
"""
from __future__ import annotations

import faulthandler
import importlib.util
import inspect
import json
import os
import sys
import tempfile
import time
import types
from typing import List

import numpy as np

from bench import check, devtrace, refsim, traffic as traffic_mod
from bench.compile_clock import CompileClock

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SRC = os.path.join(ROOT, "src")
# fixed, inside the checkout: the path is part of the cache key
CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")
# a run that compiles may take 1200 s; a request takes seconds
SETUP_DEADLINE_S = 1100
REQUEST_DEADLINE_S = 150
# collecting the trace of a whole request takes minutes (millions of ops)
TRACED_DEADLINE_S = 330
CHECK_DEADLINE_S = 300


class NoChip(RuntimeError):
    pass


class Failure(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the cell, by name
# ---------------------------------------------------------------------------
def load_cell(name: str, bench_file: str | None = None):
    with open(bench_file or os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Failure(f"no workload {name!r} in BENCHMARK.json; have "
                      f"{sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(ROOT, conf["file"])) as f:
        cfg = json.load(f)
    traffic = traffic_mod.load(w["traffic"])
    if traffic["mixes"].shape[1] != len(cfg["apps"]):
        raise Failure(f"grid {traffic['grid']!r}: a mix has "
                      f"{traffic['mixes'].shape[1]} fractions, configuration "
                      f"{conf['name']!r} has {len(cfg['apps'])} apps")

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    return types.SimpleNamespace(
        name=name, chips=int(w["chips"]), cfg=cfg,
        traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)],
        check=check.load(name))


def reader(metric: str):
    path = os.path.join(HERE, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------
# Values of the scheduler and stream model that the reference reads from
# the configuration and the program holds as constants (module, names):
# they have to be equal.
CONSTANTS = {
    "stream.frame_kbits": ("workloads", ("FRAME_KBITS",)),
    "platform.rate_register_entries": ("simulator", ("RING",)),
    "platform.lut_latency_us": ("soc", ("LUT_LATENCY_US",)),
    "platform.lut_energy_uj": ("soc", ("LUT_ENERGY_UJ",)),
    "platform.das_classifier_energy_uj": ("soc", ("DAS_CLS_ENERGY_UJ",)),
    "platform.etf_latency_us_poly": ("soc", ("ETF_LAT_C0", "ETF_LAT_C1",
                                             "ETF_LAT_C2")),
    "platform.scheduler_power_w": ("soc", ("SCHED_POWER_W",)),
}


def app_graphs(dfg, cfg) -> list:
    """The configuration's task graphs as the program's `dfg.AppGraph`s,
    in the configuration's order, task types indexed by
    `platform.task_types`."""
    type_id = {t: k for k, t in enumerate(cfg["platform"]["task_types"])}
    return [dfg.AppGraph(np.asarray([type_id[t] for t, _, _ in rows],
                                    np.int32),
                         [tuple(p) for _, p, _ in rows],
                         np.asarray([kb for _, _, kb in rows], np.float32))
            for rows in cfg["apps"].values()]


def _same_graphs(a, b) -> bool:
    return len(a) == len(b) and all(
        np.array_equal(x.task_types, y.task_types)
        and [tuple(p) for p in x.preds] == [tuple(p) for p in y.preds]
        and np.array_equal(x.out_kb, y.out_kb) for x, y in zip(a, b))


def handover(cfg, mods) -> dict:
    """Keyword arguments that hand the program what the configuration
    declares beyond its tables: {"suite": for `workloads.default_suite`,
    "build": for `workloads.build_workload`, "run": for
    `simulator.run_batch`}. The task graphs go as `apps` and the
    ready-queue size as `ready_slots` where the program takes a parameter
    of that name; where it takes none, and for the values in `CONSTANTS`,
    the configuration's value has to equal the program's own, or `Failure`
    names every key that differs. `mods` maps "dfg", "soc", "simulator"
    and "workloads" to the program's modules."""
    dfg, sim, wl = mods["dfg"], mods["simulator"], mods["workloads"]
    kw = {"suite": {}, "build": {}, "run": {}}
    bad = []

    def takes(fn, name):
        return name in inspect.signature(fn).parameters

    apps = app_graphs(dfg, cfg)
    if takes(wl.build_workload, "apps") and takes(wl.default_suite, "apps"):
        kw["suite"]["apps"] = kw["build"]["apps"] = apps
    elif not _same_graphs(apps, list(dfg.APPS.values())):
        bad.append("apps: the task graphs differ from the program's "
                   "dfg.APPS, and workloads.build_workload / default_suite "
                   "take no `apps`")
    slots = int(cfg["platform"]["ready_queue_slots"])
    own_slots = getattr(sim, "R_MAX", None)
    if takes(sim.run_batch, "ready_slots"):
        kw["run"]["ready_slots"] = slots
    elif slots != own_slots:
        bad.append(f"platform.ready_queue_slots: {slots}, the program's "
                   f"simulator.R_MAX is {own_slots}, and run_batch takes "
                   "no `ready_slots`")
    for key, (mod, names) in CONSTANTS.items():
        group, leaf = key.split(".")
        value = cfg[group][leaf]
        own = [getattr(mods[mod], n, None) for n in names]
        if None in own or not np.array_equal(
                np.asarray(value, np.float32).reshape(-1),
                np.asarray(own, np.float32)):
            bad.append(f"{key}: {value}, the program's {mod}."
                       f"{'/'.join(names)}: "
                       f"{', '.join(str(v) for v in own)}")
    if bad:
        raise Failure("the program cannot run this configuration as its "
                      "check does: " + "; ".join(bad))
    return kw


class Program:
    """The sweep engine, driven the way a user drives it."""

    def __init__(self, cell, devices):
        import jax.numpy as jnp
        from repro.core import dfg, faults, simulator as sim, soc, workloads
        self._faults, self._sim, self._wl = faults, sim, workloads
        self.kw = handover(cell.cfg, {"dfg": dfg, "soc": soc,
                                      "simulator": sim,
                                      "workloads": workloads})
        self.devices = devices
        plat = cell.cfg["platform"]
        n = plat["pes_per_cluster"]
        pe_cluster = np.repeat(np.arange(len(n), dtype=np.int32), n)
        exec_time = np.asarray([[np.inf if v is None else v for v in row]
                                for row in plat["exec_time_us"]], np.float32)
        power = np.asarray(plat["cluster_power_w"], np.float32)
        energy = np.where(np.isfinite(exec_time), exec_time * power,
                          np.inf).astype(np.float32)
        self.params = sim.make_params(soc.SoCConfig(
            n_pes=len(pe_cluster), n_clusters=len(n),
            n_task_types=len(plat["task_types"]), pe_cluster=pe_cluster,
            cluster_pe_mask=np.stack([pe_cluster == c
                                      for c in range(len(n))]),
            exec_time=exec_time, cluster_power=power, task_energy=energy,
            lut_cluster=np.argmin(energy, axis=1).astype(np.int32),
            us_per_kb=float(plat["noc_us_per_kb"])))
        mode = cell.traffic["mode"]
        self.mode = {"LUT": sim.MODE_LUT, "ETF": sim.MODE_ETF,
                     "DAS": sim.MODE_DAS}[mode]
        self.tree = None
        if mode == "DAS":
            t = cell.cfg["das_tree"]
            self.tree = sim.DTree(feat=jnp.asarray(t["feat"], jnp.int32),
                                  thr=jnp.asarray(t["thr"], jnp.float32),
                                  leaf=jnp.asarray(t["leaf"], jnp.int32))
        suite = workloads.default_suite(n_instances=cell.traffic["frames"],
                                        **self.kw["suite"])
        self.t_max, self.i_max = suite.t_max, suite.i_max

    def build(self, lanes):
        wls = [self._wl.build_workload(l.mix, l.rate_mbps, l.frames,
                                       seed=l.seed, t_max=self.t_max,
                                       i_max=self.i_max, **self.kw["build"])
               for l in lanes]
        plan = None
        if lanes[0].plan is not None:
            fp = self._faults.FaultPlan
            plan = self._faults.stack_plans([fp(
                l.plan["fail_at"], l.plan["repair_at"],
                l.plan["transient_at"], l.plan["slowdown"],
                np.int32(l.plan["max_retries"]),
                np.float32(l.plan["deadline_us"])) for l in lanes])
        return wls, plan

    def run(self, wls, plan, telemetry=None):
        return self._sim.run_batch(self.mode, wls, self.params,
                                   tree=self.tree, batch_size=len(wls),
                                   plan=plan, devices=self.devices,
                                   telemetry=telemetry, **self.kw["run"])


def request(jax, prog: Program, lanes, telemetry=None) -> dict:
    """One request: build, sweep, fetch. Returns the per-lane fields the
    check reads, as host arrays."""
    ann = jax.profiler.TraceAnnotation
    with ann("bench.request"):
        with ann("bench.build"):
            wls, plan = prog.build(lanes)
        with ann("bench.run_batch"):
            res = prog.run(wls, plan, telemetry)
        with ann("bench.fetch"):
            res = jax.device_get(res)
    return {f: np.asarray(getattr(res, f)) for f in check.FIELDS}


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------
def find_devices(jax, chips: int):
    """The cell's chips, or NoChip: this benchmark never falls back to
    the CPU."""
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no devices: {e}") from None
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX sees platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def enable_compile_cache(jax) -> None:
    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run(cell, seed: int, seconds: float, trace: bool, devs, t_start: float,
        jax) -> dict:
    """Set-up, window and check of one run; returns the result line."""
    clock = CompileClock(jax)
    t_init = time.perf_counter() - t_start
    prog = Program(cell, len(devs))
    t0 = time.perf_counter()
    warm_lanes = traffic_mod.request(cell.traffic, cell.cfg, seed, 0,
                                     warm=True)
    request(jax, prog, warm_lanes)
    setup_s = time.perf_counter() - t_start
    log(f"setup: init {t_init!r} s, program {t0 - t_start - t_init!r} s, "
        f"warm request {time.perf_counter() - t0!r} s of which compile "
        f"{clock.total!r} s ({clock.events} compile events); setup_s "
        f"{setup_s!r}")

    rows: List[dict] = []
    telemetry: list = []
    compiles = clock.events
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
        t_win = time.perf_counter()
        while True:
            lanes = traffic_mod.request(cell.traffic, cell.cfg, seed,
                                        len(rows))
            if trace and not rows:
                faulthandler.dump_traceback_later(TRACED_DEADLINE_S,
                                                  exit=True)
                # device ops and the benchmark's own spans only: the
                # Python tracer would record every call of the build
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                opts.enable_hlo_proto = False
                with jax.profiler.trace(tdir, profiler_options=opts):
                    rows.append(request(jax, prog, lanes, telemetry))
            else:
                faulthandler.dump_traceback_later(REQUEST_DEADLINE_S,
                                                  exit=True)
                rows.append(request(jax, prog, lanes))
            if time.perf_counter() - t_win >= seconds:
                break
        window_s = time.perf_counter() - t_win
        faulthandler.dump_traceback_later(CHECK_DEADLINE_S, exit=True)
        if clock.events != compiles:
            raise Failure(f"{clock.events - compiles} compile event(s) "
                          f"({clock.total!r} s in all) inside the window")
        t_read = time.perf_counter()
        tr = devtrace.read(tdir) if trace else None
        if trace:
            log(f"trace: read in {time.perf_counter() - t_read!r} s")
    events = int(sum(r["n_iters"].sum() for r in rows))
    log(f"window: {len(rows)} requests, {events} events in {window_s!r} s")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    del prog

    ctx = types.SimpleNamespace(
        cell=cell, cfg=cell.cfg, traffic=cell.traffic, seed=seed,
        setup_s=setup_s, window_s=window_s, events=events,
        requests=len(rows), lanes=len(rows[0]["n_iters"]),
        devices=len(devs), device_kind=devs[0].device_kind,
        telemetry=telemetry, trace=tr,
        trace_window=(devtrace.span(tr, "bench.request") if tr else None))
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        v = reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    # lanes that stalled or dropped a ready task
    failed = sum(int(((r["stall_reason"] != 0) | (r["ready_drop"] != 0)).sum())
                 for r in rows)
    result = {"correct": None,
              "attempted": sum(len(r["n_iters"]) for r in rows),
              "failed": failed, "metrics": metrics,
              "device": {"platform": devs[0].platform,
                         "kind": devs[0].device_kind, "count": len(devs),
                         "memory_peak_bytes": int(peak)}}
    if tr is not None and tr["devices"]:
        w = ctx.trace_window
        busy = [devtrace.total(devtrace.busy(d, w))
                for d in tr["devices"].values()]
        result["device"]["busy_s"] = float(np.mean(busy)) / 1e9
        result["device"]["window_s"] = (w[1] - w[0]) / 1e9
        result["breakdown"] = {"device_ops": devtrace.top_ops(tr),
                               "idle_gaps": devtrace.longest_gaps(tr, w)}

    nums = compare(cell, seed, rows)
    limits = cell.check["limits"]
    result["correct"] = check.verdict(nums, limits)
    result["checks"] = {k: {"value": nums[k], "limit": limits[k]}
                        for k in limits}
    return result


def sample_lanes(cell, seed: int, rows: List[dict]) -> list:
    """The (request, lane) pairs the check compares."""
    flat = [(k, j) for k, r in enumerate(rows)
            for j in range(len(r["n_iters"]))]
    longest = max(flat, key=lambda kj: rows[kj[0]]["n_iters"][kj[1]])
    return check.draw(seed, [len(r["n_iters"]) for r in rows], longest,
                      int(cell.check["sample_lanes"]))


def reference(cell, lane, precision: str = "float64") -> dict:
    """The reference's result for one lane's spec."""
    plat = refsim.Platform(cell.cfg)
    wl = refsim.build(plat, lane.mix, lane.rate_mbps, lane.frames, lane.seed)
    plan = None
    if lane.plan is not None:
        p = lane.plan
        plan = refsim.Plan(p["fail_at"], p["repair_at"], p["transient_at"],
                           p["slowdown"], p["max_retries"], p["deadline_us"])
    return refsim.simulate(cell.traffic["mode"], plat, wl,
                           tree=cell.cfg.get("das_tree"), plan=plan,
                           precision=precision)


def compare(cell, seed: int, rows: List[dict]) -> dict:
    """The check's numbers over the sample of lanes."""
    gaps = []
    specs = {}
    for k, j in sample_lanes(cell, seed, rows):
        if k not in specs:
            specs[k] = traffic_mod.request(cell.traffic, cell.cfg, seed, k)
        prog = {f: rows[k][f][j] for f in check.FIELDS}
        gaps.append(check.lane_gap(prog, reference(cell, specs[k][j])))
    return check.numbers(gaps)


def main(args, t_start: float) -> int:
    if not os.path.isdir(os.path.join(SRC, "repro")):
        log(f"bench: no program under {SRC}; run from a checkout of the "
            "repository")
        return 2
    sys.path.insert(0, SRC)
    faulthandler.dump_traceback_later(SETUP_DEADLINE_S, exit=True)
    try:
        cell = load_cell(args.workload)
        import jax
        enable_compile_cache(jax)
        devs = find_devices(jax, cell.chips)
        log(f"device {devs[0].device_kind!r} x {len(devs)}, cell "
            f"{cell.name}, seed {args.seed}")
        result = run(cell, args.seed, args.seconds, bool(args.trace), devs,
                     t_start, jax)
    except NoChip as e:
        log(f"bench: {e}")
        return 2
    except Failure as e:
        log(f"bench: FAIL: {e}")
        return 3
    finally:
        faulthandler.cancel_dump_traceback_later()
    for k, c in result["checks"].items():
        log(f"check {k} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0
