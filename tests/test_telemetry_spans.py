"""The engine's telemetry records: host spans and phase counters.

`run_batch(telemetry=[...])` / `simulate_batch` append one record per
engine call. Besides occupancy, a record holds the host spans of the call
(`spans`: name, parent, start and end on `time.perf_counter_ns()`) and
the loop's phase counters (`phase_trips`, `fault_eval_trips`,
`fault_fire_trips`). The same spans are profiler annotations
`repro.<name>`. None of this may change a result bit.
"""
import glob
import os

import numpy as np
import pytest

from repro.core import faults as flt, simulator as sim, workloads

PARAMS = sim.make_params()
SUITE = workloads.default_suite(n_instances=8)
CELLS = [(0, 0), (5, 13), (17, 9), (33, 13)]
WLS = [SUITE.build(mi, ri) for mi, ri in CELLS]
STEPS = ("stack", "to_device", "dispatch", "fetch")


def _plans(seeds):
    """Plans whose kills and deadline drops fire within 8 frames."""
    return flt.stack_plans([flt.random_plan(i, t_horizon_us=15.0,
                                            deadline_us=3.0) for i in seeds])


def _names(spans):
    return sorted(sp["name"] for sp in spans)


def _union_ns(spans):
    t, total = None, 0
    for sp in sorted(spans, key=lambda sp: sp["start_ns"]):
        lo = sp["start_ns"] if t is None else max(t, sp["start_ns"])
        total += max(0, sp["end_ns"] - lo)
        t = max(lo, sp["end_ns"])
    return total


def _check_nesting(spans, root):
    """Every child inside the one root span; returns (root, children)."""
    (top,) = [sp for sp in spans if sp["name"] == root]
    assert top["parent"] is None
    kids = [sp for sp in spans if sp is not top]
    for sp in kids:
        assert sp["parent"] == root
        assert sp["name"].startswith(root + ".")
        assert top["start_ns"] <= sp["start_ns"] <= sp["end_ns"] \
            <= top["end_ns"]
    return top, kids


def _assert_bit_identical(a, b):
    for name in sim.SimResult._fields:
        x, y = np.asarray(getattr(a, name)), np.asarray(getattr(b, name))
        assert x.dtype == y.dtype and x.tobytes() == y.tobytes(), name


# ---------------------------------------------------------------------------
# (a) spans
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("batch_size", [None, 3])
def test_run_batch_records_every_span(batch_size):
    """One chunk: each span once. Several chunks: the call's spans once on
    the first record, a `dispatch` and a `fetch` on every chunk's record,
    and the sweep's one blocking fetch on the first. The steps cover the
    call."""
    sim.run_batch(sim.MODE_ETF, WLS, PARAMS, batch_size=batch_size,
                  devices=1)  # warm: the spans then time no compile
    tel = []
    sim.run_batch(sim.MODE_ETF, WLS, PARAMS, batch_size=batch_size,
                  devices=1, telemetry=tel)
    chunks = 1 if batch_size is None else -(-len(WLS) // batch_size)
    assert len(tel) == chunks
    first = ["run_batch"] + [f"run_batch.{s}" for s in STEPS]
    if chunks > 1:
        first.append("run_batch.fetch")     # the sweep's one device_get
    assert _names(tel[0]["spans"]) == sorted(first)
    for rec in tel[1:]:
        assert _names(rec["spans"]) == ["run_batch.dispatch",
                                        "run_batch.fetch"]
    every = [sp for rec in tel for sp in rec["spans"]]
    top, kids = _check_nesting(every, "run_batch")
    assert _union_ns(kids) >= 0.95 * (top["end_ns"] - top["start_ns"])
    for sp in every:
        assert set(sp) == {"name", "parent", "start_ns", "end_ns"}


def test_simulate_batch_records_its_own_spans():
    stacked = workloads.stack_workloads(WLS)
    tel = []
    sim.simulate_batch(sim.MODE_LUT, PARAMS, sim.to_device(stacked),
                       sim.always_fast_tree(), np.float32(1e9),
                       telemetry=tel)
    assert len(tel) == 1
    assert _names(tel[0]["spans"]) == ["simulate_batch",
                                       "simulate_batch.dispatch",
                                       "simulate_batch.fetch"]
    _check_nesting(tel[0]["spans"], "simulate_batch")


@pytest.mark.parametrize("mode,with_plan", [(sim.MODE_ETF, False),
                                            (sim.MODE_LUT, True)])
def test_no_sink_records_nothing_and_changes_no_bit(mode, with_plan):
    plan = _plans(range(len(WLS))) if with_plan else None
    spans = sim._Spans("run_batch", None)
    with spans():
        with spans("stack"):
            pass
    assert not spans.on and spans.call is None
    bare = sim.run_batch(mode, WLS, PARAMS, plan=plan, devices=1)
    tel = []
    traced = sim.run_batch(mode, WLS, PARAMS, plan=plan, devices=1,
                           telemetry=tel)
    _assert_bit_identical(bare, traced)
    assert tel and all(rec["spans"] for rec in tel)


# ---------------------------------------------------------------------------
# (b) phase counters
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", [sim.MODE_LUT, sim.MODE_ETF])
@pytest.mark.parametrize("with_plan", [False, True])
def test_phase_trips_match_result_counts_on_one_lane(mode, with_plan):
    """On one lane a trip fires each phase at most once, so the counters
    are the lane's own event counts."""
    for k, wl in enumerate(WLS):
        plan = _plans([k]) if with_plan else None
        tel = []
        r = sim.run_batch(mode, [wl], PARAMS, plan=plan, devices=1,
                          telemetry=tel)
        (rec,) = tel
        pt = rec["phase_trips"]
        assert tuple(pt) == sim.PHASES

        def one(field):
            return int(np.asarray(getattr(r, field))[0])

        assert sum(pt.values()) == one("n_iters")
        assert pt["decide"] == one("n_decisions")
        assert pt["kill"] == one("n_faults")
        assert pt["completion"] == one("n_done") - one("n_dropped_tasks")
        assert pt["arrival"] == int(wl.n_insts)
        assert pt["deadline"] <= one("n_dropped_jobs")
        assert rec["fault_fire_trips"] <= pt["kill"] + pt["deadline"]
        assert rec["fault_fire_trips"] >= max(pt["kill"], pt["deadline"])
        trips = rec["lane_trips"]
        assert rec["fault_eval_trips"] == (trips if with_plan else 0)
        if not with_plan:
            assert pt["kill"] == pt["deadline"] == 0


def test_phase_trips_on_a_batch():
    """Per shard: each phase fires on at most every trip; kill and
    deadline fire only with a plan that can fire them, and their bodies
    are counted as evaluated on every trip they are compiled in."""
    for p in (None, _plans(range(len(WLS)))):
        tel = []
        sim.run_batch(sim.MODE_ETF, WLS, PARAMS, plan=p, devices=1,
                      telemetry=tel)
        (rec,) = tel
        trips = rec["lane_trips"] // rec["lanes"]
        assert all(0 <= v <= trips for v in rec["phase_trips"].values())
        if p is None:
            assert rec["phase_trips"]["kill"] == 0
            assert rec["phase_trips"]["deadline"] == 0
            assert rec["fault_eval_trips"] == rec["fault_fire_trips"] == 0
        else:
            assert rec["fault_eval_trips"] == trips
            assert 0 < rec["fault_fire_trips"] <= trips


def test_fault_eval_trips_zero_where_capabilities_rule_bodies_out():
    """A healthy plan, and one that fails PEs at t=0 with no deadline,
    can never kill or drop: `plan_capabilities` leaves the bodies out,
    and nothing counts them."""
    dead0 = flt.fail_pes(flt.healthy_plan(), [0, 1], at=0.0)
    for p in (flt.healthy_plan(), dead0):
        assert flt.plan_capabilities(p)[1:] == (False, False)
        tel = []
        sim.run_batch(sim.MODE_ETF, WLS, PARAMS, plan=p, devices=1,
                      telemetry=tel)
        assert tel[0]["fault_eval_trips"] == 0
        assert tel[0]["fault_fire_trips"] == 0


# ---------------------------------------------------------------------------
# (c) the same spans in the profiler's trace
# ---------------------------------------------------------------------------
def test_spans_are_profiler_annotations(tmp_path):
    import jax
    from jax.profiler import ProfileData

    sim.run_batch(sim.MODE_LUT, WLS, PARAMS, devices=1)   # warm
    tel = []
    with jax.profiler.trace(str(tmp_path)):
        sim.run_batch(sim.MODE_LUT, WLS, PARAMS, devices=1, telemetry=tel)
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    host = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("repro."):
                        host.setdefault(e.name, []).append(e.duration_ns)
    spans = tel[0]["spans"]
    assert sorted(host) == sorted(f"repro.{sp['name']}" for sp in spans)
    for sp in spans:
        (dur,) = host[f"repro.{sp['name']}"]
        assert abs(dur - (sp["end_ns"] - sp["start_ns"])) <= 1e6, sp
