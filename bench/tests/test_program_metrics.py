"""The metrics that read what the program records about itself: its host
spans and the sweep loop's phase counters, in the engine's telemetry
records. On hand-written records (as the program writes them, and as a
program that records neither writes them), then on the program itself
at a tiny size on the CPU."""
import copy
import json
import os

import pytest

from bench import devtrace, harness
from bench.tests.helpers import run_tiny
from bench.tests.test_trace_reduction import SMALL, run_of

SPAN_METRICS = {"stack_share": "run_batch.stack",
                "h2d_share": "run_batch.to_device",
                "dispatch_share": "run_batch.dispatch"}
NEW = list(SPAN_METRICS) + ["fault_phase_useful_share"]


def _span(name, start, end):
    parent = None if name == "run_batch" else "run_batch"
    return {"name": name, "parent": parent, "start_ns": start,
            "end_ns": end}


# a request of 400 ns (`bench.request` in SMALL); the program's clock has
# another origin than the profiler's, so only durations carry over
OLD_RECORD = {"lanes": 560, "devices": 1, "lane_trips": 560 * 4,
              "active_trips": 560 * 3, "events": 9000, "occupancy": 0.75}
NEW_RECORDS = [
    dict(OLD_RECORD,
         phase_trips={"completion": 4, "kill": 1, "deadline": 2,
                      "arrival": 1, "decide": 4, "advance": 3},
         fault_eval_trips=4, fault_fire_trips=2,
         spans=[_span("run_batch", 10_000, 10_300),
                _span("run_batch.stack", 10_000, 10_040),
                _span("run_batch.to_device", 10_040, 10_050),
                _span("run_batch.dispatch", 10_050, 10_060),
                _span("run_batch.fetch", 10_060, 10_300),
                _span("run_batch.fetch", 10_300, 10_300)]),
    # a second chunk: its own dispatch and fetch
    dict(OLD_RECORD,
         phase_trips={"completion": 4, "kill": 0, "deadline": 1,
                      "arrival": 1, "decide": 4, "advance": 2},
         fault_eval_trips=4, fault_fire_trips=1,
         spans=[_span("run_batch.dispatch", 10_055, 10_075),
                _span("run_batch.fetch", 10_300, 10_301)]),
]


def test_span_shares_over_the_traced_request():
    run = run_of(SMALL, telemetry=NEW_RECORDS)
    assert devtrace.span(SMALL, "bench.request") == (50, 450)
    assert harness.reader("stack_share")(run) == pytest.approx(10.0)
    assert harness.reader("h2d_share")(run) == pytest.approx(2.5)
    # both chunks' dispatches
    assert harness.reader("dispatch_share")(run) == pytest.approx(7.5)


def test_fault_phase_useful_share_sums_over_records():
    run = run_of(SMALL, telemetry=NEW_RECORDS)
    assert harness.reader("fault_phase_useful_share")(run) == \
        pytest.approx(100 * 3 / 8)


def test_missing_spans_and_counters_are_missing_not_zero():
    # an engine that records neither (records as older versions write them)
    old = run_of(SMALL, telemetry=[dict(OLD_RECORD)])
    for m in NEW:
        assert harness.reader(m)(old) is None
    # no telemetry at all, and no trace
    for m in NEW:
        assert harness.reader(m)(run_of(SMALL)) is None
    untraced = run_of(SMALL, telemetry=NEW_RECORDS)
    untraced.trace, untraced.trace_window = None, None
    for m in SPAN_METRICS:
        assert harness.reader(m)(untraced) is None
    # a span the records do not hold
    no_stack = copy.deepcopy(NEW_RECORDS)
    for rec in no_stack:
        rec["spans"] = [sp for sp in rec["spans"]
                        if sp["name"] != "run_batch.stack"]
    assert harness.reader("stack_share")(run_of(SMALL,
                                                telemetry=no_stack)) is None
    # fault bodies never evaluated (no fault plan): no share, not 0
    healthy = copy.deepcopy(NEW_RECORDS)
    for rec in healthy:
        rec["fault_eval_trips"] = rec["fault_fire_trips"] = 0
    assert harness.reader("fault_phase_useful_share")(
        run_of(SMALL, telemetry=healthy)) is None


@pytest.mark.parametrize("metric", ["lane_occupancy", "trip_us"])
def test_older_metrics_read_the_same_with_the_new_keys(metric):
    old = run_of(SMALL, telemetry=[dict(OLD_RECORD), dict(OLD_RECORD)])
    new = run_of(SMALL, telemetry=NEW_RECORDS)
    assert harness.reader(metric)(new) == harness.reader(metric)(old)
    assert harness.reader(metric)(new) is not None


def test_each_new_metric_is_declared_where_it_reads():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for m in NEW:
        assert per_layer[m]["moves"] == "events_per_s"
        assert os.path.exists(os.path.join(harness.HERE, "metrics",
                                           f"{m}.py"))
    assert per_layer["fault_phase_useful_share"]["workloads"] == [
        "soc19_faults.etf_grid"]


@pytest.mark.parametrize("name", ["soc19.etf_grid", "soc19_faults.etf_grid"])
def test_program_metrics_in_a_traced_run_on_cpu(name):
    res = run_tiny(name, trace=True)
    assert res["correct"] is True
    got = res["metrics"]
    for m in SPAN_METRICS:
        assert 0 < got[m]["value"] < 100, m
    spans = sum(got[m]["value"] for m in SPAN_METRICS)
    assert spans < 100
    if name.startswith("soc19_faults"):
        # at 6 frames a plan may fire nothing: a true 0, not a missing one
        assert 0 <= got["fault_phase_useful_share"]["value"] <= 100
    else:
        assert "fault_phase_useful_share" not in got
