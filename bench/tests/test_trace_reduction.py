"""The reduction from a trace to device busy time, idle gaps, kernel
time and the per-layer metrics, on a small trace whose op names are as
a TPU v5e records them (an op event is named by its HLO instruction)."""
import types

import pytest

from bench import devtrace, etf_ft, harness

SEARCH = ("%vmap_jit_etf_ft_search_masked__.14 = (f32[560,1,1,128]"
          "{3,2,1,0:T(1,128)}, s32[560,1,1,128]) custom-call(f32[560,1,16,"
          "128] %fusion.1)")
PUSH = ("%vmap_jit_push_rows__.10 = f32[560,1,4,128]{3,2,1,0:T(4,128)S(1)} "
        "custom-call(f32[560,1,4,4] %fusion.2)")
# takes the search kernel's result as an operand: not a kernel event
SLICE = ("%slice.554 = s32[560]{0:T(1024)} slice(s32[560,1,1,128] "
         "%vmap_jit_etf_ft_search_masked__.14)")

EVENTS = [
    ("%fusion.1 = f32[560,128] fusion(f32[560,128] %p)", 100, 50),
    ("%fusion.2 = f32[560,4] fusion(f32[560,4] %q)", 140, 30),  # overlaps
    (SEARCH, 200, 20),
    (SLICE, 220, 2),
    (PUSH, 230, 10),
    ("%fusion.3 = s8[560,120] fusion(s8[560,120] %r)", 300, 100),
    ("%fusion.4 = f32[560] fusion(f32[560] %s)", 460, 10),  # after the request
]
HOST = [("bench.request", 50, 400), ("bench.build", 50, 90),
        ("bench.run_batch", 140, 5), ("bench.fetch", 145, 305)]
SMALL = {"devices": {"/device:TPU:0": devtrace.reduce_events(EVENTS)},
         "host": HOST}


def run_of(trace, telemetry=None, lanes=560, devices=1,
           cell="soc19.etf_grid"):
    c = harness.load_cell(cell)
    return types.SimpleNamespace(
        cfg=c.cfg, trace=trace,
        trace_window=devtrace.span(trace, "bench.request"),
        telemetry=telemetry or [], lanes=lanes, devices=devices,
        device_kind="TPU v5 lite")


def test_busy_union_and_idle_gaps():
    w = devtrace.span(SMALL, "bench.request")
    assert w == (50, 450)
    dev = SMALL["devices"]["/device:TPU:0"]
    assert dev["busy"] == [(100, 170), (200, 222), (230, 240), (300, 400),
                           (460, 470)]
    busy = devtrace.busy(dev, w)
    assert busy == [(100, 170), (200, 222), (230, 240), (300, 400)]
    assert devtrace.total(busy) == 202
    assert devtrace.idle_gaps(busy, w) == [(50, 100), (170, 200), (222, 230),
                                           (240, 300), (400, 450)]


def test_union_of_unsorted_nested_intervals():
    import numpy as np
    got = devtrace.union_arrays(np.array([50, 0, 10, 60, 200]),
                                np.array([55, 100, 20, 150, 210]))
    assert got == [(0, 150), (200, 210)]


def test_idle_gaps_named_by_host_span():
    gaps = devtrace.longest_gaps(SMALL, (50, 450), n=3)
    assert gaps == [["fetch", 60e-9], ["build", 50e-9], ["fetch", 50e-9]]


def test_top_ops_by_time():
    ops = devtrace.top_ops(SMALL, n=2)
    assert [o[0] for o in ops] == [EVENTS[5][0][:120], EVENTS[0][0][:120]]
    assert ops[0][1] == pytest.approx(100e-9)


def test_kernel_matching():
    assert devtrace.matching(SMALL, etf_ft.PATTERNS["search"]) == (20, 1)
    assert devtrace.matching(SMALL, etf_ft.PATTERNS["push"]) == (10, 1)


def test_layer_metrics():
    run = run_of(SMALL, telemetry=[{"lanes": 560, "lane_trips": 560 * 4,
                                    "active_trips": 560 * 3}])
    assert harness.reader("device_idle_share")(run) == pytest.approx(49.5)
    assert harness.reader("etf_ft_share")(run) == pytest.approx(
        100 * 30 / 202)
    assert harness.reader("build_share")(run) == pytest.approx(22.5)
    assert harness.reader("lane_occupancy")(run) == pytest.approx(75.0)
    # 202 ns busy over 4 trips
    assert harness.reader("trip_us")(run) == pytest.approx(0.0505)
    need = (etf_ft.bytes_per_call("search", 560, run.cfg)
            + etf_ft.bytes_per_call("push", 560, run.cfg)) / 819e9
    assert harness.reader("etf_ft_roofline")(run) == pytest.approx(
        100 * need / 30e-9)


def test_missing_kernel_is_missing_not_zero():
    trace = {"devices": {"/device:TPU:0": devtrace.reduce_events(
        [EVENTS[0], EVENTS[3]])}, "host": HOST}
    run = run_of(trace)
    assert harness.reader("etf_ft_share")(run) is None
    assert harness.reader("etf_ft_roofline")(run) is None
    assert harness.reader("device_idle_share")(run) == pytest.approx(87.0)


def test_no_device_plane_reads_nothing():
    run = run_of({"devices": {}, "host": HOST})
    for m in ("device_idle_share", "trip_us", "etf_ft_share",
              "etf_ft_roofline"):
        assert harness.reader(m)(run) is None


def test_unknown_device_kind_is_an_error():
    run = run_of(SMALL)
    run.device_kind = "TPU v9"
    with pytest.raises(KeyError):
        harness.reader("etf_ft_roofline")(run)
