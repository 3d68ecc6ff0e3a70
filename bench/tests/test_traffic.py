"""The request generator: deterministic per seed, no scenario repeated
across requests or seeds, fault plans inside the configured regime."""
import math

import numpy as np
import pytest

from bench import harness, traffic


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell("soc19_faults.etf_grid")


def key(lane):
    return (lane.mix, lane.rate_mbps, lane.frames, lane.seed)


def test_same_seed_same_requests(cell):
    a = traffic.request(cell.traffic, cell.cfg, 2**31 + 11, 3)
    b = traffic.request(cell.traffic, cell.cfg, 2**31 + 11, 3)
    assert [key(x) for x in a] == [key(x) for x in b]
    for x, y in zip(a, b):
        for f in x.plan:
            np.testing.assert_array_equal(x.plan[f], y.plan[f])


def test_full_grid_per_request():
    cell = harness.load_cell("soc19.etf_grid")
    lanes = traffic.request(cell.traffic, cell.cfg, 5, 0)
    assert len(lanes) == 40 * 14
    assert len({(l.mix, l.rate_mbps) for l in lanes}) == 40 * 14
    assert {l.frames for l in lanes} == {60}
    warm = traffic.request(cell.traffic, cell.cfg, 5, 0, warm=True)
    assert [(l.mix, l.rate_mbps) for l in warm] == \
        [(l.mix, l.rate_mbps) for l in lanes]
    assert {l.frames for l in warm} == {1}


def test_no_scenario_repeats_across_requests_and_seeds(cell):
    seen = set()
    n = 0
    for seed in (0, 1, 2**31 - 1, 2**31 + 3, 2**33 + 5):
        for k in range(6):
            for lane in traffic.request(cell.traffic, cell.cfg, seed, k):
                seen.add(key(lane))
                n += 1
    assert len(seen) == n


def test_quarter_grid_keeps_every_rate(cell):
    lanes = traffic.request(cell.traffic, cell.cfg, 5, 0)
    assert len(lanes) == 10 * 14
    assert len({l.rate_mbps for l in lanes}) == 14
    assert all(l.plan is not None for l in lanes)


def test_fault_plans_follow_the_regime(cell):
    reg = cell.cfg["faults"]
    for lane in traffic.request(cell.traffic, cell.cfg, 9, 1)[:100]:
        p = lane.plan
        failed = np.isfinite(p["fail_at"])
        assert failed.sum() == reg["permanent"]
        assert np.isfinite(p["repair_at"]).sum() == math.ceil(
            reg["permanent"] / 2)
        assert np.isfinite(p["transient_at"]).sum() == reg["transient"]
        times = np.concatenate([p["fail_at"][failed],
                                p["transient_at"][np.isfinite(
                                    p["transient_at"])]])
        assert ((times >= 0) & (times <= reg["horizon_us"])).all()
        assert p["max_retries"] == reg["max_retries"]
        assert p["deadline_us"] == reg["deadline_us"]
