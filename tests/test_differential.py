"""Differential test: the jittable lax.while_loop simulator vs an
independently-written pure-Python reference (core/ref_sim.py) must agree on
per-task schedules for LUT / ETF / ETF-ideal across workloads and rates."""
import pytest

from repro.core import ref_sim, simulator as sim, workloads

SUITE = workloads.default_suite(n_instances=10)
PARAMS = sim.make_params()

CASES = [(mix, rate, mode)
         for mix in (0, 1, 4, 5)
         for rate in (0, 9, 13)
         for mode in (sim.MODE_LUT, sim.MODE_ETF, sim.MODE_ETF_IDEAL)]


@pytest.mark.parametrize("mix,rate,mode", CASES)
def test_jax_sim_matches_reference(mix, rate, mode):
    wl = SUITE.build(mix, rate)
    r_jax = sim.run(mode, wl, PARAMS)
    r_ref = ref_sim.simulate_ref(mode, wl)

    # fp32 sim vs fp64 reference within the tolerances `disagreements`
    # states (tight finish times for ~all tasks; exact ties broken
    # differently may move a handful of placements)
    assert ref_sim.disagreements(r_jax, r_ref, int(wl.n_tasks)) == []
