"""What the harness hands the program of a configuration, and what it
refuses at load: a value goes by keyword where the program takes it, must
otherwise equal the program's own, and a configuration of the three
measured cells reaches the program by exactly the calls it always made."""
import copy
import re

import numpy as np
import pytest

from bench import harness
from bench.tests.helpers import fanout_config, run_tiny
from repro.core import dfg, simulator as sim, soc, workloads

CELLS = ["soc19.etf_grid", "soc19_faults.etf_grid", "soc19.lut_grid"]
MODS = {"dfg": dfg, "soc": soc, "simulator": sim, "workloads": workloads}


@pytest.fixture
def calls(monkeypatch):
    """The program's three entry points with the signatures they have
    without `apps` and `ready_slots`: an unknown keyword is a TypeError.
    Returns the names of the calls made."""
    seen = []
    bw, ds, rb = (workloads.build_workload, workloads.default_suite,
                  sim.run_batch)

    def build_workload(mix, rate_mbps, n_instances, seed, t_max=None,
                       i_max=None):
        seen.append("build_workload")
        return bw(mix, rate_mbps, n_instances, seed, t_max=t_max,
                  i_max=i_max)

    def default_suite(n_instances=40):
        seen.append("default_suite")
        return ds(n_instances=n_instances)

    def run_batch(mode, wls, params=None, tree=None, rate_threshold=1e9,
                  batch_size=None, plan=None, devices=None,
                  step_budget=None, kernels=None, telemetry=None):
        seen.append("run_batch")
        return rb(mode, wls, params, tree=tree,
                  rate_threshold=rate_threshold, batch_size=batch_size,
                  plan=plan, devices=devices, step_budget=step_budget,
                  kernels=kernels, telemetry=telemetry)

    monkeypatch.setattr(workloads, "build_workload", build_workload)
    monkeypatch.setattr(workloads, "default_suite", default_suite)
    monkeypatch.setattr(sim, "run_batch", run_batch)
    return seen


@pytest.mark.parametrize("name", CELLS)
def test_measured_cells_call_the_program_as_before(name, calls):
    """soc19's graphs and queue are the program's own: no keyword goes
    beyond the ones it always had, and the run is correct."""
    cell = harness.load_cell(name)
    assert harness.handover(cell.cfg, MODS) == {
        "suite": {}, "build": {}, "run": {}}
    res = run_tiny(name)
    assert res["correct"] is True and res["failed"] == 0
    assert set(calls) == {"build_workload", "default_suite", "run_batch"}


def test_soc19_graphs_are_the_programs():
    cfg = harness.load_cell("soc19.etf_grid").cfg
    apps = harness.app_graphs(dfg, cfg)
    assert harness._same_graphs(apps, list(dfg.APPS.values()))
    cfg = copy.deepcopy(cfg)
    cfg["apps"]["wifi_rx"][3][2] = 16.0           # one payload differs
    assert not harness._same_graphs(harness.app_graphs(dfg, cfg),
                                    list(dfg.APPS.values()))


def test_graphs_and_queue_the_program_cannot_take_fail_at_load(calls):
    cell = harness.load_cell("soc19.etf_grid")
    cell.cfg = fanout_config()
    with pytest.raises(harness.Failure) as e:
        harness.Program(cell, 1)
    assert "apps:" in str(e.value)
    assert "platform.ready_queue_slots: 32" in str(e.value)
    assert calls == []                             # nothing ran


def _bump(cfg, key):
    group, leaf = key.split(".")
    v = cfg[group][leaf]
    cfg[group][leaf] = ([x * 1.5 for x in v] if isinstance(v, list)
                        else v * 2)


@pytest.mark.parametrize("key", sorted(harness.CONSTANTS))
def test_value_only_the_reference_reads_fails_at_load(key):
    """A configuration that states another value than the program's
    constant stops before set-up, naming the key."""
    cell = harness.load_cell("soc19.etf_grid")
    cell.cfg = copy.deepcopy(cell.cfg)
    _bump(cell.cfg, key)
    with pytest.raises(harness.Failure, match=re.escape(key)):
        harness.Program(cell, 1)


def test_grid_of_another_width_fails_at_load(monkeypatch):
    load = harness.traffic_mod.load

    def six_wide(name):
        t = load(name)
        t["mixes"] = np.concatenate(
            [t["mixes"], np.zeros((len(t["mixes"]), 1), np.float32)], 1)
        return t

    monkeypatch.setattr(harness.traffic_mod, "load", six_wide)
    with pytest.raises(harness.Failure, match="6 fractions.*5 apps"):
        harness.load_cell("soc19.etf_grid")
