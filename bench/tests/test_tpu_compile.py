"""Each cell's program, at the cell's real shapes, compiles for a
described TPU v5e: one chip, and the 2x2 mesh for the sharded cell.
Nothing runs. The topology is described inside a module fixture, never
at import (one process at a time may load the TPU library)."""
import numpy as np
import pytest

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from bench import harness, traffic
from bench.tests.helpers import pending_x4_cell
from bench.tests.test_rehearsal import CELLS
from repro.core import faults as flt, simulator as sim, workloads


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / topology in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(autouse=True)
def no_persistent_cache():
    # a described chip's compile can be written to the cache but not read
    # back without one
    jax.config.update("jax_enable_compilation_cache", False)


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype,
                                       sharding=sharding), tree)


@pytest.mark.parametrize("name", CELLS + ["soc19.das_grid_x4"])
def test_cell_program_compiles_for_v5e(name, topo):
    cell = (pending_x4_cell({}) if name == "soc19.das_grid_x4"
            else harness.load_cell(name))
    prog = harness.Program(cell, cell.chips)
    wls, plan = prog.build(traffic.request(cell.traffic, cell.cfg, 1, 0))
    stacked = workloads.stack_workloads(wls)
    assert stacked.task_type.shape == (len(traffic.grid_cells(cell.traffic)),
                                       60 * 20)
    fcaps = flt.plan_capabilities(plan) if plan is not None \
        else flt.NO_CAPS
    tree = prog.tree if prog.tree is not None else sim.always_fast_tree()
    if cell.chips == 1:
        one = SingleDeviceSharding(topo.devices[0])
        compiled = sim._simulate_batch.lower(
            prog.mode, _shapes(prog.params, one), _shapes(stacked, one),
            _shapes(tree, one), _shapes(np.float32(1e9), one),
            None if plan is None else _shapes(plan, one), None, None,
            None if plan is None else 0, None, "pallas", fcaps).compile()
    else:
        devs = tuple(topo.devices[:cell.chips])
        mesh = Mesh(np.array(devs), ("s",))
        rep = NamedSharding(mesh, PartitionSpec())
        lanes = NamedSharding(mesh, PartitionSpec("s"))
        fn = sim._sharded_batch_fn(prog.mode, None, None, None, False, devs,
                                   None, "pallas", fcaps)
        compiled = fn.lower(_shapes(prog.params, rep),
                            _shapes(stacked, lanes), _shapes(tree, rep),
                            _shapes(np.float32(1e9), rep), None).compile()
        assert "all-gather" not in compiled.as_text()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
