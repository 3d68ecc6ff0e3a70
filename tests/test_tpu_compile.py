"""Compile the decision kernels and the batched engine for a TPU v5e.

The TPU compiler compiles for a described chip that is not attached, so
these tests catch Mosaic refusals (unaligned blocks, gathers, scalar
stores to VMEM) that interpret mode cannot see. Nothing runs; each test
only asserts that the compiled program contains the Pallas kernel
(`tpu_custom_call`).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every test worker
imports this file. All compile tests live in this one file so a single
worker owns the library.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import dfg, faults as flt, simulator as sim, soc, workloads
from repro.kernels.etf_ft import kernel as ek

S, R, P = 16, sim.R_MAX, soc.N_PES
N_INSTANCES = 60


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / topology in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip):
    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return spec


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


# the simulator's decision shape, and the batch `benchmarks/overhead.py`
# times
@pytest.mark.parametrize("s,r", [(S, R), (64, 64)])
def test_etf_ft_search_masked_compiles(one_chip, s, r):
    f = _spec(one_chip)
    compiled = ek.etf_ft_search_masked.lower(
        f((s, r, P)), f((s, P)), f((s, r, P)), f((s,)),
        f((s, r), jnp.bool_), f((s, P), jnp.bool_)).compile()
    assert _has_kernel(compiled)


def test_etf_ft_search_compiles(one_chip):
    # the unmasked batch `benchmarks/overhead.py` times
    f = _spec(one_chip)
    compiled = ek.etf_ft_search.lower(
        f((64, 64, P)), f((64, P)), f((64, 64, P)), f((64,))).compile()
    assert _has_kernel(compiled)


def test_push_rows_compiles(one_chip):
    f = _spec(one_chip)
    K, MP = dfg.MAX_SUCCS, dfg.MAX_PREDS
    compiled = ek.push_rows.lower(
        f((S, K, MP)), f((S, K, MP)), f((S, K, MP), jnp.int32),
        f((S, K, MP), jnp.bool_), f((P,), jnp.int32), f((S, K))).compile()
    assert _has_kernel(compiled)


def _stacked_workloads():
    """S scenarios of the default suite, stacked on the lane axis."""
    suite = workloads.default_suite(n_instances=N_INSTANCES)
    return suite.build_many([(mi, ri) for mi in range(4)
                             for ri in (0, 5, 9, 13)])


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype,
                                       sharding=sharding), tree)


@pytest.mark.parametrize("mode", [sim.MODE_ETF, sim.MODE_DAS])
def test_simulate_batch_compiles_with_pallas(one_chip, mode):
    stacked = _stacked_workloads()

    def shapes(tree):
        return _shapes(tree, one_chip)

    compiled = sim._simulate_batch.lower(
        mode, shapes(sim.make_params()), shapes(stacked),
        shapes(sim.always_fast_tree()), shapes(np.float32(1e9)), None,
        None, None, None, None, "pallas", flt.NO_CAPS).compile()
    assert _has_kernel(compiled)


def test_fault_phases_compile_without_lane_flattened_scatters(one_chip):
    """Under `vmap`, a gated scatter over a task row becomes one scatter
    over lanes x tasks flattened into a single axis, which the TPU walks
    update by update on every trip; a gather of a per-PE row for every
    task (`taus[pe_of]`) is walked the same way. The fault phases write
    task rows and per-PE rows densely and select each task's kill times
    from the PE rows, so the loop holds neither."""
    stacked = _stacked_workloads()
    plan = flt.stack_plans([flt.with_deadline(flt.random_plan(k), 22.0)
                            for k in range(S)])

    def shapes(tree):
        return _shapes(tree, one_chip)

    compiled = sim._simulate_batch.lower(
        sim.MODE_ETF, shapes(sim.make_params()), shapes(stacked),
        shapes(sim.always_fast_tree()), shapes(np.float32(1e9)),
        shapes(plan), None, None, 0, None, "pallas", flt.FULL_CAPS).compile()
    text = compiled.as_text()
    assert _has_kernel(compiled)
    T = stacked.task_type.shape[1]
    Tp = -(-T // sim.SEG) * sim.SEG
    flat = {S * T, S * Tp, S * P}
    sizes = [int(n) for n in re.findall(r"= \w+\[(\d+)\]\S* scatter\(", text)]
    assert not flat & set(sizes), sizes
    rows = re.findall(r"= \w+\[(\d+),(\d+),\d+\]\S* gather\(", text)
    assert (str(S), str(T)) not in rows, rows
