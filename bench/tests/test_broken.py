"""`correct` comes out false when the timed path is broken underneath:
the control (the reference in bfloat16 put in the program's place), and
each fault the cells can have — a step that returns its state unchanged,
half of the batch left out with the mean taken over the rest, the
exchange between chips left out, and an answer (the events a step
retires) altered where it is produced."""
import jax
import numpy as np
import pytest

from bench import harness
from bench.tests.helpers import pending_x4_cell, run_tiny, tiny_cell
from bench.tests.test_rehearsal import CELLS
from repro.core import simulator as sim


@pytest.fixture
def fresh_programs():
    """Drop compiled programs before and after, so a patched engine is
    traced anew and the next test gets the real one back."""
    jax.clear_caches()
    sim._sharded_batch_fn.cache_clear()
    yield
    jax.clear_caches()
    sim._sharded_batch_fn.cache_clear()


def _fails(res):
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_control_bfloat16_reference_fails(name, monkeypatch):
    cell = tiny_cell(name)

    def control(jax_, prog, lanes, telemetry=None):
        refs = [harness.reference(cell, l, "bfloat16") for l in lanes]
        return {"avg_exec_us": np.array([r["avg_exec_us"] for r in refs]),
                "total_energy_uj": np.array([r["total_energy_uj"]
                                             for r in refs]),
                "n_iters": np.array([r["events"] for r in refs]),
                "n_slow": np.array([r["n_slow"] for r in refs]),
                "n_done": np.array([r["n_done"] for r in refs]),
                "stall_reason": np.zeros(len(refs), int),
                "ready_drop": np.array([r["ready_drop"] for r in refs])}

    monkeypatch.setattr(harness, "request", control)
    _fails(run_tiny(name, cell=cell))


@pytest.mark.parametrize("name", CELLS)
def test_step_returning_its_state_unchanged_fails(name, monkeypatch,
                                                  fresh_programs):
    def frozen(*args, **kw):
        s, ev = orig(*args, **kw)
        return args[2], jax.numpy.ones_like(ev)

    orig = sim._masked_step
    monkeypatch.setattr(sim, "_masked_step", frozen)
    _fails(run_tiny(name))


def _halves(res, n, fill):
    """A full-width result whose lanes from `n` on are `fill(lanes[:n])`."""
    res = jax.device_get(res)
    out = []
    for x in res:
        x = np.asarray(x)
        out.append(np.concatenate([x[:n], fill(x[:n])]))
    return sim.SimResult(*out)


@pytest.mark.parametrize("name", CELLS)
def test_half_of_the_batch_left_out_fails(name, monkeypatch):
    orig = sim.run_batch

    def half(mode, wls, params=None, plan=None, batch_size=None, **kw):
        n = len(wls) // 2
        if plan is not None:
            plan = type(plan)(*[np.asarray(f)[:n] for f in plan])
        res = orig(mode, wls[:n], params, plan=plan, batch_size=n, **kw)

        def mean(x):
            m = x.astype(np.float64).mean(axis=0, keepdims=True)
            return np.repeat(m.astype(x.dtype), len(wls) - n, axis=0)

        return _halves(res, n, mean)

    monkeypatch.setattr(sim, "run_batch", half)
    _fails(run_tiny(name))


def test_exchange_between_chips_left_out_fails(monkeypatch):
    orig = sim.run_batch

    def no_exchange(mode, wls, params=None, devices=None, **kw):
        res = orig(mode, wls, params, devices=devices, **kw)
        shard = len(wls) // devices
        # only the first chip's lanes reach the host; every other
        # chip's block repeats them
        return _halves(res, shard, lambda x: np.concatenate(
            [x] * (devices - 1)))

    monkeypatch.setattr(sim, "run_batch", no_exchange)
    _fails(run_tiny(None, cell=pending_x4_cell()))


@pytest.mark.parametrize("name", CELLS)
def test_events_altered_where_produced_fails(name, monkeypatch,
                                             fresh_programs):
    def doubled(*args, **kw):
        s, ev = orig(*args, **kw)
        return s, 2 * ev

    orig = sim._masked_step
    monkeypatch.setattr(sim, "_masked_step", doubled)
    _fails(run_tiny(name))


def test_compile_inside_the_window_fails_the_run(monkeypatch):
    real = harness.request
    calls = []

    def compiling(jax_, prog, lanes, telemetry=None):
        calls.append(1)
        if len(calls) == 2:     # the window's first request
            jax.jit(lambda x: x * 3 + len(calls))(np.ones(7))
        return real(jax_, prog, lanes, telemetry)

    monkeypatch.setattr(harness, "request", compiling)
    with pytest.raises(harness.Failure, match="inside the window"):
        run_tiny("soc19.etf_grid")
