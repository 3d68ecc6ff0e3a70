"""Backend-aware dispatch for the decision-path kernels.

The simulator's decision hot path (`_etf_choice` / `_etf_choice_degraded`
/ `_avail_rows` in `core/simulator.py`) routes through this module when
the `REPRO_SIM_KERNELS` knob is on. Dispatch rule:

  ``REPRO_SIM_KERNELS`` =
    * ``0`` / ``off``      -> simulator keeps its inline jnp path
    * ``1`` / ``auto`` (default) -> Pallas kernels native on TPU, the
      single fused XLA formulation (`ref.py`) everywhere else
    * ``pallas``           -> the native Pallas kernels; raises off-TPU
    * ``pallas-interpret`` -> the Pallas kernels through the interpreter
      on any backend (slow — CI correctness runs only)
    * ``xla``              -> the fused XLA formulation on any backend

The resolved mode is threaded into the jit'd simulator as a *static*
argument by `run` / `run_batch` / `simulate_batch`, so flipping the env
var between calls dispatches correctly instead of hitting a stale trace.

Every path honours the same tie-break contract: the FIRST global minimum
of the flattened masked [R, P] finish-time matrix wins (bit-exact vs the
inline `jnp.argmin` path, including the all-masked -> slot 0 / pe 0
case), and the push-time rows are bitwise identical to the inline
[K, MP, P] contribution max.

`DISPATCH_COUNT` tallies which backend each decision primitive traced
through (trace-time, mirroring `sim.TRACE_COUNT`); the tests and
`chip_smoke.py` read it to prove which path actually ran.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels.etf_ft import kernel, ref

#: trace-time tallies per (primitive, backend) — a jit cache hit adds
#: nothing, exactly like `sim.TRACE_COUNT`.
DISPATCH_COUNT = {
    "etf_xla": 0, "etf_pallas": 0, "etf_pallas_interpret": 0,
    "push_xla": 0, "push_pallas": 0, "push_pallas_interpret": 0,
    "etf_ft_ref_fallback": 0,
}

_OFF = ("0", "off", "no", "false")
_AUTO = ("1", "auto", "on", "yes", "true")


def kernel_mode(raw: str | None = None) -> str:
    """Resolve the `REPRO_SIM_KERNELS` knob to a dispatch mode:
    'off' | 'xla' | 'pallas' | 'pallas-interpret'.

    Idempotent: resolved modes pass through unchanged, so callers may
    hand either the raw knob value or an already-resolved mode. `xla`
    forces the fused XLA formulation even on TPU; `pallas-interpret`
    forces the Pallas kernels through the interpreter on any backend;
    `pallas` off a TPU is an error, never a silent downgrade.
    """
    if raw is None:
        raw = os.environ.get("REPRO_SIM_KERNELS", "1")
    raw = raw.strip().lower()
    if raw in _OFF:
        return "off"
    if raw in ("xla", "pallas-interpret"):
        return raw
    on_tpu = jax.default_backend() == "tpu"
    if raw == "pallas":
        if not on_tpu:
            raise ValueError(
                f"REPRO_SIM_KERNELS=pallas needs a TPU (backend is "
                f"{jax.default_backend()!r}); ask for 'pallas-interpret' "
                "to run the kernels through the interpreter")
        return "pallas"
    if raw in _AUTO:
        return "pallas" if on_tpu else "xla"
    raise ValueError(
        f"REPRO_SIM_KERNELS={raw!r}: expected one of "
        f"{_OFF + _AUTO + ('pallas', 'pallas-interpret', 'xla')}")


def etf_decide(avail, free, exec_t, now, slot_ok, pe_alive, *, mode):
    """Per-lane masked ETF search: avail/exec_t [R, P], free [P], now
    scalar, slot_ok [R] bool, pe_alive [P] bool or None (all alive).
    Returns (slot, pe, feasible) int32/int32/bool. Batches under vmap.
    """
    if mode == "xla":
        DISPATCH_COUNT["etf_xla"] += 1
        _, slot, pe, ok = ref.etf_ft_masked_reference(
            avail, free, exec_t, now, slot_ok, pe_alive)
    else:
        key = "etf_pallas" if mode == "pallas" else "etf_pallas_interpret"
        DISPATCH_COUNT[key] += 1
        alive = (jnp.ones(avail.shape[-1], bool) if pe_alive is None
                 else pe_alive)
        _, slot, pe, ok = kernel.etf_ft_search_masked(
            avail[None], free[None], exec_t[None], now[None],
            slot_ok[None], alive[None],
            interpret=(mode != "pallas"))
        slot, pe, ok = slot[0], pe[0], ok[0]
    return slot.astype(jnp.int32), pe.astype(jnp.int32), ok


def push_rows(pfin, cost, pcl, pv, pe_cluster, bases, n_clusters, *,
              mode):
    """Per-lane push-time availability rows: pfin/cost/pcl/pv [K, MP],
    pe_cluster [P], bases [K], n_clusters static. Returns [K, P].
    Batches under vmap."""
    if mode == "xla":
        DISPATCH_COUNT["push_xla"] += 1
        return ref.push_rows_reference(pfin, cost, pcl, pv, pe_cluster,
                                       bases, n_clusters)
    key = "push_pallas" if mode == "pallas" else "push_pallas_interpret"
    DISPATCH_COUNT[key] += 1
    out = kernel.push_rows(pfin[None], cost[None],
                           pcl[None], pv[None], pe_cluster, bases[None],
                           interpret=(mode != "pallas"))
    return out[0]


def interpret_batch_limit(r: int, p: int) -> int:
    """Largest batch the interpret-mode search kernel accepts before
    `etf_ft` falls back to the jnp reference, derived from the kernel's
    own block geometry (`kernel.MAX_INTERPRET_CELLS` over the [R, Pp]
    block) instead of a hard-coded batch count. Override the cell budget
    with `REPRO_ETF_FT_INTERPRET_CELLS`."""
    cells = kernel.MAX_INTERPRET_CELLS
    env = os.environ.get("REPRO_ETF_FT_INTERPRET_CELLS")
    if env is not None:
        cells = int(env)
    block = r * kernel._pad_lanes(p)
    return max(1, cells // block)


def etf_ft(avail, free, exec_t, now, *, interpret: bool = False):
    """Unmasked batched search (`kernel.etf_ft_search`). Interpret mode is
    asked for by name; above `interpret_batch_limit` it falls back to the
    jnp reference and tallies `etf_ft_ref_fallback`."""
    B, R, P = avail.shape
    if interpret and B > interpret_batch_limit(R, P):
        DISPATCH_COUNT["etf_ft_ref_fallback"] += 1
        return ref.etf_ft_reference(avail, free, exec_t, now)
    return kernel.etf_ft_search(avail, free, exec_t, now,
                                interpret=interpret)
