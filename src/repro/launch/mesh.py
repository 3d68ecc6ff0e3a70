"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state. The dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import;
smoke tests and benchmarks see the real single CPU device.
"""
from __future__ import annotations

import jax


def _auto(n: int) -> tuple:
    """Auto axis types: `jax.make_mesh` defaults to Explicit axes, which
    the trainer's sharding rules (`parallel/sharding.py`) do not use."""
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(shape)))


def make_local_mesh(data: int = 1, model: int = 1):
    """Mesh over whatever devices exist (tests / examples on CPU)."""
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, n // data)
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=_auto(2))


# TPU v5e hardware constants used by the roofline analysis
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link
CHIP_HBM_BYTES = 16e9           # v5e HBM capacity
