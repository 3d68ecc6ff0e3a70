"""Seconds and events JAX spends tracing, lowering and compiling.

Counts JAX's own `/jax/core/compile/*` duration events, so no program
counter that a later change could rename decides whether something
compiled inside the measured window. (Copied from `chip_smoke.py`.)
"""
from __future__ import annotations


class CompileClock:
    def __init__(self, jax):
        self.total = 0.0
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.total += duration
            self.events += 1
