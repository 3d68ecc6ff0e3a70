"""Reduction of a profiler trace to device busy time, idle gaps and op
time by name.

A traced request on the chip records millions of device-op events (the
sweep's while loop runs thousands of trips of hundreds of ops), so the
trace is reduced as it is read: for each device plane
(`/device:TPU:<n>`) the union of its XLA op intervals and each op name's
total time and count, and from the host planes the benchmark's own spans
(`TraceAnnotation`s named `bench.*`). Everything below works on that
reduced form, so a small trace written by hand exercises the same code
as a trace from the chip.
"""
from __future__ import annotations

import glob
import os
import re
from array import array
from typing import Dict, Iterable, List, Sequence, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
SPAN_PREFIX = "bench."

Event = Tuple[str, int, int]        # (name, start_ns, duration_ns)


def reduce_events(events: Iterable[Event]) -> dict:
    """{"busy": [(start, end)] merged, "ops": {name: [ns, count]}}."""
    ops: Dict[str, List[int]] = {}
    starts, ends = array("q"), array("q")
    for name, s, d in events:
        acc = ops.get(name)
        if acc is None:
            ops[name] = [d, 1]
        else:
            acc[0] += d
            acc[1] += 1
        starts.append(s)
        ends.append(s + d)
    return {"busy": union_arrays(np.frombuffer(starts, np.int64),
                                 np.frombuffer(ends, np.int64)),
            "ops": ops}


def union_arrays(starts: np.ndarray, ends: np.ndarray) -> list:
    """Merged, sorted, non-overlapping intervals of [starts, ends)."""
    if starts.size == 0:
        return []
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], np.maximum.accumulate(ends[order])
    new = np.concatenate([[True], s[1:] > e[:-1]])
    first = np.flatnonzero(new)
    last = np.concatenate([first[1:] - 1, [s.size - 1]])
    return list(zip(s[first].tolist(), e[last].tolist()))


def read(profile_dir: str) -> dict:
    """{"devices": {plane: reduced}, "host": [Event]} from the newest
    `.xplane.pb` under `profile_dir`."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    pd = ProfileData.from_file(paths[-1])
    devices: Dict[str, dict] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OP_LINE:
                    devices[plane.name] = reduce_events(
                        (e.name, int(e.start_ns), int(e.duration_ns))
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns), int(e.duration_ns))
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "host": host}


def span(trace: dict, name: str) -> Tuple[int, int]:
    """(start_ns, end_ns) of the one host span `name`."""
    hits = [(s, s + d) for n, s, d in trace["host"] if n == name]
    if len(hits) != 1:
        raise ValueError(f"expected one host span {name!r}, found "
                         f"{len(hits)}")
    return hits[0]


def clip(intervals: Sequence[Tuple[int, int]],
         window: Tuple[int, int]) -> List[Tuple[int, int]]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy(device: dict, window: Tuple[int, int]) -> List[Tuple[int, int]]:
    """The union of the device's op intervals inside `window`."""
    return clip(device["busy"], window)


def idle_gaps(busy_union: Sequence[Tuple[int, int]],
              window: Tuple[int, int]) -> List[Tuple[int, int]]:
    """The intervals of `window` that no device op covers."""
    gaps, t = [], window[0]
    for s, e in busy_union:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < window[1]:
        gaps.append((t, window[1]))
    return gaps


def total(intervals: Iterable[Tuple[int, int]]) -> int:
    return sum(e - s for s, e in intervals)


def matching(trace: dict, pattern: str) -> Tuple[int, int]:
    """(device ns, calls) of the ops whose name matches the regular
    expression, summed over devices."""
    rx = re.compile(pattern)
    ns = calls = 0
    for dev in trace["devices"].values():
        for name, (d, c) in dev["ops"].items():
            if rx.search(name):
                ns += d
                calls += c
    return ns, calls


def attribute(gap: Tuple[int, int], host: Sequence[Event]) -> str:
    """The `bench.*` host span inside the request that overlaps the gap
    most, without its prefix; "other" where none does."""
    best, best_ov = "other", 0
    for name, s, d in host:
        if name == SPAN_PREFIX + "request":
            continue
        ov = min(gap[1], s + d) - max(gap[0], s)
        if ov > best_ov:
            best, best_ov = name[len(SPAN_PREFIX):], ov
    return best


def top_ops(trace: dict, n: int = 10) -> List[list]:
    """[[op name, device seconds]] of the n ops that took most time,
    summed over devices (ops nest: a while loop's time holds its body's)."""
    acc: Dict[str, int] = {}
    for dev in trace["devices"].values():
        for name, (d, _) in dev["ops"].items():
            acc[name] = acc.get(name, 0) + d
    return [[k[:120], v / 1e9] for k, v in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def longest_gaps(trace: dict, window: Tuple[int, int],
                 n: int = 10) -> List[list]:
    """[[host span, seconds]] of the n longest device idle gaps (over
    all devices), each named by what the host was doing in it."""
    gaps = []
    for dev in trace["devices"].values():
        gaps += idle_gaps(busy(dev, window), window)
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[attribute(g, trace["host"]), (g[1] - g[0]) / 1e9]
            for g in gaps[:n]]
