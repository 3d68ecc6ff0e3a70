"""etf_ft_roofline (%, layer: decision kernels): the least time the two
`etf_ft` kernels' calls in the traced request need — the bytes each
call needs (`bench/etf_ft.py`) at the chip's peak HBM bandwidth
(`bench/peaks.json`) — over the device time they took. Missing when no
kernel event matches."""
import json
import os

from bench import etf_ft


def read(run):
    ev = etf_ft.events(run)
    if not ev or not any(calls for _, calls in ev.values()):
        return None
    with open(os.path.join(etf_ft.HERE, "peaks.json")) as f:
        peaks = json.load(f)
    if run.device_kind not in peaks:
        raise KeyError(f"no peaks for device kind {run.device_kind!r} in "
                       "bench/peaks.json")
    bw = peaks[run.device_kind]["hbm_bytes_per_s"]
    lanes = run.lanes // run.devices
    need_s = sum(calls * etf_ft.bytes_per_call(kind, lanes, run.cfg) / bw
                 for kind, (_, calls) in ev.items())
    took_s = sum(ns for ns, _ in ev.values()) / 1e9
    return 100.0 * need_s / took_s
