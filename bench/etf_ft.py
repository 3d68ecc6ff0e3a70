"""The decision kernels of `kernels/etf_ft` as the device trace names
them, and the bytes each call needs.

Byte counts follow the algorithm on unpadded shapes, whatever implements
it: S lanes per device, R ready slots, P PEs, K successors and MP
predecessors per task (the largest in the configuration's task graphs);
4 bytes a value and 1 a mask. These kernels compare and add on float32
and do no matrix work, so bytes bound them: their roofline is the HBM
bandwidth.
"""
import os

from bench import devtrace

HERE = os.path.dirname(os.path.abspath(__file__))

# The two Pallas kernels' device events. An XLA op event is named by its
# HLO instruction text, and a kernel's custom call by the jitted function
# that wraps its `pallas_call` (`%vmap_jit_etf_ft_search_masked__.14 =
# ... custom-call(...)`); anchored at the start, so ops that merely take
# a kernel's result as an operand do not match.
PATTERNS = {"search": r"^%?[\w.]*etf_ft_search_masked[\w.]* = ",
            "push": r"^%?[\w.]*push_rows[\w.]* = "}


def shapes(cfg):
    plat = cfg["platform"]
    preds = [len(p) for app in cfg["apps"].values() for _, p, _ in app]
    succs = {}
    for name, app in cfg["apps"].items():
        for _, p, _ in app:
            for q in p:
                succs[(name, q)] = succs.get((name, q), 0) + 1
    return {"R": int(plat["ready_queue_slots"]),
            "P": sum(plat["pes_per_cluster"]),
            "MP": max(preds), "K": max(succs.values())}


def bytes_per_call(kind, lanes, cfg):
    d = shapes(cfg)
    R, P, K, MP = d["R"], d["P"], d["K"], d["MP"]
    if kind == "search":
        # avail, exec [R, P]; free [P]; now; slot mask [R]; with a fault
        # regime the live-PE mask [P]; out: finish time and flat index
        per = 4 * (2 * R * P + P + 1) + R + 8
        if cfg.get("faults") is not None:
            per += P
    else:
        # pred finish, NoC cost, pred cluster [K, MP]; pred mask [K, MP];
        # PE clusters [P]; bases [K]; out: rows [K, P]
        per = 4 * (3 * K * MP + P + K + K * P) + K * MP
    return per * lanes


def events(run):
    """{kind: (device ns, calls)} of the kernels in the traced request,
    over all devices; None without a device trace."""
    if run.trace is None or not run.trace["devices"]:
        return None
    return {kind: devtrace.matching(run.trace, pat)
            for kind, pat in PATTERNS.items()}
