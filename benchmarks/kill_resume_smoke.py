"""Kill-and-resume smoke test for the campaign layer (CI, both jobs).

Proves the crash-safety claim end to end with a real SIGKILL:

  1. launch a child process running a checkpointed campaign, throttled
     (`chunk_delay_s`) so chunks land one at a time;
  2. SIGKILL the child once some — but not all — chunks are checkpointed;
  3. compute an uninterrupted reference sweep in-process (`sim.run_batch`);
  4. resume the campaign in-process and assert (a) completed chunks were
     reused, not recomputed, and (b) every `SimResult` field is
     byte-identical to the uninterrupted reference.

A device belongs to one process at a time, so the parent imports nothing
that touches JAX until the child is dead: until then it only watches the
checkpoint directory.

    PYTHONPATH=src python -m benchmarks.kill_resume_smoke [--dir DIR]

Exit status 0 on success. `--child DIR` is the internal child entry.
"""
from __future__ import annotations

import argparse
import glob
import os
import signal
import subprocess
import sys
import tempfile
import time

N_INSTANCES = 5
CELLS = [(mi, ri) for mi in range(4) for ri in (0, 5, 9, 13)]  # 16 scenarios
BATCH = 2                                                      # -> 8 chunks
CHUNK_DELAY_S = 0.6


def _workloads():
    from repro.core import workloads
    suite = workloads.default_suite(n_instances=N_INSTANCES)
    return [suite.build(mi, ri) for mi, ri in CELLS]


def child(cdir: str) -> None:
    """Run the campaign slowly so the parent can SIGKILL it mid-grid."""
    from repro.core import campaign as camp, simulator as sim
    camp.run_campaign(sim.MODE_LUT, _workloads(), batch_size=BATCH,
                      checkpoint_dir=cdir, chunk_delay_s=CHUNK_DELAY_S)


def _chunk_files(cdir: str):
    return glob.glob(os.path.join(cdir, "*", "chunk_*.npz"))


def main(cdir: str) -> None:
    n_chunks = -(-len(CELLS) // BATCH)
    print(f"# launching child campaign (throttled): {len(CELLS)} "
          f"scenarios, {n_chunks} chunks")
    proc = subprocess.Popen(
        [sys.executable, "-m", "benchmarks.kill_resume_smoke",
         "--child", cdir],
        env={**os.environ,
             "PYTHONPATH": os.pathsep.join(
                 p for p in ("src", os.environ.get("PYTHONPATH", "")) if p)})
    deadline = time.time() + 300
    try:
        while True:
            done = len(_chunk_files(cdir))
            if done >= 2:
                break
            if proc.poll() is not None:
                raise SystemExit(
                    f"child exited early (rc={proc.returncode}) with only "
                    f"{done} chunk(s) checkpointed — widen CHUNK_DELAY_S?")
            if time.time() > deadline:
                raise SystemExit("timed out waiting for the first chunks")
            time.sleep(0.05)
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait()
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()

    done = len(_chunk_files(cdir))
    print(f"# SIGKILLed child after {done}/{n_chunks} chunks")
    if done >= n_chunks:
        raise SystemExit("child finished before the kill — not a mid-grid "
                         "interruption; widen CHUNK_DELAY_S")

    # the child is dead: only now may this process touch a device
    import numpy as np

    from repro.core import campaign as camp, simulator as sim
    wls = _workloads()
    print("# reference sweep in-process...")
    ref = sim.run_batch(sim.MODE_LUT, wls, batch_size=BATCH)
    print("# resuming in-process...")
    out = camp.run_campaign(sim.MODE_LUT, wls, batch_size=BATCH,
                            checkpoint_dir=cdir)
    assert out.stats["chunks_reused"] >= done - 1, out.stats
    assert out.stats["chunks_reused"] < n_chunks, out.stats
    assert out.stats["chunks_computed"] + out.stats["chunks_reused"] \
        == n_chunks, out.stats
    for name in sim.SimResult._fields:
        a = np.asarray(getattr(ref, name))
        b = np.asarray(getattr(out.result, name))
        assert a.tobytes() == b.tobytes(), \
            f"field {name} differs after resume"
    print(f"# resume reused {out.stats['chunks_reused']} chunk(s), "
          f"recomputed {out.stats['chunks_computed']}; all "
          f"{len(sim.SimResult._fields)} result fields byte-identical "
          "to the uninterrupted sweep: PASS")


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dir", default=None,
                    help="campaign dir (default: a fresh temp dir)")
    ap.add_argument("--child", default=None, metavar="DIR",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        child(args.child)
    elif args.dir:
        main(args.dir)
    else:
        with tempfile.TemporaryDirectory(prefix="repro-campaign-") as d:
            main(d)
