"""End-to-end training driver: train a reduced-config model for a few
hundred steps on CPU with checkpointing, failure injection and resume —
the full production loop at toy scale.

    PYTHONPATH=src python examples/train_tiny_lm.py [--arch yi-34b]
        [--steps 300] [--compress] [--fail-at 150]
"""
import argparse
import shutil

import jax

from repro import configs
from repro.data.pipeline import Prefetcher, SyntheticLM
from repro.train import optimizer as optim
from repro.train import trainer as tr

ap = argparse.ArgumentParser()
ap.add_argument("--arch", default="yi-34b", choices=configs.ARCH_IDS)
ap.add_argument("--steps", type=int, default=300)
ap.add_argument("--compress", action="store_true")
ap.add_argument("--fail-at", type=int, default=None)
ap.add_argument("--ckpt-dir", default="/tmp/repro_example_train")
ap.add_argument("--fresh", action="store_true")
args = ap.parse_args()

if args.fresh:
    shutil.rmtree(args.ckpt_dir, ignore_errors=True)

cfg = configs.get_smoke_config(args.arch, n_layers=4, d_model=128,
                               vocab=512)
mesh = jax.make_mesh((1, 1), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
data = Prefetcher(SyntheticLM(vocab=cfg.vocab, batch=8, seq_len=128,
                              n_codebooks=cfg.n_codebooks))
tcfg = tr.TrainerConfig(
    total_steps=args.steps, ckpt_every=max(args.steps // 5, 10),
    ckpt_dir=args.ckpt_dir, log_every=25,
    grad_compression="int8" if args.compress else None)
ocfg = optim.AdamWConfig(lr_peak=3e-3, warmup_steps=args.steps // 10,
                         total_steps=args.steps)

t = tr.Trainer(tcfg, cfg, ocfg, mesh, data)
if args.fail_at:
    t.inject_failure_at = args.fail_at
out = t.fit(resume=True)
print(f"\nfinished: step {out['step']}, restarts {out['restarts']}, "
      f"loss {out['metrics'][0]['loss']:.3f} -> "
      f"{out['metrics'][-1]['loss']:.3f}")
data.close()
