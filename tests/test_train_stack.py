"""Train stack: optimizer, checkpointing (atomic/async/elastic), trainer
fault tolerance, data pipeline, gradient compression."""
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.checkpoint import checkpoint as ckpt
from repro.data.pipeline import Prefetcher, SyntheticLM, TokenFileDataset
from repro.parallel import compression
from repro.train import optimizer as optim
from repro.train import trainer as tr

# `jax.make_mesh` defaults to Explicit axes; the trainer shards with Auto
AUTO2 = (jax.sharding.AxisType.Auto,) * 2


def test_adamw_decreases_quadratic():
    cfg = optim.AdamWConfig(lr_peak=0.1, warmup_steps=5, total_steps=100,
                            weight_decay=0.0)
    params = {"w": jnp.array([3.0, -2.0])}
    state = optim.adamw_init(params)
    for _ in range(100):
        grads = {"w": 2 * params["w"]}
        params, state, m = optim.adamw_update(cfg, grads, state, params)
    assert float(jnp.abs(params["w"]).max()) < 0.5


def test_lr_schedule_shape():
    cfg = optim.AdamWConfig(lr_peak=1e-3, lr_min=1e-5, warmup_steps=10,
                            total_steps=100)
    lrs = [float(optim.lr_at(cfg, s)) for s in [0, 5, 10, 50, 100]]
    assert lrs[0] == 0.0
    assert lrs[1] == pytest.approx(5e-4)
    assert lrs[2] == pytest.approx(1e-3)
    assert lrs[3] < lrs[2]
    assert lrs[4] == pytest.approx(1e-5, rel=1e-2)


def test_grad_clipping_applied():
    cfg = optim.AdamWConfig(clip_norm=1.0, lr_peak=1.0, warmup_steps=0,
                            total_steps=1, weight_decay=0.0)
    params = {"w": jnp.zeros(4)}
    state = optim.adamw_init(params)
    _, _, m = optim.adamw_update(cfg, {"w": jnp.full(4, 100.0)}, state,
                                 params)
    assert float(m["grad_norm"]) == pytest.approx(200.0)


def test_checkpoint_roundtrip_and_atomicity(tmp_path):
    tree = {"a": np.arange(6).reshape(2, 3).astype(np.float32),
            "b": [np.ones(4), np.zeros((2, 2))]}
    ckpt.save(str(tmp_path), tree, step=7, meta={"x": 1})
    out, step, meta = ckpt.restore(str(tmp_path), tree)
    assert step == 7 and meta == {"x": 1}
    np.testing.assert_array_equal(np.asarray(out["a"]), tree["a"])
    # LATEST points at a complete checkpoint even with a stale tmp dir
    os.makedirs(str(tmp_path / "step_00000009.tmp"), exist_ok=True)
    assert ckpt.latest_step(str(tmp_path)) == 7


def test_async_checkpointer(tmp_path):
    c = ckpt.AsyncCheckpointer(str(tmp_path))
    c.save_async({"w": jnp.ones(8)}, step=1)
    c.wait()
    assert ckpt.latest_step(str(tmp_path)) == 1


def test_checkpoint_prune(tmp_path):
    for s in [1, 2, 3, 4]:
        ckpt.save(str(tmp_path), {"w": np.zeros(2)}, step=s)
    ckpt.prune_old(str(tmp_path), keep=2)
    steps = sorted(int(d[5:]) for d in os.listdir(str(tmp_path))
                   if d.startswith("step_"))
    assert steps == [3, 4]


def test_trainer_failure_recovery(tmp_path):
    mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=AUTO2)
    cfg = configs.get_smoke_config("phi3-mini-3.8b", n_layers=2,
                                   d_model=64, vocab=128)
    tc = tr.TrainerConfig(total_steps=40, ckpt_every=10,
                          ckpt_dir=str(tmp_path), log_every=100)
    oc = optim.AdamWConfig(lr_peak=5e-3, warmup_steps=5, total_steps=40)
    data = SyntheticLM(vocab=128, batch=4, seq_len=32)
    t = tr.Trainer(tc, cfg, oc, mesh, data)
    t.inject_failure_at = 25
    out = t.fit()
    assert out["restarts"] == 1
    assert out["step"] == 40
    losses = [m["loss"] for m in out["metrics"]]
    assert losses[-1] < losses[0]


def test_trainer_resume_from_checkpoint(tmp_path):
    mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=AUTO2)
    cfg = configs.get_smoke_config("phi3-mini-3.8b", n_layers=2,
                                   d_model=64, vocab=128)
    oc = optim.AdamWConfig(lr_peak=5e-3, warmup_steps=5, total_steps=30)
    tc1 = tr.TrainerConfig(total_steps=20, ckpt_every=10,
                           ckpt_dir=str(tmp_path), log_every=100)
    tr.Trainer(tc1, cfg, oc, mesh,
               SyntheticLM(vocab=128, batch=4, seq_len=32)).fit()
    tc2 = tr.TrainerConfig(total_steps=30, ckpt_every=10,
                           ckpt_dir=str(tmp_path), log_every=100)
    out = tr.Trainer(tc2, cfg, oc, mesh,
                     SyntheticLM(vocab=128, batch=4, seq_len=32)).fit(
        resume=True)
    assert out["step"] == 30
    # resumed run performed only 10 new steps
    assert len(out["metrics"]) == 10


def test_das_gate_fast_slow():
    calls = []
    g = tr.DASGate(rate_thr=0.5, inflation_thr=2.0,
                   replan=lambda: calls.append(1))
    assert g.decide(0.1, 3.0) == "fast"
    assert g.decide(0.9, 1.0) == "fast"
    assert g.decide(0.9, 3.0) == "slow"
    assert len(calls) == 1


def test_synthetic_data_learnable_and_deterministic():
    d1 = SyntheticLM(vocab=64, batch=2, seq_len=16, seed=3)
    d2 = SyntheticLM(vocab=64, batch=2, seq_len=16, seed=3)
    b1, b2 = next(d1), next(d2)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    np.testing.assert_array_equal(b1["tokens"][:, 1:], b1["labels"][:, :-1])


def test_token_file_dataset(tmp_path):
    toks = np.arange(1000, dtype=np.int32)
    p = tmp_path / "shard0.bin"
    toks.tofile(str(p))
    ds = TokenFileDataset([str(p)], batch=2, seq_len=9)
    b = next(ds)
    assert b["tokens"].shape == (2, 9)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


def test_prefetcher_delivers_in_order():
    src = iter([{"x": np.array([i])} for i in range(5)])
    pf = Prefetcher(src, depth=2)
    got = [int(b["x"][0]) for b in pf]
    assert got == list(range(5))


def test_int8_compression_accuracy():
    g = {"w": jnp.linspace(-3, 3, 1000)}
    gq = compression.fake_requantize(g)
    err = float(jnp.max(jnp.abs(gq["w"] - g["w"])))
    assert err <= 3 / 127.0 + 1e-6


def test_compressed_psum_shard_map():
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    x = jnp.arange(8, dtype=jnp.float32)

    f = jax.shard_map(lambda v: compression.compressed_psum(v, "data"),
                  mesh=mesh, in_specs=P("data"), out_specs=P("data"))
    out = f(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x), atol=0.05)
