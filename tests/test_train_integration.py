"""Training integration: loss decreases, microbatch-accumulation equivalence,
checkpoint resume determinism, optimizer behaviours, compression round trip
under shard_map."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.data import make_batch_iterator
from repro.launch.train import train_loop
from repro.models import transformer as T
from repro.optim import make_optimizer
from repro.train import step as TS


def test_loss_decreases_short_run(tmp_path):
    cfg = get_arch("internlm2-1.8b").reduced()
    tc = TS.TrainConfig(lr=1e-3, warmup=5, total_steps=40)
    _, _, hist = train_loop(cfg, tc, steps=40, batch=4, seq_len=64,
                            log_every=5, log=lambda *_: None)
    assert hist[-1]["loss"] < hist[0]["loss"]


def test_grad_accum_equals_full_batch():
    """microbatches=2 must match microbatches=1 on the same global batch."""
    cfg = get_arch("mamba2-130m").reduced()
    inputs = {"tokens": jax.random.randint(jax.random.key(0), (4, 32), 0,
                                           cfg.vocab_size),
              "labels": jax.random.randint(jax.random.key(1), (4, 32), 0,
                                           cfg.vocab_size)}
    outs = {}
    for m in (1, 2):
        tc = TS.TrainConfig(microbatches=m)
        params, state = TS.init_train_state(jax.random.key(2), cfg, tc)
        step = jax.jit(TS.make_train_step(cfg, tc))
        p2, _, metrics = step(params, state, inputs)
        outs[m] = (p2, float(metrics["loss"]))
    assert abs(outs[1][1] - outs[2][1]) < 1e-4
    for a, b in zip(jax.tree.leaves(outs[1][0]),
                    jax.tree.leaves(outs[2][0])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-5, rtol=3e-4)


def test_checkpoint_resume_bit_exact(tmp_path):
    """Stop at step 10, resume to 20 == straight run to 20."""
    cfg = get_arch("mamba2-130m").reduced()
    tc = TS.TrainConfig(lr=1e-3, warmup=2, total_steps=20)
    d1 = str(tmp_path / "a")
    train_loop(cfg, tc, steps=10, batch=2, seq_len=32, ckpt_dir=d1,
               ckpt_every=10, log=lambda *_: None)
    p_resumed, _, _ = train_loop(cfg, tc, steps=20, batch=2, seq_len=32,
                                 ckpt_dir=d1, ckpt_every=10,
                                 log=lambda *_: None)
    p_straight, _, _ = train_loop(cfg, tc, steps=20, batch=2, seq_len=32,
                                  ckpt_dir=None, log=lambda *_: None)
    for a, b in zip(jax.tree.leaves(p_resumed),
                    jax.tree.leaves(p_straight)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=1e-6, rtol=1e-6)


def test_adamw_and_adafactor_reduce_loss():
    def quad_loss(p):
        return jnp.sum((p["w"] - 3.0) ** 2)

    for name in ("adamw", "adafactor"):
        opt = make_optimizer(name, lambda s: 0.1)
        params = {"w": jnp.zeros((4, 4))}
        state = opt.init(params)
        losses = []
        for step in range(50):
            g = jax.grad(quad_loss)(params)
            upd, state = opt.update(g, state, params, step)
            params = jax.tree.map(lambda p, u: p + u, params, upd)
            losses.append(float(quad_loss(params)))
        assert losses[-1] < losses[0] * 0.1, name


def test_adafactor_state_is_factored():
    opt = make_optimizer("adafactor", lambda s: 1e-3)
    params = {"w": jnp.zeros((16, 8)), "b": jnp.zeros((8,))}
    st = opt.init(params)
    assert st["v"]["w"]["vr"].shape == (16,)
    assert st["v"]["w"]["vc"].shape == (8,)
    assert st["v"]["b"]["v"].shape == (8,)


def test_compressed_psum_shard_map_single_device():
    """int8 psum under shard_map on a 1-element 'pod' axis: exact identity
    up to quantization error; error feedback captures the residual."""
    from repro.optim.compression import compressed_psum
    mesh = jax.make_mesh((1,), ("pod",))
    g = jnp.asarray(np.random.default_rng(0).standard_normal((8, 8)),
                    jnp.float32)

    def body(g):
        avg, err = compressed_psum(g, "pod", jnp.zeros_like(g))
        return avg, err

    from jax.sharding import PartitionSpec as P
    fn = jax.shard_map(body, mesh=mesh, in_specs=P(),
                       out_specs=(P(), P()), check_vma=False)
    avg, err = fn(g)
    scale = float(jnp.max(jnp.abs(g))) / 127.0
    assert float(jnp.max(jnp.abs(avg - g))) <= scale / 2 + 1e-6
    np.testing.assert_allclose(np.asarray(avg + err), np.asarray(g),
                               atol=1e-6)


def test_train_driver_cli(tmp_path):
    from repro.launch.train import main
    rc = main(["--arch", "mamba2-130m", "--reduced", "--steps", "4",
               "--batch", "2", "--seq", "32",
               "--ckpt-dir", str(tmp_path / "c")])
    assert rc == 0
    assert os.path.isdir(tmp_path / "c" / "step_4")
