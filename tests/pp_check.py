"""Standalone PP-correctness check, run in a subprocess with a forced
2-device host (tests/test_pipeline.py drives it).

Compares the GPipe pipeline loss/step against the standard (non-PP)
train step on identical params and batch: the pipeline is just a
re-scheduling, so the loss must match to fp tolerance and one optimizer
step must produce the same parameters.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.models import transformer as T
from repro.train import step as TS
from repro.train.pipeline import (PipelineConfig, init_pp_state,
                                  make_pp_train_step)


def main():
    cfg = get_arch("internlm2-1.8b").reduced()
    cfg = dataclasses.replace(cfg, remat=False)
    tc = TS.TrainConfig(lr=1e-3, warmup=1, total_steps=10)
    pc = PipelineConfig(n_stages=2, microbatches=2, stage_axis="pod")
    mesh = jax.make_mesh((2,), ("pod",))
    rules = T.ShardRules(batch=(), model=None, fsdp=None,
                         moe_groups=1)

    key = jax.random.key(0)
    batch = {
        "tokens": jax.random.randint(jax.random.key(1), (4, 32), 0,
                                     cfg.vocab_size),
        "labels": jax.random.randint(jax.random.key(2), (4, 32), 0,
                                     cfg.vocab_size),
    }

    # --- reference: plain train step (no sharding, 1 device semantics) ---
    ref_params, ref_state = TS.init_train_state(key, cfg, tc)
    ref_step = jax.jit(TS.make_train_step(cfg, tc))
    ref_p2, _, ref_metrics = ref_step(ref_params, ref_state, batch)
    ref_loss = float(ref_metrics["loss"])

    # --- pipeline: same init, blocks reshaped to (S, L/S, ...) ---
    pp_params, pp_state = init_pp_state(key, cfg, tc, pc)
    with jax.set_mesh(mesh):
        pp_step = make_pp_train_step(cfg, tc, pc, rules, mesh)
        pp_p2, _, pp_metrics = pp_step(pp_params, pp_state, batch)
    pp_loss = float(pp_metrics["loss"])

    print(f"ref_loss={ref_loss:.6f} pp_loss={pp_loss:.6f}")
    assert abs(ref_loss - pp_loss) < 2e-4, (ref_loss, pp_loss)

    # parameters after one step must match (reshape blocks back)
    pp_blocks_flat = jax.tree.map(
        lambda x: np.asarray(x).reshape(-1, *x.shape[2:]),
        pp_p2["blocks"])
    ref_blocks = jax.tree.map(np.asarray, ref_p2["blocks"])
    flat_pp, _ = jax.tree.flatten(pp_blocks_flat)
    flat_ref, _ = jax.tree.flatten(ref_blocks)
    for a, b in zip(flat_pp, flat_ref):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-3)
    np.testing.assert_allclose(np.asarray(pp_p2["head"]),
                               np.asarray(ref_p2["head"]),
                               atol=5e-4, rtol=5e-3)
    print("PP == reference: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
