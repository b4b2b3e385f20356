"""Pipeline parallelism (GPipe) over the `pod` axis.

Beyond-paper parallelism mode for the multi-pod mesh: instead of data-
parallel pods, the two pods form a 2-stage pipeline — layers split
contiguously across stages, microbatches stream through, activations hop
stages over the DCN via ``lax.ppermute``. Inside each stage, the usual
TP(+FSDP) sharding applies on the (data, model) axes (shard_map is manual
over 'pod' only).

Schedule: GPipe with T = M + S − 1 ticks; stage s runs microbatch (t − s)
at tick t; the bubble fraction is (S−1)/T. Activations cross the DCN once
per stage boundary per microbatch — for deep models this is far less DCN
traffic than data-parallel gradient reduction (the §Perf comparison), which
is exactly why PP is the standard cross-DCN axis at 1000+ node scale.

Autodiff: the whole schedule is differentiable — ``ppermute`` transposes to
the reverse permutation, so the backward pass *is* the reverse pipeline.
Every stage holds the embedding/head replicas (they are small next to the
blocks) and masks their use by stage id; the loss is psum'd off the last
stage.

Restrictions (asserted): n_layers % n_stages == 0, global_batch %
microbatches == 0, arch uses the scan-block decoder (all ten do). MoE
aux-losses flow through like the main loss.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import transformer as T
from repro.models import layers as L
from repro.optim import clip_by_global_norm
from repro.train import step as TS


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    n_stages: int = 2
    microbatches: int = 4
    stage_axis: str = "pod"


def _stage_forward(blocks, x, cos, sin, cfg, rules):
    """Run this stage's contiguous slice of layers (scan)."""
    def body(h, lp):
        h, _ = T.block_forward(lp, h, cos, sin, cfg, impl="dense",
                               chunk=1024, rules=rules)
        return h, None

    body = jax.checkpoint(body,
                          policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = lax.scan(body, x, blocks)
    return x


def make_pp_loss_fn(cfg: ArchConfig, pc: PipelineConfig,
                    rules: Optional[T.ShardRules]):
    """Returns loss(params, batch) to be used under shard_map manual on the
    stage axis. ``params['blocks']`` leaves carry a leading stage dim of 1
    (this stage's slice); embed/head/ln_f are replicated across stages."""
    S = pc.n_stages
    M = pc.microbatches

    def loss_fn(params, batch):
        sid = lax.axis_index(pc.stage_axis)
        tokens, labels = batch["tokens"], batch["labels"]
        b, seq = tokens.shape[0], tokens.shape[1]
        assert b % M == 0, (b, M)
        mb = b // M
        tok_m = tokens.reshape(M, mb, seq)
        lab_m = labels.reshape(M, mb, seq)
        cos, sin = T._positions_cos_sin(cfg, batch, seq, T._rope_dim(cfg))
        blocks = jax.tree.map(lambda x: x[0], params["blocks"])

        def embed(tok):
            return T._embed_inputs(params, cfg, {"tokens": tok})

        d = cfg.d_model
        buf = jnp.zeros((mb, seq, d),
                        T._embed_inputs(params, cfg,
                                        {"tokens": tok_m[0]}).dtype)
        total_loss = jnp.zeros((), jnp.float32)
        total_tok = jnp.zeros((), jnp.float32)

        def tick(carry, t):
            buf, total_loss, total_tok = carry
            m = t - sid                           # microbatch at this stage
            active = (m >= 0) & (m < M)
            m_c = jnp.clip(m, 0, M - 1)
            # stage 0 sources from the embedding; others from the wire
            x_in = jnp.where(sid == 0, embed(tok_m[m_c]), buf)
            y = _stage_forward(blocks, x_in, cos, sin, cfg, rules)
            # last stage computes the loss for its finished microbatch
            h = L.rms_norm(y, params["ln_f"], cfg.norm_eps)
            logits = T._logits(params, cfg, h, rules)
            vp = cfg.padded_vocab_size
            lg = logits.astype(jnp.float32)
            if vp != cfg.vocab_size:
                lg = jnp.where(jnp.arange(vp) >= cfg.vocab_size, -1e30, lg)
            lse = jax.nn.logsumexp(lg, axis=-1)
            oh = jax.nn.one_hot(lab_m[m_c], vp, dtype=lg.dtype)
            gold = jnp.einsum("...v,...v->...", lg, oh)
            ce = (lse - gold).sum()
            is_last = sid == S - 1
            total_loss = total_loss + jnp.where(active & is_last, ce, 0.0)
            total_tok = total_tok + jnp.where(active & is_last,
                                              jnp.float32(mb * seq), 0.0)
            # ship activations to the next stage (ring; last->0 discarded)
            perm = [(i, (i + 1) % S) for i in range(S)]
            buf = lax.ppermute(y, pc.stage_axis, perm)
            return (buf, total_loss, total_tok), None

        (buf, total_loss, total_tok), _ = lax.scan(
            tick, (buf, total_loss, total_tok), jnp.arange(M + S - 1))
        # average over all tokens; psum so every stage returns the same
        loss = (lax.psum(total_loss, pc.stage_axis)
                / jnp.maximum(lax.psum(total_tok, pc.stage_axis), 1.0))
        return loss

    return loss_fn


def make_pp_train_step(cfg: ArchConfig, tc: TS.TrainConfig,
                       pc: PipelineConfig, rules, mesh):
    """Full PP train step: shard_map(manual over stage axis) around
    loss→grad→opt. Params: blocks sharded on the stage axis (leading layer
    dim), embed/head/ln_f replicated across stages (their grads psum'd)."""
    assert cfg.n_layers % pc.n_stages == 0
    opt = TS._opt(cfg, tc)
    inner_rules = dataclasses.replace(
        rules, batch=tuple(a for a in rules.batch if a != pc.stage_axis))
    loss_fn = make_pp_loss_fn(cfg, pc, inner_rules)

    def body(params, state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        # replicated leaves (embed/head/ln_f) accumulate grads on every
        # stage: psum them; block grads are stage-local.
        grads = {k: (v if k == "blocks"
                     else jax.tree.map(
                         lambda g: lax.psum(g, pc.stage_axis), v))
                 for k, v in grads.items()}
        grads, gnorm = clip_by_global_norm(grads, tc.grad_clip)
        updates, new_opt = opt.update(grads, state["opt"], params,
                                      state["step"])
        new_params = jax.tree.map(lambda p, u: (p + u).astype(p.dtype),
                                  params, updates)
        new_state = {"opt": new_opt, "step": state["step"] + 1}
        return new_params, new_state, {"loss": loss, "grad_norm": gnorm}

    def spec_of(tree, stage_spec):
        return jax.tree.map(lambda _: stage_spec, tree)

    def make_specs(params_like):
        pspec = {k: (spec_of(v, P(pc.stage_axis))
                     if k == "blocks" else spec_of(v, P()))
                 for k, v in params_like.items()}
        return pspec

    def step_fn(params, state, batch):
        pspec = make_specs(params)
        # opt state mirrors params: anything under 'blocks' stage-sharded
        sspec = {"opt": _opt_specs(state["opt"], pc), "step": P()}
        bspec = {k: P() for k in batch}
        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(pspec, sspec, bspec),
            out_specs=(pspec, sspec, {"loss": P(), "grad_norm": P()}),
            axis_names={pc.stage_axis}, check_vma=False)
        return fn(params, state, batch)

    return step_fn


def _opt_specs(opt_state, pc: PipelineConfig):
    """Optimizer state mirrors param structure: anything under a 'blocks'
    key is stage-sharded, the rest replicated."""
    def rec(tree, under_blocks=False):
        if isinstance(tree, dict):
            return {k: rec(v, under_blocks or k == "blocks")
                    for k, v in tree.items()}
        return P(pc.stage_axis) if under_blocks else P()
    return rec(opt_state)


def init_pp_state(key, cfg: ArchConfig, tc: TS.TrainConfig,
                  pc: PipelineConfig, dtype=jnp.float32):
    """Host-side init: standard params with blocks reshaped to a leading
    (n_stages, L/S) stage dim so the stage axis shards cleanly."""
    params = T.init_params(key, cfg, dtype)
    S = pc.n_stages
    params["blocks"] = jax.tree.map(
        lambda x: x.reshape(S, cfg.n_layers // S, *x.shape[1:]),
        params["blocks"])
    opt = TS._opt(cfg, tc)
    state = {"opt": opt.init(params), "step": jnp.zeros((), jnp.int32)}
    return params, state
