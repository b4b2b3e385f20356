"""Train-step factory: loss → grad → (accumulate) → clip → optimizer.

Supports:
* gradient accumulation over microbatches (``lax.scan`` — XLA overlaps the
  next microbatch's compute with the previous collective),
* remat (inherited from the model's scan-over-layers checkpoint policy),
* optional int8 cross-pod gradient compression with error feedback
  (``grad_compression='int8_pod'``; runs the grad path under shard_map
  manual on the 'pod' axis, auto elsewhere),
* AdamW / Adafactor per arch config.
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
from repro.optim import clip_by_global_norm, cosine_schedule, make_optimizer
from repro.optim.compression import tree_compressed_psum


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    lr: float = 3e-4
    warmup: int = 100
    total_steps: int = 10_000
    grad_clip: float = 1.0
    microbatches: int = 1
    accum_dtype: str = "float32"       # bfloat16 for the >=100B archs
    attn_impl: str = "dense"           # dense | chunked | pallas
    attn_chunk: int = 1024
    grad_compression: Optional[str] = None   # None | 'int8_pod'
    moment_dtype: str = "float32"


def _opt(cfg: ArchConfig, tc: TrainConfig):
    lr_fn = cosine_schedule(tc.lr, tc.warmup, tc.total_steps)
    if cfg.optimizer == "adafactor":
        return make_optimizer("adafactor", lr_fn)
    return make_optimizer("adamw", lr_fn,
                          moment_dtype=jnp.dtype(tc.moment_dtype))


def init_train_state(key, cfg: ArchConfig, tc: TrainConfig,
                     dtype=jnp.float32):
    params = T.init_params(key, cfg, dtype)
    opt = _opt(cfg, tc)
    state = {"opt": opt.init(params), "step": jnp.zeros((), jnp.int32)}
    if tc.grad_compression == "int8_pod":
        state["ef"] = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.bfloat16), params)
    return params, state


def train_state_shapes(cfg: ArchConfig, tc: TrainConfig,
                       dtype=jnp.bfloat16):
    """ShapeDtypeStructs for (params, opt_state) — dry-run, no allocation."""
    return jax.eval_shape(
        lambda k: init_train_state(k, cfg, tc, dtype), jax.random.key(0))


def _factored_spec(spec, ndim, drop_axis):
    parts = list(spec) + [None] * (ndim - len(spec))
    del parts[drop_axis]
    return P(*parts)


def train_state_pspecs(cfg: ArchConfig, tc: TrainConfig, rules: T.ShardRules,
                       params_tree):
    """PartitionSpec tree matching init_train_state structure exactly."""
    pspecs = T.param_pspecs(cfg, rules)
    if cfg.optimizer == "adafactor":
        def per_leaf(p, spec):
            if p.ndim >= 2:
                return {"vr": _factored_spec(spec, p.ndim, p.ndim - 1),
                        "vc": _factored_spec(spec, p.ndim, p.ndim - 2)}
            return {"v": spec}
        opt_spec = {"v": jax.tree.map(per_leaf, params_tree, pspecs)}
    else:
        opt_spec = {"mu": pspecs, "nu": pspecs}
    state_spec = {"opt": opt_spec, "step": P()}
    if tc.grad_compression == "int8_pod":
        state_spec["ef"] = pspecs
    return pspecs, state_spec


def batch_pspec(cfg: ArchConfig, rules: T.ShardRules):
    b = rules.batch
    spec = {"tokens": P(b, None), "labels": P(b, None)}
    if cfg.n_codebooks > 1:
        spec = {"tokens": P(b, None, None), "labels": P(b, None, None)}
    if cfg.input_mode == "embeddings":
        spec = {"embeds": P(b, None, None), "positions": P(None, b, None),
                "labels": P(b, None)}
    return spec


def make_train_step(cfg: ArchConfig, tc: TrainConfig,
                    rules: Optional[T.ShardRules] = None):
    opt = _opt(cfg, tc)
    accum_dtype = jnp.dtype(tc.accum_dtype)

    def loss(params, batch):
        return T.loss_fn(params, cfg, batch, impl=tc.attn_impl,
                         chunk=tc.attn_chunk, rules=rules)

    grad_fn = jax.grad(loss, has_aux=True)

    def compute_grads(params, batch):
        if tc.microbatches == 1:
            return grad_fn(params, batch)
        m = tc.microbatches

        def resh(x):
            b = x.shape[0]
            assert b % m == 0, (b, m)
            return x.reshape(m, b // m, *x.shape[1:])

        # positions (3,B,S) has batch second — handle leading-batch only
        mb = {}
        for k, v in batch.items():
            if k == "positions":
                mb[k] = v.reshape(v.shape[0], m, v.shape[1] // m,
                                  *v.shape[2:]).swapaxes(0, 1)
            else:
                mb[k] = resh(v)

        def body(acc, micro):
            g, metrics = grad_fn(params, micro)
            acc_g, acc_m = acc
            acc_g = jax.tree.map(
                lambda a, x: a + x.astype(accum_dtype), acc_g, g)
            acc_m = jax.tree.map(lambda a, x: a + x / m, acc_m, metrics)
            return (acc_g, acc_m), None

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, accum_dtype), params)
        _, m0 = jax.eval_shape(grad_fn, params, jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype)
            if False else x[0], mb))
        m0 = jax.tree.map(lambda s: jnp.zeros((), jnp.float32), m0)
        (g, metrics), _ = lax.scan(body, (g0, m0), mb)
        g = jax.tree.map(lambda x, p: (x / m).astype(p.dtype), g, params)
        return g, metrics

    def train_step(params, state, batch):
        grads, metrics = compute_grads(params, batch)
        new_state = dict(state)
        if tc.grad_compression == "int8_pod":
            grads, new_ef = tree_compressed_psum(grads, "pod", state["ef"])
            grads = jax.tree.map(lambda g, p: g.astype(p.dtype), grads,
                                 params)
            new_state["ef"] = new_ef
        grads, gnorm = clip_by_global_norm(grads, tc.grad_clip)
        updates, new_opt = opt.update(grads, state["opt"], params,
                                      state["step"])
        new_params = jax.tree.map(
            lambda p, u: (p.astype(jnp.float32)
                          + u.astype(jnp.float32)).astype(p.dtype),
            params, updates)
        new_state["opt"] = new_opt
        new_state["step"] = state["step"] + 1
        metrics = dict(metrics)
        metrics["grad_norm"] = gnorm
        return new_params, new_state, metrics

    return train_step


def make_compressed_train_step(cfg: ArchConfig, tc: TrainConfig,
                               rules: T.ShardRules, mesh):
    """int8-compressed cross-pod DP: the whole step runs under shard_map
    manual on the 'pod' axis (auto on data/model), so the pod-axis gradient
    reduction is our explicit int8 psum instead of GSPMD's bf16 all-reduce.

    Params/optimizer state are replicated across pods (P() on 'pod'); the
    batch is split on 'pod'. Inside the body, grads are pod-local partial
    sums; ``tree_compressed_psum`` produces the exact int8-quantized average
    with error feedback carried in ``state['ef']``.
    """
    assert tc.grad_compression == "int8_pod"
    opt = _opt(cfg, tc)
    # inside the manual 'pod' region only the auto axes may appear in
    # sharding constraints — drop 'pod' from the batch rule
    inner_rules = dataclasses.replace(
        rules, batch=tuple(a for a in rules.batch if a != "pod"))

    def body(params, state, batch):
        grads, metrics = jax.grad(
            lambda p, b: T.loss_fn(p, cfg, b, impl=tc.attn_impl,
                                   chunk=tc.attn_chunk, rules=inner_rules),
            has_aux=True)(params, batch)
        grads, new_ef = tree_compressed_psum(grads, "pod", state["ef"])
        grads = jax.tree.map(lambda g, p: g.astype(p.dtype), grads, params)
        grads, gnorm = clip_by_global_norm(grads, tc.grad_clip)
        updates, new_opt = opt.update(grads, state["opt"], params,
                                      state["step"])
        new_params = jax.tree.map(
            lambda p, u: (p.astype(jnp.float32)
                          + u.astype(jnp.float32)).astype(p.dtype),
            params, updates)
        new_state = dict(state)
        new_state["ef"] = new_ef
        new_state["opt"] = new_opt
        new_state["step"] = state["step"] + 1
        metrics = dict(metrics)
        metrics["grad_norm"] = gnorm
        metrics = jax.tree.map(lambda x: jax.lax.pmean(x, "pod"), metrics)
        return new_params, new_state, metrics

    def specs_of(tree_):
        return jax.tree.map(lambda _: P(), tree_)

    def step_fn(params, state, batch):
        # batch split on 'pod'; positions (3,B,S) carry batch on dim 1
        bspec = {}
        for k, v in batch.items():
            if k == "positions":
                bspec[k] = P(None, "pod")
            else:
                bspec[k] = P(*("pod",) + (None,) * (v.ndim - 1))
        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(specs_of(params), specs_of(state), bspec),
            out_specs=(specs_of(params), specs_of(state),
                       {"ce": P(), "loss": P(), "grad_norm": P(),
                        **({"lb_loss": P(), "z_loss": P(),
                            "dropped_frac": P()} if cfg.moe is not None
                           else {})}),
            axis_names={"pod"}, check_vma=False)
        return fn(params, state, batch)

    return step_fn
