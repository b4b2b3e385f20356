"""Gradient compression for the cross-pod (DCN) data-parallel reduction.

The ICI all-reduce inside a pod is cheap (~50 GB/s/link); the pod axis rides
on DCN where bandwidth is the scarce resource. We compress the pod-axis
gradient all-reduce to int8 with per-tensor scale + error feedback:

    q = round(g / s),  s = max|g| / 127        (per leaf)
    psum(q) over 'pod'  →  dequantize  →  average

Error feedback (Karimireddy et al. 2019) keeps the quantization residual in
the optimizer state and re-injects it next step, preserving convergence.

``compressed_psum`` must run under ``shard_map`` manual over the 'pod' axis
(the train step uses shard_map(auto={'data','model'}) when
``grad_compression='int8_pod'``). The DCN traffic drops 4x vs fp32 / 2x vs
bf16 per direction; §Perf records the measured collective-bytes delta.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def int8_compress(g):
    """Per-tensor symmetric int8 quantization. Returns (q, scale)."""
    g32 = g.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(g32)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(g32 / scale), -127, 127).astype(jnp.int8)
    return q, scale


def int8_decompress(q, scale):
    return q.astype(jnp.float32) * scale


def compressed_psum(g, axis_name: str, error: jnp.ndarray | None = None):
    """int8 all-reduce-mean over ``axis_name`` with error feedback.

    Wire format stays int8 end-to-end: a naive ``psum(int32)`` would put
    4 B/elem on the DCN (2x WORSE than bf16 — §Perf measured exactly that
    on the first attempt). Instead:

        all_to_all(int8 chunks)  →  local dequant + sum  →  requantize
        →  all_gather(int8)

    = 2N int8 bytes on the wire vs ~4N for a bf16 ring all-reduce: 2x DCN
    reduction, 4x vs fp32. Error feedback keeps the local quantization
    residual; the reduced-chunk requantization error is O(1/127) of the
    already-averaged gradient.

    Returns (g_avg_f32, new_error). Call under shard_map manual over
    ``axis_name``.
    """
    g32 = g.astype(jnp.float32)
    if error is not None:
        g32 = g32 + error.astype(jnp.float32)
    p = jax.lax.axis_size(axis_name)
    shape = g32.shape
    n = g32.size
    pad = (-n) % p
    flat = jnp.pad(g32.reshape(-1), (0, pad))

    scale_local = jnp.maximum(jnp.max(jnp.abs(g32)), 1e-12) / 127.0
    scale = jax.lax.pmax(scale_local, axis_name)
    q = jnp.clip(jnp.round(flat / scale), -127, 127).astype(jnp.int8)
    deq = q[:n].astype(jnp.float32).reshape(shape) * scale
    new_error = g32 - deq

    if p == 1:
        return q[:n].astype(jnp.float32).reshape(shape) * scale, new_error

    # scatter int8 chunks: row i goes to peer i
    chunks = q.reshape(p, -1)
    recv = jax.lax.all_to_all(chunks, axis_name, split_axis=0,
                              concat_axis=0, tiled=False)
    # recv (p, chunk): peer contributions for MY chunk — dequant + sum
    local_sum = jnp.sum(recv.astype(jnp.float32), axis=0) * scale / p
    # requantize the reduced chunk and gather
    scale2_local = jnp.maximum(jnp.max(jnp.abs(local_sum)), 1e-12) / 127.0
    scale2 = jax.lax.pmax(scale2_local, axis_name)
    q2 = jnp.clip(jnp.round(local_sum / scale2), -127, 127).astype(jnp.int8)
    gathered = jax.lax.all_gather(q2, axis_name, axis=0)     # (p, chunk)
    out = gathered.reshape(-1)[:n].astype(jnp.float32) * scale2
    return out.reshape(shape), new_error


def tree_compressed_psum(grads, axis_name: str, errors=None):
    """Apply compressed_psum leaf-wise over a gradient pytree."""
    if errors is None:
        errors = jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.bfloat16),
                              grads)
    out = jax.tree.map(
        lambda g, e: compressed_psum(g, axis_name, e), grads, errors)
    g_avg = jax.tree.map(lambda o: o[0], out,
                         is_leaf=lambda x: isinstance(x, tuple))
    new_err = jax.tree.map(lambda o: o[1].astype(jnp.bfloat16), out,
                           is_leaf=lambda x: isinstance(x, tuple))
    return g_avg, new_err
