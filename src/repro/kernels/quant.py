"""Symmetric per-feature int8 quantization for the k-means kernels.

The quantized k-means variants (``kmeans_int8`` in ``calibration.json``)
store points *and* centroids as int8 with one shared fp32 scale per
feature — the praxis-style weight-only scheme: storage and memory traffic
shrink 4×, the kernel dequantizes in-register, and every accumulation
(distance expansion, per-centroid sums) stays fp32.  A shared
per-*feature* scale is the correct axis for k-means: points and centroids
live in the same feature space, and per-feature scales do **not** factor
through the contraction axis of an int8×int8 matmul (Σ_f s_f² q_x q_c has
no common factor), so the MXU matmul runs on dequantized values while the
int8 arrays only pay the (4×-smaller) memory bill.

Shared by the Pallas int8 kernel (dequant in VMEM), the jnp simulation
path in :mod:`repro.ml.kmeans` and the :mod:`repro.kernels.ref` oracles —
one rounding definition, so parity tests are exact.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

INT8_MAX = 127.0


def symmetric_scales(points, centroids):
    """Per-feature symmetric scales shared by points and centroids:
    ``s_f = max(max|x_f|, max|c_f|) / 127`` (never zero, so dequantize is
    always well-defined).  Returns an ``(F,)`` fp32 array."""
    amax = jnp.maximum(
        jnp.max(jnp.abs(points.astype(jnp.float32)), axis=0),
        jnp.max(jnp.abs(centroids.astype(jnp.float32)), axis=0))
    return jnp.maximum(amax, 1e-12) / INT8_MAX


def quantize(x, scales):
    """Round-to-nearest symmetric int8 quantization, ``(N, F) -> int8``."""
    q = jnp.round(x.astype(jnp.float32) / scales[None, :])
    return jnp.clip(q, -INT8_MAX, INT8_MAX).astype(jnp.int8)


def dequantize(q, scales):
    """``int8 -> fp32`` (the values the kernels actually compute on)."""
    return q.astype(jnp.float32) * scales[None, :]


def fake_quantize(x, scales):
    """Quantize → dequantize in one step: the fp32 values an int8 kernel
    sees.  The jnp simulation path and the parity oracles both use this,
    so 'int8 kernel vs int8 reference' comparisons are bit-meaningful."""
    return dequantize(quantize(x, scales), scales)


def round_to_bf16(x):
    """The fp32 values a bf16 kernel holds: ``x`` rounded to bfloat16.
    ``reduce_precision`` is kept by XLA, where a ``bf16 -> f32`` convert
    pair may be folded away as excess precision."""
    return jax.lax.reduce_precision(x.astype(jnp.float32), exponent_bits=8,
                                    mantissa_bits=7)
