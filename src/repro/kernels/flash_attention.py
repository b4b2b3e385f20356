"""Pallas TPU flash-attention kernel (GQA-aware, causal, sliding-window).

TPU-native design (not a CUDA port — see DESIGN.md §2):

* grid = (batch, q_heads, q_blocks, k_blocks); the innermost k-block axis is
  sequential ("arbitrary"), so VMEM scratch (m/l/acc) carries the online-
  softmax state across k-blocks — the TPU analogue of a CUDA thread-block
  loop, with the MXU doing the (block_q × d) @ (d × block_k) score matmul
  and the (block_q × block_k) @ (block_k × d) value matmul.
* GQA happens in the BlockSpec index_map: the kv block for q-head ``h`` is
  head ``h // (H // Hkv)`` — no repeated kv materialization in HBM.
* block_q = block_k = 128 keeps matmul dims MXU-aligned (128×128 systolic
  array) and the working set (q,k,v,acc ≈ 4·128·d·4B) well under VMEM.
* masks (causal / sliding window / k-padding) are f32 ``-inf`` adds built
  from 2-D ``broadcasted_iota`` (TPU has no 1-D iota).

Out-of-window k-blocks are masked, not skipped; the §Perf causal-block
scheduling note quantifies the waste (≤2× for causal) and the follow-up.

Validated in interpret mode against kernels/ref.py::flash_attention_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = float("-inf")


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, block_q: int, block_k: int, nk: int,
                  causal: bool, window, k_len: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                  # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)                  # (bk, d)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = kpos < k_len                                   # k padding
    if causal:
        mask = mask & (kpos <= qpos)
    if window is not None:
        mask = mask & (kpos > qpos - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    m_safe = jnp.where(m_new == NEG_INF, 0.0, m_new)
    p = jnp.exp(s - m_safe[:, None])
    p = jnp.where(s == NEG_INF, 0.0, p)
    corr = jnp.exp(jnp.where(m_prev == NEG_INF, 0.0, m_prev) - m_safe)
    corr = jnp.where(m_prev == NEG_INF, 0.0, corr)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr[:, None] + pv
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _done():
        denom = jnp.maximum(l_ref[...], 1e-20)[:, None]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _pad_to(x, axis: int, mult: int):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    block_q: int = 128, block_k: int = 128,
                    interpret=None):
    """q (B,Sq,H,D); k/v (B,Sk,Hkv,D) -> (B,Sq,H,D).

    ``interpret=None`` compiles the kernel on a TPU backend and runs the
    kernel body in the interpreter elsewhere
    (:func:`repro.kernels.resolve_interpret`).
    """
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    assert h % hkv == 0, (h, hkv)
    rep = h // hkv

    # (B,H,S,D) layout for clean blocking
    qt = _pad_to(q.transpose(0, 2, 1, 3), 2, block_q)
    kt = _pad_to(k.transpose(0, 2, 1, 3), 2, block_k)
    vt = _pad_to(v.transpose(0, 2, 1, 3), 2, block_k)
    sq_p, sk_p = qt.shape[2], kt.shape[2]
    nq, nk = sq_p // block_q, sk_p // block_k

    kernel = functools.partial(
        _flash_kernel, scale=1.0 / (d ** 0.5), block_q=block_q,
        block_k=block_k, nk=nk, causal=causal, window=window, k_len=sk)

    out = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda ib, ih, iq, ik, rep=rep:
                         (ib, ih // rep, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda ib, ih, iq, ik, rep=rep:
                         (ib, ih // rep, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qt, kt, vt)
    return out[:, :, :sq, :].transpose(0, 2, 1, 3)
