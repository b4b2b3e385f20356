"""Layer primitives for the unified decoder model zoo.

Pure-JAX implementations of every mixer/FFN family needed by the assigned
architectures:

* GQA attention (dense / chunked-flash / sliding-window / decode)
* MLA — multi-head latent attention (prefill expansion + absorbed decode)
* Mamba2 SSD — chunked state-space duality scan (prefill) + stateful decode
* Hymba hybrid block — parallel attention + SSM heads
* FFN: SwiGLU / squared-ReLU / GELU
* MoE: top-k router with scatter-based capacity dispatch (+ arctic's parallel
  dense residual)

All functions take params as plain dict pytrees; initializers live next to the
forward functions so the structure is defined exactly once. Softmax/norm math
runs in float32 regardless of the compute dtype.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig

# ---------------------------------------------------------------------------
# small utilities
# ---------------------------------------------------------------------------


def rms_norm(x, weight, eps=1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


def _init(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def silu(x):
    return x * jax.nn.sigmoid(x)


# ---------------------------------------------------------------------------
# rotary position embeddings (RoPE + M-RoPE)
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def rope_cos_sin(positions, head_dim: int, theta: float):
    """positions (...,S) int -> cos/sin (...,S,head_dim//2) float32."""
    freqs = rope_freqs(head_dim, theta)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def mrope_cos_sin(positions, head_dim: int, theta: float, sections):
    """M-RoPE (Qwen2-VL): positions (3,B,S) for (t,h,w) sections.

    ``sections`` gives per-axis counts of rotary half-dims,
    sum(sections) == head_dim // 2.
    """
    assert sum(sections) == head_dim // 2, (sections, head_dim)
    freqs = rope_freqs(head_dim, theta)                       # (hd/2,)
    ang = positions.astype(jnp.float32)[..., None] * freqs    # (3,B,S,hd/2)
    parts_cos, parts_sin = [], []
    off = 0
    for i, n in enumerate(sections):
        parts_cos.append(jnp.cos(ang[i, ..., off:off + n]))
        parts_sin.append(jnp.sin(ang[i, ..., off:off + n]))
        off += n
    return jnp.concatenate(parts_cos, -1), jnp.concatenate(parts_sin, -1)


def apply_rope(x, cos, sin):
    """x (B,S,H,D); cos/sin (B,S,D/2) or (S,D/2) — rotate-half convention."""
    if cos.ndim == 2:
        cos, sin = cos[None], sin[None]
    cos = cos[:, :, None, :].astype(jnp.float32)
    sin = sin[:, :, None, :].astype(jnp.float32)
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention cores
# ---------------------------------------------------------------------------


def _repeat_kv(k, n_rep: int):
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)
                            ).reshape(b, s, h * n_rep, d)


def attention_dense(q, k, v, *, causal=True, window=None, q_offset=0):
    """Reference O(S^2)-memory attention. q (B,Sq,H,D), k/v (B,Sk,Hkv,D)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    k = _repeat_kv(k, h // k.shape[2])
    v = _repeat_kv(v, h // v.shape[2])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(d))
    qpos = jnp.arange(sq) + q_offset
    kpos = jnp.arange(sk)
    mask = kpos[None, :] <= qpos[:, None] if causal else jnp.ones(
        (sq, sk), bool)
    if window is not None:
        mask = mask & (kpos[None, :] > qpos[:, None] - window)
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    return out


def attention_chunked(q, k, v, *, causal=True, window=None,
                      chunk_q=1024, chunk_k=1024):
    """Flash-style chunked attention in pure jnp (online softmax).

    Memory is O(chunk_q * chunk_k) per (batch, head) instead of O(S^2); this
    is the XLA stand-in for the Pallas flash kernel and is used for the long
    prefill shapes. Upper-triangular chunk pairs are masked (not skipped) —
    see EXPERIMENTS.md §Perf for the scheduling optimization that removes the
    waste.
    """
    b, s, h, d = q.shape
    dv = v.shape[-1]                      # MLA: v head dim != qk head dim
    sk = k.shape[1]
    assert s % chunk_q == 0 and sk % chunk_k == 0, (s, sk, chunk_q, chunk_k)
    nq, nk = s // chunk_q, sk // chunk_k
    k = _repeat_kv(k, h // k.shape[2])
    v = _repeat_kv(v, h // v.shape[2])

    qc = q.reshape(b, nq, chunk_q, h, d)
    kc = k.reshape(b, nk, chunk_k, h, d).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nk, chunk_k, h, dv).transpose(1, 0, 2, 3, 4)
    scale = 1.0 / jnp.sqrt(jnp.float32(d))

    qpos = jnp.arange(s).reshape(nq, 1, chunk_q, 1)          # (nq,1,cq,1)

    def body(carry, xs):
        m, l, acc = carry                                    # running stats
        kb, vb, j = xs
        kpos = (j * chunk_k + jnp.arange(chunk_k)).reshape(1, 1, 1, chunk_k)
        sc = jnp.einsum("bnqhd,bkhd->bnhqk", qc, kb,
                        preferred_element_type=jnp.float32) * scale
        mask = kpos <= qpos if causal else (kpos >= 0)       # (nq,1,cq,ck)
        if window is not None:
            mask = mask & (kpos > qpos - window)
        # (nq,1,cq,ck) -> (1,nq,1,cq,ck), broadcasts against (b,nq,h,cq,ck)
        sc = jnp.where(mask[None], sc, -jnp.inf)
        m_new = jnp.maximum(m, sc.max(-1))
        # guard fully-masked rows
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(sc - m_safe[..., None])
        p = jnp.where(jnp.isneginf(sc), 0.0, p)
        corr = jnp.exp(jnp.where(jnp.isneginf(m), 0.0, m) - m_safe)
        corr = jnp.where(jnp.isneginf(m), 0.0, corr)
        l_new = l * corr + p.sum(-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bnhqk,bkhd->bnhqd", p.astype(vb.dtype), vb).astype(jnp.float32)
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, nq, h, chunk_q), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, nq, h, chunk_q), jnp.float32)
    a0 = jnp.zeros((b, nq, h, chunk_q, dv), jnp.float32)
    (m, l, acc), _ = lax.scan(body, (m0, l0, a0),
                              (kc, vc, jnp.arange(nk)))
    out = acc / jnp.maximum(l[..., None], 1e-20)
    return out.transpose(0, 1, 3, 2, 4).reshape(b, s, h, dv).astype(q.dtype)


def attention_decode(q, k_cache, v_cache, valid_len):
    """Single-token decode. q (B,1,H,D); caches (B,Smax,Hkv,D); valid_len =
    number of valid cache entries (the new token is already written).

    GQA is computed *grouped* — q reshaped to (B,1,Hkv,rep,D) against the
    raw (B,S,Hkv,D) cache — instead of materializing ``repeat_kv``. The
    broadcast reshape defeated GSPMD sharding propagation (Hkv=8 cannot
    re-tile to 16 model shards), forcing a full KV-cache all-gather per
    layer; the grouped einsum keeps the cache model-sharded along S and
    turns the collective into tiny (B,H,1)-stat all-reduces.

    Ring-buffer caches (sliding-window archs) are handled by the caller: once
    the buffer wraps, *every* slot is valid and in-window, so a plain
    ``kpos < valid_len`` mask is exact for both layouts."""
    b, _, h, d = q.shape
    smax, hkv = k_cache.shape[1], k_cache.shape[2]
    rep = h // hkv
    qg = q.reshape(b, 1, hkv, rep, d)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k_cache,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(d))
    kpos = jnp.arange(smax)
    mask = kpos < valid_len
    scores = jnp.where(mask[None, None, None, None, :], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", p.astype(v_cache.dtype), v_cache)
    return out.reshape(b, 1, h, d)


# ---------------------------------------------------------------------------
# GQA attention layer
# ---------------------------------------------------------------------------


def gqa_init(key, cfg: ArchConfig, dtype):
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    scale = 0.02
    out_scale = 0.02 / jnp.sqrt(2.0 * cfg.n_layers)
    return {
        "wq": _init(ks[0], (d, h * hd), scale, dtype),
        "wk": _init(ks[1], (d, hkv * hd), scale, dtype),
        "wv": _init(ks[2], (d, hkv * hd), scale, dtype),
        "wo": _init(ks[3], (h * hd, d), out_scale, dtype),
    }


def gqa_forward(p, x, cos, sin, cfg: ArchConfig, *, impl="dense",
                window=None, chunk=1024):
    b, s, d = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(b, s, h, hd)
    k = (x @ p["wk"]).reshape(b, s, hkv, hd)
    v = (x @ p["wv"]).reshape(b, s, hkv, hd)
    if cos is not None:
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    if impl == "dense":
        o = attention_dense(q, k, v, causal=True, window=window)
    elif impl == "chunked":
        o = attention_chunked(q, k, v, causal=True, window=window,
                              chunk_q=min(chunk, s), chunk_k=min(chunk, s))
    elif impl == "pallas":
        from repro.kernels.flash_attention import flash_attention
        o = flash_attention(q, k, v, causal=True, window=window)
    else:
        raise ValueError(impl)
    return o.reshape(b, s, h * hd) @ p["wo"], (k, v)


def gqa_decode(p, x, cache_k, cache_v, write_idx, valid_len, cos, sin,
               cfg: ArchConfig):
    """x (B,1,D). Writes the new kv at ``write_idx`` (== position, or
    position % window for ring buffers); attends over ``valid_len`` entries.
    Returns (out, new_k, new_v)."""
    b, s, d = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(b, 1, h, hd)
    k = (x @ p["wk"]).reshape(b, 1, hkv, hd)
    v = (x @ p["wv"]).reshape(b, 1, hkv, hd)
    if cos is not None:
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    cache_k = lax.dynamic_update_slice(cache_k, k.astype(cache_k.dtype),
                                       (0, write_idx, 0, 0))
    cache_v = lax.dynamic_update_slice(cache_v, v.astype(cache_v.dtype),
                                       (0, write_idx, 0, 0))
    o = attention_decode(q, cache_k, cache_v, valid_len)
    return o.reshape(b, 1, h * hd) @ p["wo"], cache_k, cache_v


# ---------------------------------------------------------------------------
# MLA — multi-head latent attention (MiniCPM3 / DeepSeek-V2 family)
# ---------------------------------------------------------------------------


def mla_init(key, cfg: ArchConfig, dtype):
    m, d, h = cfg.mla, cfg.d_model, cfg.n_heads
    qk = m.qk_nope_dim + m.qk_rope_dim
    ks = jax.random.split(key, 5)
    out_scale = 0.02 / jnp.sqrt(2.0 * cfg.n_layers)
    return {
        "wq_a": _init(ks[0], (d, m.q_lora_rank), 0.02, dtype),
        "q_norm": jnp.ones((m.q_lora_rank,), dtype),
        "wq_b": _init(ks[1], (m.q_lora_rank, h * qk), 0.02, dtype),
        "wkv_a": _init(ks[2], (d, m.kv_lora_rank + m.qk_rope_dim), 0.02,
                       dtype),
        "kv_norm": jnp.ones((m.kv_lora_rank,), dtype),
        "wkv_b": _init(ks[3], (m.kv_lora_rank,
                               h * (m.qk_nope_dim + m.v_head_dim)), 0.02,
                       dtype),
        "wo": _init(ks[4], (h * m.v_head_dim, d), out_scale, dtype),
    }


def _mla_qkv(p, x, cos, sin, cfg):
    """Shared projection path; returns q_nope,q_rope,c_kv(normed),k_rope."""
    m, h = cfg.mla, cfg.n_heads
    b, s, _ = x.shape
    cq = rms_norm(x @ p["wq_a"], p["q_norm"], cfg.norm_eps)
    q = (cq @ p["wq_b"]).reshape(b, s, h, m.qk_nope_dim + m.qk_rope_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_dim], axis=-1)
    kv = x @ p["wkv_a"]
    c_kv, k_rope = jnp.split(kv, [m.kv_lora_rank], axis=-1)
    c_kv = rms_norm(c_kv, p["kv_norm"], cfg.norm_eps)
    q_rope = apply_rope(q_rope, cos, sin)
    k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)[:, :, 0]
    return q_nope, q_rope, c_kv, k_rope


def mla_forward(p, x, cos, sin, cfg: ArchConfig, *, impl="dense",
                chunk=1024):
    """Prefill/train path: expand the latent back to per-head k/v."""
    m, h = cfg.mla, cfg.n_heads
    b, s, _ = x.shape
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(p, x, cos, sin, cfg)
    kvx = (c_kv @ p["wkv_b"]).reshape(b, s, h, m.qk_nope_dim + m.v_head_dim)
    k_nope, v = jnp.split(kvx, [m.qk_nope_dim], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate([k_nope,
                         jnp.broadcast_to(k_rope[:, :, None, :],
                                          (b, s, h, m.qk_rope_dim))], -1)
    if impl == "chunked":
        o = attention_chunked(q, k, v, causal=True,
                              chunk_q=min(chunk, s), chunk_k=min(chunk, s))
    else:
        o = attention_dense(q, k, v, causal=True)
    return o.reshape(b, s, h * m.v_head_dim) @ p["wo"], (c_kv, k_rope)


def mla_decode(p, x, cache_ckv, cache_krope, length, cos, sin,
               cfg: ArchConfig):
    """Absorbed-matmul MLA decode: attention runs in the latent space, so the
    cache stays compressed — (B,S,kv_lora) + (B,S,rope) only."""
    m, h = cfg.mla, cfg.n_heads
    b = x.shape[0]
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(p, x, cos, sin, cfg)
    cache_ckv = lax.dynamic_update_slice(
        cache_ckv, c_kv.astype(cache_ckv.dtype), (0, length, 0))
    cache_krope = lax.dynamic_update_slice(
        cache_krope, k_rope.astype(cache_krope.dtype), (0, length, 0))
    w_kv = p["wkv_b"].reshape(m.kv_lora_rank, h, m.qk_nope_dim + m.v_head_dim)
    w_uk, w_uv = w_kv[..., :m.qk_nope_dim], w_kv[..., m.qk_nope_dim:]
    # absorb: q_lat[b,h,r] = sum_n q_nope[b,h,n] w_uk[r,h,n]
    q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope, w_uk)
    scale = 1.0 / jnp.sqrt(jnp.float32(m.qk_nope_dim + m.qk_rope_dim))
    sc = (jnp.einsum("bqhr,bsr->bhqs", q_lat, cache_ckv,
                     preferred_element_type=jnp.float32)
          + jnp.einsum("bqhn,bsn->bhqs", q_rope, cache_krope,
                       preferred_element_type=jnp.float32)) * scale
    smax = cache_ckv.shape[1]
    mask = jnp.arange(smax) < (length + 1)
    sc = jnp.where(mask[None, None, None, :], sc, -jnp.inf)
    pattn = jax.nn.softmax(sc, axis=-1)
    o_lat = jnp.einsum("bhqs,bsr->bqhr", pattn.astype(cache_ckv.dtype),
                       cache_ckv)
    o = jnp.einsum("bqhr,rhv->bqhv", o_lat, w_uv)
    out = o.reshape(b, 1, h * m.v_head_dim) @ p["wo"]
    return out, cache_ckv, cache_krope


# ---------------------------------------------------------------------------
# FFN variants
# ---------------------------------------------------------------------------


def ffn_init(key, cfg: ArchConfig, dtype, d_ff=None):
    d = cfg.d_model
    f = d_ff if d_ff is not None else cfg.d_ff
    ks = jax.random.split(key, 3)
    out_scale = 0.02 / jnp.sqrt(2.0 * cfg.n_layers)
    p = {"w_up": _init(ks[0], (d, f), 0.02, dtype),
         "w_down": _init(ks[1], (f, d), out_scale, dtype)}
    if cfg.ffn_kind == "swiglu":
        p["w_gate"] = _init(ks[2], (d, f), 0.02, dtype)
    return p


def ffn_forward(p, x, kind: str):
    if kind == "swiglu":
        return (silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]
    if kind == "relu2":
        h = jax.nn.relu(x @ p["w_up"])
        return (h * h) @ p["w_down"]
    if kind == "gelu":
        return jax.nn.gelu(x @ p["w_up"]) @ p["w_down"]
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# MoE — top-k router + scatter-based capacity dispatch
# ---------------------------------------------------------------------------


def moe_init(key, cfg: ArchConfig, dtype):
    m, d = cfg.moe, cfg.d_model
    ks = jax.random.split(key, 5)
    out_scale = 0.02 / jnp.sqrt(2.0 * cfg.n_layers)
    p = {
        "router": _init(ks[0], (d, m.n_experts), 0.02, jnp.float32),
        "w_gate": _init(ks[1], (m.n_experts, d, m.d_expert), 0.02, dtype),
        "w_up": _init(ks[2], (m.n_experts, d, m.d_expert), 0.02, dtype),
        "w_down": _init(ks[3], (m.n_experts, m.d_expert, d), out_scale,
                        dtype),
    }
    if m.dense_residual:
        p["dense"] = ffn_init(ks[4], cfg, dtype)
    return p


def moe_capacity(cfg: ArchConfig, n_tokens: int) -> int:
    m = cfg.moe
    c = int(m.capacity_factor * n_tokens * m.top_k / m.n_experts)
    return max(8, -(-c // 8) * 8)          # round up to multiple of 8


def _dispatch_positions(flat_ids, n_experts: int):
    """Position of each (token, slot) within its expert's arrival order.

    Sort-free (cumsum over a one-hot): the argsort formulation lowered to
    multi-megabyte variadic sorts in HLO (§Perf measured them at ~3 TB of
    traffic for qwen3 train); cumsum is linear, deterministic, and keeps
    the same (token, slot)-order priority semantics.

    flat_ids (..., N) int -> pos (..., N) int32.
    """
    oh = jax.nn.one_hot(flat_ids, n_experts, dtype=jnp.float32)
    csum = jnp.cumsum(oh, axis=-2)                      # inclusive
    pos = jnp.take_along_axis(csum, flat_ids[..., None].astype(jnp.int32),
                              axis=-1)[..., 0] - 1.0    # exclusive
    return pos.astype(jnp.int32)


def moe_forward(p, x, cfg: ArchConfig, *, shard_experts=None,
                groups: int = 1):
    """x (B,S,D) -> (y (B,S,D), aux_losses dict).

    Scatter/gather capacity dispatch: tokens are routed to a fixed-capacity
    (E, C, D) buffer with plain scatters (no one-hot dispatch einsum), so the
    HLO FLOP count stays proportional to *useful* expert FLOPs. Overflowing
    tokens are dropped (their combine weight contribution is zero), matching
    GShard/Switch semantics.

    ``groups > 1`` enables GShard-style *local dispatch groups*: tokens are
    pre-split into ``groups`` row blocks (aligned with the data-parallel
    sharding of the batch) and each group scatters into its own capacity
    slice. Without groups, the scatter's contributions from different data
    shards must be summed — XLA emits a full (E·C, D) all-reduce per scatter
    per layer per microbatch, which §Perf measured at 98.9% of all
    collective bytes for qwen3-moe. Group-local dispatch removes that sum
    entirely (each buffer row is written by exactly one shard); the
    trade-off is GShard's: capacity is enforced per group, so imbalance
    across groups can drop marginally more tokens.
    """
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    # group-local dispatch only when each group fills its capacity floor:
    # with few tokens/group (decode), the per-expert minimum capacity (8)
    # makes the grouped buffer `groups`x oversized — measured 2x WORSE for
    # arctic decode. Training shapes (tg ~ 65k) stay grouped.
    if (groups > 1 and t % groups == 0
            and m.capacity_factor * (t // groups) * m.top_k
            / m.n_experts >= 8):
        return _moe_forward_grouped(p, x, cfg, shard_experts, groups)
    xf = x.reshape(t, d)
    logits = (xf.astype(jnp.float32) @ p["router"])          # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, ids = lax.top_k(probs, m.top_k)                    # (T,k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    cap = moe_capacity(cfg, t)
    flat_ids = ids.reshape(-1)                               # (T*k,)
    pos = _dispatch_positions(flat_ids, m.n_experts).reshape(t, m.top_k)
    keep = pos < cap
    slot = jnp.where(keep, ids * cap + pos, m.n_experts * cap)  # drop slot

    buf = jnp.zeros((m.n_experts * cap + 1, d), x.dtype)
    for j in range(m.top_k):                                 # k small, unroll
        buf = buf.at[slot[:, j]].set(xf, mode="drop")
    eb = buf[:-1].reshape(m.n_experts, cap, d)
    if shard_experts is not None:
        eb = shard_experts(eb)
    h = jnp.einsum("ecd,edf->ecf", eb, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", eb, p["w_up"])
    out = jnp.einsum("ecf,efd->ecd", silu(h) * u, p["w_down"])
    if shard_experts is not None:
        out = shard_experts(out)
    out_flat = jnp.concatenate(
        [out.reshape(m.n_experts * cap, d),
         jnp.zeros((1, d), out.dtype)], 0)

    y = jnp.zeros((t, d), jnp.float32)
    for j in range(m.top_k):
        yj = out_flat[slot[:, j]]
        y = y + gate[:, j:j + 1] * yj.astype(jnp.float32)
    y = y.astype(x.dtype).reshape(b, s, d)

    # aux losses: switch load-balance + router z-loss
    me = probs.mean(0)                                        # (E,)
    one_hot_top1 = jax.nn.one_hot(ids[:, 0], m.n_experts, dtype=jnp.float32)
    ce = one_hot_top1.mean(0)
    aux = {
        "lb_loss": m.router_aux_coef * m.n_experts * jnp.sum(me * ce),
        "z_loss": m.router_z_coef * jnp.mean(
            jax.nn.logsumexp(logits, axis=-1) ** 2),
        "dropped_frac": 1.0 - keep.mean(),
    }
    if m.dense_residual:
        y = y + ffn_forward(p["dense"], x, cfg.ffn_kind)
    return y, aux


def _moe_forward_grouped(p, x, cfg: ArchConfig, shard_experts, groups: int):
    """Group-local capacity dispatch (see moe_forward docstring)."""
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    g = groups
    tg = t // g
    xf = x.reshape(g, tg, d)
    logits = jnp.einsum("gtd,de->gte", xf.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate, ids = lax.top_k(probs, m.top_k)                    # (g,tg,k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    cap = moe_capacity(cfg, tg)
    flat_ids = ids.reshape(g, tg * m.top_k)
    pos = _dispatch_positions(flat_ids, m.n_experts).reshape(g, tg,
                                                             m.top_k)
    keep = pos < cap
    slot = jnp.where(keep, ids * cap + pos, m.n_experts * cap)

    buf = jnp.zeros((g, m.n_experts * cap + 1, d), x.dtype)
    for j in range(m.top_k):
        buf = jax.vmap(lambda bf, sl, xr: bf.at[sl].set(xr, mode="drop"))(
            buf, slot[:, :, j], xf)
    eb = buf[:, :-1].reshape(g, m.n_experts, cap, d)
    if shard_experts is not None:
        eb = shard_experts(eb)
    h = jnp.einsum("gecd,edf->gecf", eb, p["w_gate"])
    u = jnp.einsum("gecd,edf->gecf", eb, p["w_up"])
    out = jnp.einsum("gecf,efd->gecd", silu(h) * u, p["w_down"])
    if shard_experts is not None:
        out = shard_experts(out)
    out_flat = jnp.concatenate(
        [out.reshape(g, m.n_experts * cap, d),
         jnp.zeros((g, 1, d), out.dtype)], 1)

    y = jnp.zeros((g, tg, d), jnp.float32)
    for j in range(m.top_k):
        yj = jax.vmap(lambda of, sl: of[sl])(out_flat, slot[:, :, j])
        y = y + gate[:, :, j:j + 1] * yj.astype(jnp.float32)
    y = y.astype(x.dtype).reshape(b, s, d)

    me = probs.mean((0, 1))
    one_hot_top1 = jax.nn.one_hot(ids[..., 0], m.n_experts,
                                  dtype=jnp.float32)
    ce = one_hot_top1.mean((0, 1))
    aux = {
        "lb_loss": m.router_aux_coef * m.n_experts * jnp.sum(me * ce),
        "z_loss": m.router_z_coef * jnp.mean(
            jax.nn.logsumexp(logits, axis=-1) ** 2),
        "dropped_frac": 1.0 - keep.mean(),
    }
    if m.dense_residual:
        y = y + ffn_forward(p["dense"], x, cfg.ffn_kind)
    return y, aux


# ---------------------------------------------------------------------------
# Mamba2 — SSD (state-space duality), chunked
# ---------------------------------------------------------------------------


def ssm_dims(cfg: ArchConfig):
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    nh = d_in // s.head_dim
    conv_dim = d_in + 2 * s.n_groups * s.d_state
    return d_in, nh, conv_dim


def ssm_init(key, cfg: ArchConfig, dtype):
    s = cfg.ssm
    d = cfg.d_model
    d_in, nh, conv_dim = ssm_dims(cfg)
    ks = jax.random.split(key, 4)
    out_scale = 0.02 / jnp.sqrt(2.0 * cfg.n_layers)
    dt = jnp.exp(jax.random.uniform(ks[2], (nh,), jnp.float32)
                 * (jnp.log(s.dt_max) - jnp.log(s.dt_min)) + jnp.log(s.dt_min))
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))                  # inv softplus
    return {
        "in_proj": _init(ks[0], (d, 2 * d_in + 2 * s.n_groups * s.d_state
                                 + nh), 0.02, dtype),
        "conv_w": _init(ks[1], (s.d_conv, conv_dim), 0.02, dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "A_log": jnp.log(jnp.arange(1, nh + 1, dtype=jnp.float32)),
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": dt_bias.astype(jnp.float32),
        "norm": jnp.ones((d_in,), dtype),
        "out_proj": _init(ks[3], (d_in, d), out_scale, dtype),
    }


def _ssm_split(p, x, cfg: ArchConfig):
    """in_proj + causal conv; returns (z, xh, B, C, dt_raw)."""
    s = cfg.ssm
    d_in, nh, conv_dim = ssm_dims(cfg)
    proj = x @ p["in_proj"]
    z, xbc, dt_raw = jnp.split(proj, [d_in, d_in + conv_dim], axis=-1)
    return z, xbc, dt_raw


def _causal_conv(xbc, conv_w, conv_b):
    """xbc (B,S,C); depthwise causal conv along S."""
    k = conv_w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + xbc.shape[1], :] * conv_w[i] for i in range(k))
    return silu(out + conv_b)


def ssd_chunked(xh, dt, A, B_, C_, D, chunk: int, *, return_state=False):
    """Chunked SSD scan (Mamba2 alg. 1), pure jnp.

    xh (B,S,nh,hd); dt (B,S,nh) [post-softplus]; A (nh,) negative;
    B_/C_ (B,S,g,d_state); D (nh,). Returns y (B,S,nh,hd), and with
    ``return_state`` also the final recurrent state (B,nh,hd,ds).
    """
    b, s, nh, hd = xh.shape
    g, ds = B_.shape[2], B_.shape[3]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    rep = nh // g

    xc = xh.reshape(b, nc, chunk, nh, hd)
    dtc = dt.reshape(b, nc, chunk, nh)
    Bc = B_.reshape(b, nc, chunk, g, ds)
    Cc = C_.reshape(b, nc, chunk, g, ds)
    BH = jnp.repeat(Bc, rep, axis=3)                        # (b,nc,q,nh,ds)
    CH = jnp.repeat(Cc, rep, axis=3)

    dA = dtc * A[None, None, None, :]                       # (b,nc,q,nh) <=0
    cum = jnp.cumsum(dA, axis=2)                            # within-chunk
    total = cum[:, :, -1, :]                                # (b,nc,nh)

    # ---- intra-chunk (quadratic within chunk) ----
    # L[i,j] = exp(cum_i - cum_j) * dt_j  for i >= j
    li = cum[:, :, :, None, :]                              # (b,nc,q,1,nh)
    lj = cum[:, :, None, :, :]                              # (b,nc,1,q,nh)
    mask = (jnp.arange(chunk)[:, None] >= jnp.arange(chunk)[None, :])
    L = jnp.exp(li - lj) * dtc[:, :, None, :, :]
    L = jnp.where(mask[None, None, :, :, None], L, 0.0)     # (b,nc,i,j,nh)
    G = jnp.einsum("bcihn,bcjhn->bcijh", CH, BH,
                   preferred_element_type=jnp.float32)      # (b,nc,i,j,nh)
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", G * L,
                         xc.astype(jnp.float32))

    # ---- chunk states ----
    decay_to_end = jnp.exp(total[:, :, None, :] - cum)      # (b,nc,j,nh)
    st = jnp.einsum("bcjhn,bcjh,bcjhp->bchpn", BH,
                    (decay_to_end * dtc).astype(jnp.float32),
                    xc.astype(jnp.float32))                 # (b,nc,nh,hd,ds)

    # ---- inter-chunk recurrence ----
    def step(state, xs):
        st_c, tot_c = xs                                    # (b,nh,hd,ds)
        prev = state
        new = jnp.exp(tot_c)[:, :, None, None] * prev + st_c
        return new, prev                                    # emit state *before* chunk

    init = jnp.zeros((b, nh, hd, ds), jnp.float32)
    final_state, prev_states = lax.scan(step, init,
                                        (st.transpose(1, 0, 2, 3, 4),
                                         total.transpose(1, 0, 2)))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)      # (b,nc,nh,hd,ds)

    # ---- inter-chunk output ----
    y_inter = jnp.einsum("bcihn,bchpn->bcihp", CH * jnp.exp(cum)[..., None],
                         prev_states)
    y = (y_intra + y_inter).reshape(b, s, nh, hd)
    y = (y + xh.astype(jnp.float32) * D[None, None, :, None]).astype(
        xh.dtype)
    if return_state:
        return y, final_state
    return y


def ssm_forward(p, x, cfg: ArchConfig, *, return_state=False, impl="jnp"):
    """Full-sequence Mamba2 mixer. x (B,S,D) -> y, or with ``return_state``
    -> (y, (final ssm_state (B,nh,hd,ds), conv_state (B,d_conv-1,conv_dim)))."""
    s = cfg.ssm
    d_in, nh, conv_dim = ssm_dims(cfg)
    b, sl, _ = x.shape
    z, xbc_raw, dt_raw = _ssm_split(p, x, cfg)
    xbc = _causal_conv(xbc_raw, p["conv_w"], p["conv_b"])
    xh, B_, C_ = jnp.split(xbc, [d_in, d_in + s.n_groups * s.d_state], -1)
    xh = xh.reshape(b, sl, nh, s.head_dim)
    B_ = B_.reshape(b, sl, s.n_groups, s.d_state)
    C_ = C_.reshape(b, sl, s.n_groups, s.d_state)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"][None, None, :])
    A = -jnp.exp(p["A_log"])
    if impl == "pallas":
        from repro.kernels.ssd import ssd_chunk_scan
        y, final = ssd_chunk_scan(xh, dt, A, B_, C_, p["D"],
                                  chunk=min(s.chunk, sl))
    else:
        y, final = ssd_chunked(xh, dt, A, B_, C_, p["D"], min(s.chunk, sl),
                               return_state=True)
    y = y.reshape(b, sl, d_in)
    y = rms_norm(y * silu(z), p["norm"], cfg.norm_eps)
    out = y @ p["out_proj"]
    if return_state:
        k = s.d_conv - 1
        conv_state = xbc_raw[:, -k:, :] if sl >= k else jnp.pad(
            xbc_raw, ((0, 0), (k - sl, 0), (0, 0)))
        return out, (final, conv_state.astype(x.dtype))
    return out


def ssm_decode(p, x, ssm_state, conv_state, cfg: ArchConfig):
    """Stateful single-token decode.

    x (B,1,D); ssm_state (B,nh,hd,ds) float32; conv_state (B,d_conv-1,conv_dim).
    Returns (y, new_ssm_state, new_conv_state).
    """
    s = cfg.ssm
    d_in, nh, conv_dim = ssm_dims(cfg)
    b = x.shape[0]
    z, xbc, dt_raw = _ssm_split(p, x, cfg)
    xbc = xbc[:, 0]                                          # (B,conv_dim)
    window = jnp.concatenate([conv_state, xbc[:, None, :]], 1)
    out = jnp.einsum("bkc,kc->bc", window, p["conv_w"]) + p["conv_b"]
    xbc_t = silu(out)
    new_conv = window[:, 1:]
    xh, B_, C_ = jnp.split(xbc_t, [d_in, d_in + s.n_groups * s.d_state], -1)
    xh = xh.reshape(b, nh, s.head_dim)
    B_ = jnp.repeat(B_.reshape(b, s.n_groups, s.d_state), nh // s.n_groups, 1)
    C_ = jnp.repeat(C_.reshape(b, s.n_groups, s.d_state), nh // s.n_groups, 1)
    dt = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    dA = jnp.exp(dt * A)                                     # (B,nh)
    upd = jnp.einsum("bh,bhp,bhn->bhpn", dt, xh.astype(jnp.float32),
                     B_.astype(jnp.float32))
    new_state = dA[:, :, None, None] * ssm_state + upd
    y = jnp.einsum("bhpn,bhn->bhp", new_state, C_.astype(jnp.float32))
    y = y + xh.astype(jnp.float32) * p["D"][None, :, None]
    y = y.reshape(b, 1, d_in).astype(x.dtype)
    y = rms_norm(y * silu(z), p["norm"], cfg.norm_eps)
    return y @ p["out_proj"], new_state, new_conv


# ---------------------------------------------------------------------------
# Hymba hybrid block pieces (parallel attn + SSM heads)
# ---------------------------------------------------------------------------


def hybrid_init(key, cfg: ArchConfig, dtype):
    k1, k2 = jax.random.split(key)
    d_in, _, _ = ssm_dims(cfg)
    return {
        "attn": gqa_init(k1, cfg, dtype),
        "ssm": ssm_init(k2, cfg, dtype),
        "attn_norm": jnp.ones((cfg.d_model,), dtype),
        "ssm_norm_out": jnp.ones((cfg.d_model,), dtype),
    }


def hybrid_forward(p, x, cos, sin, cfg: ArchConfig, *, impl="dense",
                   chunk=1024):
    a, kv = gqa_forward(p["attn"], x, cos, sin, cfg, impl=impl,
                        window=cfg.sliding_window, chunk=chunk)
    m = ssm_forward(p["ssm"], x, cfg)
    y = 0.5 * (rms_norm(a, p["attn_norm"], cfg.norm_eps)
               + rms_norm(m, p["ssm_norm_out"], cfg.norm_eps))
    return y, kv


def hybrid_decode(p, x, cache, write_idx, valid_len, cos, sin,
                  cfg: ArchConfig):
    a, ck, cv = gqa_decode(p["attn"], x, cache["k"], cache["v"], write_idx,
                           valid_len, cos, sin, cfg)
    m, st, conv = ssm_decode(p["ssm"], x, cache["ssm"], cache["conv"], cfg)
    y = 0.5 * (rms_norm(a, p["attn_norm"], cfg.norm_eps)
               + rms_norm(m, p["ssm_norm_out"], cfg.norm_eps))
    return y, {"k": ck, "v": cv, "ssm": st, "conv": conv}
