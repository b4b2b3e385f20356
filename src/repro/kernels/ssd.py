"""Pallas TPU Mamba2-SSD chunk kernel.

The SSD prefill decomposes into (Mamba2 Alg. 1):

  1. **intra-chunk** (quadratic in chunk length): y += (L ∘ (C·Bᵀ)) · X —
     two (chunk × chunk) MXU matmuls per (batch, head, chunk); this is the
     compute hot-spot and lives in the kernel,
  2. **chunk states**: S_c = Bᵀ·(decay·dt·X) — one (ds × chunk)@(chunk × hd)
     MXU matmul, also in the kernel,
  3. **inter-chunk recurrence** — sequential over ~S/chunk steps; stays in
     ``lax.scan`` outside (a sequential dependence has no MXU win).

Grid = (batch, heads, chunks); heads map to their B/C group via the
BlockSpec index_map (n_groups ≤ heads, like GQA). The cumulative decay
``cum`` is computed with a lower-triangular ones matmul (MXU) rather than a
1-D scan (TPU-friendly), and is emitted so the host-side inter-chunk pass
can reuse it.

Validated in interpret mode against kernels/ref.py::ssd_ref (exact
sequential recurrence).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _ssd_kernel(xh_ref, dt_ref, b_ref, c_ref, a_ref, d_ref,
                y_ref, st_ref, cum_ref, *, chunk: int):
    x = xh_ref[0, 0].astype(jnp.float32)                   # (q, hd)
    dt = dt_ref[0, 0].astype(jnp.float32)                  # (1, q) row
    dt = dt.reshape(chunk)
    B = b_ref[0, 0].astype(jnp.float32)                    # (q, ds)
    C = c_ref[0, 0].astype(jnp.float32)                    # (q, ds)
    A = a_ref[0, 0]                                        # scalar
    D = d_ref[0, 0]

    dA = dt * A                                            # (q,) <= 0
    # cumulative sum via lower-triangular ones matmul (MXU, no 1-D scan)
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tril = (ii >= jj).astype(jnp.float32)
    cum = jax.lax.dot_general(tril, dA[:, None], (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)[:, 0]

    # intra-chunk: L[i,j] = exp(cum_i - cum_j)·dt_j for i >= j
    L = jnp.exp(cum[:, None] - cum[None, :]) * dt[None, :]
    L = jnp.where(ii >= jj, L, 0.0)
    G = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (q,q)
    y = jax.lax.dot_general(G * L, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (q,hd)
    y = y + x * D

    # chunk state: S = Bᵀ · (decay_to_end · dt · X)  -> (ds, hd)
    total = cum[chunk - 1]
    w = jnp.exp(total - cum) * dt                          # (q,)
    st = jax.lax.dot_general(B, x * w[:, None], (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)

    y_ref[0, 0] = y.astype(y_ref.dtype)
    st_ref[0, 0, 0] = st
    cum_ref[0, 0] = cum[None, :]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunk_scan(xh, dt, A, B_, C_, D, *, chunk: int = 256,
                   interpret=None):
    """Full SSD pass: Pallas intra-chunk kernel + host inter-chunk scan.

    xh (B,S,nh,hd); dt (B,S,nh) post-softplus; A (nh,) negative;
    B_/C_ (B,S,g,ds); D (nh,). Returns (y (B,S,nh,hd), final_state
    (B,nh,hd,ds)) matching ref.ssd_ref.
    """
    b, s, nh, hd = xh.shape
    g, ds = B_.shape[2], B_.shape[3]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    rep = nh // g

    xt = xh.transpose(0, 2, 1, 3)                          # (B,nh,S,hd)
    dtt = dt.transpose(0, 2, 1)[:, :, None, :]             # (B,nh,1,S)
    Bt = B_.transpose(0, 2, 1, 3)                          # (B,g,S,ds)
    Ct = C_.transpose(0, 2, 1, 3)
    A2 = A.reshape(nh, 1).astype(jnp.float32)
    D2 = D.reshape(nh, 1).astype(jnp.float32)

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y, st, cum = pl.pallas_call(
        kernel,
        grid=(b, nh, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, hd), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda ib, ih, ic: (ib, ih, 0, ic)),
            pl.BlockSpec((1, 1, chunk, ds),
                         lambda ib, ih, ic, rep=rep: (ib, ih // rep, ic, 0)),
            pl.BlockSpec((1, 1, chunk, ds),
                         lambda ib, ih, ic, rep=rep: (ib, ih // rep, ic, 0)),
            pl.BlockSpec((1, 1), lambda ib, ih, ic: (ih, 0)),
            pl.BlockSpec((1, 1), lambda ib, ih, ic: (ih, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, hd), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, 1, ds, hd),
                         lambda ib, ih, ic: (ib, ih, ic, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk),
                         lambda ib, ih, ic: (ib, ih, ic, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nh, s, hd), xh.dtype),
            jax.ShapeDtypeStruct((b, nh, nc, ds, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, nh, nc, chunk), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(xt, dtt, Bt, Ct, A2, D2)

    # ---- inter-chunk recurrence (sequential, host-side jnp) ----
    total = cum[:, :, :, chunk - 1]                        # (B,nh,nc)

    def step(prev, xs):
        st_c, tot_c = xs                                   # (B,nh,ds,hd)
        new = jnp.exp(tot_c)[..., None, None] * prev + st_c
        return new, prev

    init = jnp.zeros((b, nh, ds, hd), jnp.float32)
    final, prev_states = jax.lax.scan(
        step, init, (st.transpose(2, 0, 1, 3, 4), total.transpose(2, 0, 1)))
    prev_states = prev_states.transpose(1, 2, 0, 3, 4)     # (B,nh,nc,ds,hd)

    CH = jnp.repeat(Ct, rep, axis=1).reshape(b, nh, nc, chunk, ds)
    y_inter = jnp.einsum("bhcin,bhcnp->bhcip",
                         CH * jnp.exp(cum)[..., None].astype(jnp.float32),
                         prev_states)
    y = y + y_inter.reshape(b, nh, s, hd).astype(y.dtype)
    return (y.transpose(0, 2, 1, 3),
            final.transpose(0, 1, 3, 2))                   # (B,nh,hd,ds)
