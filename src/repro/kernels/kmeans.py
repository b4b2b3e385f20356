"""Pallas TPU k-means kernels — the paper's k-means hot loop, fused.

The paper streams (N × 32)-point messages through a 25-centroid k-means
(§III.2); its per-message work is one assignment (outlier scoring) plus
one mini-batch centroid update.  TPU-native formulation: ‖x−c‖² = ‖x‖² −
2·x·cᵀ + ‖c‖², so the inner loop is a single (block_n × F) @ (F × K) MXU
matmul instead of a gather/scan — the MXU does the distance expansion,
the VPU the row-argmin.

Two entry points:

* :func:`kmeans_assign` — assignment only (ids + distances), one grid
  pass over N.
* :func:`kmeans_assign_update` — the **fused** assign+update kernel: the
  same grid pass additionally builds the block's one-hot membership
  in-register and accumulates per-centroid point sums (one more
  (K × block_n) @ (block_n × F) MXU matmul) and counts into accumulator
  outputs that live in VMEM across the sequential grid steps (constant
  index_map).  This eliminates the second pass of the two-pass update
  (``KMeans(impl="jnp")``), which materializes an (N × K) one-hot.

Precision variants (the placement axis ``cost/calibrate.py`` prices):

* ``fp32`` — everything float32.
* ``bf16`` — points/centroids stored as bfloat16 (half the HBM and VMEM
  traffic), widened to fp32 in VMEM for the MXU.
* ``int8`` — symmetric per-feature scales shared by points and
  centroids (:mod:`repro.kernels.quant`), int8 storage (quarter traffic),
  in-kernel dequantization, fp32 distance + sum accumulation.

Tiling: points are tiled over N (block_n rows in VMEM); the centroid
matrix (K × F) is tiny (25×32 ≈ 3 KB padded to 128×128 lanes) and
replicated into VMEM for every block.  F and K are zero/+big-padded to
the 128-lane width — padded centroids get ‖c‖² = +big so argmin never
selects them, and the fused kernel masks padded *rows* out of the
accumulators with a ``broadcasted_iota`` validity test.  Padding is
skipped entirely when shapes are already lane-aligned and otherwise uses
a single ``jnp.pad`` (one HLO pad op that fuses under jit — the
historical ``zeros().at[].set()`` materialized an O(N·Fp) copy chain).

``block_n`` is autotunable: :func:`autotune_block_n` sweeps a small
deterministic candidate set on a capped probe shape and caches the
winner per (shape, precision, backend) — the DES ``--profile`` workflow
applied to the kernel grid.

Validated in interpret mode against kernels/ref.py (assignment,
fused-update and int8 oracles); ``interpret=None`` (the default) compiles
the kernel on a TPU backend and interprets it elsewhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import quant, resolve_interpret

BIG = 1e30
PRECISIONS = ("fp32", "bf16", "int8")

# autotune: candidate block sizes (all multiples of the fp32/bf16/int8
# sublane minimums) and the per-(shape, precision, backend) winner cache
AUTOTUNE_CANDIDATES = (128, 256, 512)
_autotune_cache: dict = {}


def _pad2(a, rows: int, cols: int, value=0):
    """Pad a 2-D array up to (rows, cols) — a no-op when already aligned,
    otherwise one fusable ``jnp.pad`` (never an at[].set() copy chain)."""
    n, f = a.shape
    if n == rows and f == cols:
        return a
    return jnp.pad(a, ((0, rows - n), (0, cols - f)),
                   constant_values=value)


def _make_kernel(n: int, block_n: int, quantized: bool, fused: bool):
    """Build the grid kernel body.  ``n`` (static) is the true row count
    — the fused accumulators mask padded tail rows with it.

    Both MXU dots run at ``Precision.HIGHEST``: the distance expansion
    cancels terms ~10³ times an inlier's distance, and at the default
    (one bf16 pass) precision the fp32 kernel lost 0.6% of assignments
    against a float64 argmin on a TPU v5e.  The TPU compiler takes
    ``HIGHEST`` on f32 operands only, so every storage dtype is widened
    to f32 in VMEM; one code path serves all three precisions."""

    def kernel(*refs):
        if quantized:
            pts_ref, cent_ref, scale_ref, c2_ref, *out = refs
        else:
            pts_ref, cent_ref, c2_ref, *out = refs
        if fused:
            ids_ref, dmin_ref, sums_ref, counts_ref = out
        else:
            ids_ref, dmin_ref = out

        x32 = pts_ref[...].astype(jnp.float32)
        cm = cent_ref[...].astype(jnp.float32)
        if quantized:
            s = scale_ref[...]                        # (1, Fp) f32
            x32 = x32 * s
            cm = cm * s
        c2 = c2_ref[...]                              # (1, Kp) f32
        x2 = jnp.sum(x32 * x32, axis=1, keepdims=True)
        xc = jax.lax.dot_general(x32, cm, (((1,), (1,)), ((), ())),
                                 precision=jax.lax.Precision.HIGHEST,
                                 preferred_element_type=jnp.float32)
        d2 = jnp.maximum(x2 - 2.0 * xc + c2, 0.0)     # (bn, Kp)
        ids = jnp.argmin(d2, axis=1).astype(jnp.int32)
        ids_ref[...] = ids[:, None]
        dmin_ref[...] = jnp.sqrt(jnp.min(d2, axis=1))[:, None]

        if not fused:
            return
        i = pl.program_id(0)
        kp = c2.shape[1]
        # in-register one-hot membership; padded tail rows (>= n) are
        # masked out so they never reach the accumulators
        rows = i * block_n + jax.lax.broadcasted_iota(
            jnp.int32, (block_n, kp), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (block_n, kp), 1)
        onehot = jnp.where((rows < n) & (ids[:, None] == cols),
                           1.0, 0.0).astype(jnp.float32)
        # (Kp, bn) @ (bn, Fp) on the MXU: this block's per-centroid sums
        bs = jax.lax.dot_general(onehot, x32, (((0,), (0,)), ((), ())),
                                 precision=jax.lax.Precision.HIGHEST,
                                 preferred_element_type=jnp.float32)
        bc = jnp.sum(onehot, axis=0, keepdims=True)   # (1, Kp)

        # the accumulator outputs have a constant index_map, so their
        # blocks stay resident in VMEM across the sequential grid steps:
        # initialize on the first block, accumulate on the rest
        @pl.when(i == 0)
        def _init():
            sums_ref[...] = bs
            counts_ref[...] = bc

        @pl.when(i > 0)
        def _acc():
            sums_ref[...] += bs
            counts_ref[...] += bc

    return kernel


def _call(points, centroids, *, block_n: int, interpret,
          precision: str, fused: bool):
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}, "
                         f"got {precision!r}")
    n, f = points.shape
    k = centroids.shape[0]
    fp = max(128, -(-f // 128) * 128)
    kp = max(128, -(-k // 128) * 128)
    np_ = -(-n // block_n) * block_n

    ptsf = points.astype(jnp.float32)
    centf = centroids.astype(jnp.float32)
    extra = []
    if precision == "int8":
        scales = quant.symmetric_scales(ptsf, centf)
        pts = _pad2(quant.quantize(ptsf, scales), np_, fp)
        qc = quant.quantize(centf, scales)
        cent = _pad2(qc, kp, fp)
        # c2 from the *rounded* centroid values the kernel dequantizes
        centv = quant.dequantize(qc, scales)
        extra = [jnp.pad(scales, (0, fp - f))[None, :]
                 if f != fp else scales[None, :]]
    elif precision == "bf16":
        pts = _pad2(ptsf, np_, fp).astype(jnp.bfloat16)
        # c2 must come from the rounded centroids the kernel holds
        centv = quant.round_to_bf16(centf)
        cent = _pad2(centv, kp, fp).astype(jnp.bfloat16)
    else:
        pts = _pad2(ptsf, np_, fp)
        cent = _pad2(centf, kp, fp)
        centv = centf
    c2v = jnp.sum(centv * centv, axis=1)[None, :]     # (1, k)
    c2 = (jnp.pad(c2v, ((0, 0), (0, kp - k)), constant_values=BIG)
          if k != kp else c2v)

    nb = np_ // block_n
    in_specs = [pl.BlockSpec((block_n, fp), lambda i: (i, 0)),
                pl.BlockSpec((kp, fp), lambda i: (0, 0))]
    if extra:
        in_specs.append(pl.BlockSpec((1, fp), lambda i: (0, 0)))
    in_specs.append(pl.BlockSpec((1, kp), lambda i: (0, 0)))
    out_specs = [pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
                 pl.BlockSpec((block_n, 1), lambda i: (i, 0))]
    out_shape = [jax.ShapeDtypeStruct((np_, 1), jnp.int32),
                 jax.ShapeDtypeStruct((np_, 1), jnp.float32)]
    if fused:
        out_specs += [pl.BlockSpec((kp, fp), lambda i: (0, 0)),
                      pl.BlockSpec((1, kp), lambda i: (0, 0))]
        out_shape += [jax.ShapeDtypeStruct((kp, fp), jnp.float32),
                      jax.ShapeDtypeStruct((1, kp), jnp.float32)]

    res = pl.pallas_call(
        _make_kernel(n, block_n, bool(extra), fused),
        grid=(nb,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=resolve_interpret(interpret),
    )(pts, cent, *extra, c2)
    if fused:
        ids, dmin, sums, counts = res
        return ids[:n, 0], dmin[:n, 0], sums[:k, :f], counts[0, :k]
    ids, dmin = res
    return ids[:n, 0], dmin[:n, 0]


@functools.partial(jax.jit,
                   static_argnames=("block_n", "interpret", "precision"))
def kmeans_assign(points, centroids, *, block_n: int = 256,
                  interpret=None, precision: str = "fp32"):
    """points (N,F), centroids (K,F) -> (ids (N,) int32, dmin (N,) f32)."""
    return _call(points, centroids, block_n=block_n, interpret=interpret,
                 precision=precision, fused=False)


@functools.partial(jax.jit,
                   static_argnames=("block_n", "interpret", "precision"))
def kmeans_assign_update(points, centroids, *, block_n: int = 256,
                         interpret=None, precision: str = "fp32"):
    """The fused hot path: one grid pass returns
    ``(ids (N,), dmin (N,), sums (K,F) f32, counts (K,) f32)`` — the
    assignment *and* the per-centroid membership sums/counts a mini-batch
    k-means step needs, with no second pass over the points."""
    return _call(points, centroids, block_n=block_n, interpret=interpret,
                 precision=precision, fused=True)


def autotune_block_n(n: int, f: int, k: int, *, precision: str = "fp32",
                     interpret=None, candidates=AUTOTUNE_CANDIDATES,
                     probe_n: int = 4096, repeats: int = 2, timer=None):
    """Pick the fastest ``block_n`` for a (n, f, k) shape: a small
    deterministic sweep over ``candidates``, each timed ``repeats`` times
    on a ``min(n, probe_n)``-row probe after a warmup call, cached per
    (probe shape, precision, backend).  The sweep order and candidate set
    are fixed; only the wall-clock winner is host-dependent, which is why
    benchmark reports exclude the chosen ``block_n`` from their
    deterministic columns."""
    import time as _time

    interpret = resolve_interpret(interpret)
    pn = min(n, probe_n)
    key = (pn, f, k, precision, interpret, jax.default_backend())
    hit = _autotune_cache.get(key)
    if hit is not None:
        return hit
    timer = timer or _time.perf_counter
    # deterministic probe data (values don't matter for timing)
    pts = jnp.linspace(-5.0, 5.0, pn * f, dtype=jnp.float32
                       ).reshape(pn, f)
    cent = jnp.linspace(-5.0, 5.0, k * f, dtype=jnp.float32
                        ).reshape(k, f)
    best, best_t = None, None
    for c in candidates:
        run = functools.partial(kmeans_assign_update, pts, cent,
                                block_n=c, interpret=interpret,
                                precision=precision)
        jax.block_until_ready(run())              # warm the compile cache
        t = []
        for _ in range(max(repeats, 1)):
            t0 = timer()
            jax.block_until_ready(run())
            t.append(timer() - t0)
        tm = min(t)
        if best_t is None or tm < best_t:
            best, best_t = c, tm
    _autotune_cache[key] = best
    return best
