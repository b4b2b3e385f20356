"""Isolation forest in JAX — the paper's mid-complexity workload (§III.2).

"Isolation forests [17] are an ensemble technique where each task partitions
the dataset randomly into trees. An outlier is defined by the number of steps
required to isolate a data point ... We use the PyOD [18] implementation and
a default of 100 ensemble tasks."

PyOD wraps sklearn's IsolationForest: 100 trees, subsample ψ=256,
max_depth=⌈log₂ψ⌉=8. We build the forest *vectorized*: trees are heap-layout
arrays (feature/threshold/leaf-size per node), constructed level-by-level
with masked segment min/max (no data-dependent recursion — JAX-native), and
vmapped over the 100 trees.

Scoring walks every tree for every point together, level by level, without
a gather.  On the TPU a data-dependent gather runs almost one index at a
time (about 10 ns each), so four per level over 100 trees x 10,000 points
cost a third of a second a message.  Instead each (tree, point) pair keeps
its index within the level, and the node's entries (feature and leaf flag
in one table, threshold or leaf size in another) are picked with a one-hot
compare against the level's ``2^d`` nodes, the point's value with one
against its ``F`` features.
Each pick is a select and a sum with one non-zero term, so it is exact and
the path lengths are those of a per-node descent, bit for bit.  The cost
per pair is about ``2^(max_depth+1)`` selects per table: fixed and small at
ψ ≤ 256 (max_depth 8), but doubling with every level, so a much larger ψ
would want another layout.

Anomaly score (Liu et al. 2008): s(x) = 2^(−E[h(x)]/c(ψ)), where h(x) is
path length + c(leaf_size) continuation, c(n) = 2H(n−1) − 2(n−1)/n.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.ml import handler

EULER_GAMMA = 0.5772156649015329


def _c(n):
    """Average unsuccessful-search path length in a BST of n nodes."""
    n = jnp.asarray(n, jnp.float32)
    h = jnp.log(jnp.maximum(n - 1.0, 1.0)) + EULER_GAMMA
    return jnp.where(n > 1.0, 2.0 * h - 2.0 * (n - 1.0) / n, 0.0)


def _build_tree(key, pts, max_depth: int):
    """One isolation tree over pts (psi, F) — heap arrays of size
    2^(max_depth+1)-1. Returns dict(feature, threshold, is_leaf, size).

    Level-synchronous construction with *segment* ops: each point knows its
    node; per-node min/max of the (randomly chosen) split feature are
    ``segment_min/max`` over node ids — O(psi) per level, no
    (psi × nodes × features) mask blow-up.
    """
    psi, F = pts.shape
    n_nodes = 2 ** (max_depth + 1) - 1
    first_leaf = 2 ** max_depth - 1          # nodes at the bottom level

    feature = jnp.zeros((n_nodes,), jnp.int32)
    threshold = jnp.zeros((n_nodes,), jnp.float32)
    is_leaf = jnp.zeros((n_nodes,), bool)
    size = jnp.zeros((n_nodes,), jnp.float32).at[0].set(psi)
    assign = jnp.zeros((psi,), jnp.int32)    # every point starts at root

    def level(d, carry):
        feature, threshold, is_leaf, size, assign, key = carry
        start = 2 ** d - 1
        width = 2 ** d
        key, kf, kt = jax.random.split(key, 3)
        local = assign - start
        valid = (local >= 0) & (local < width)
        seg = jnp.where(valid, local, width)             # invalid -> dump
        feat = jax.random.randint(kf, (width,), 0, F)    # per-node feature
        # each point's value of ITS node's split feature
        my_feat = feat[jnp.clip(local, 0, width - 1)]
        val = jnp.take_along_axis(pts, my_feat[:, None], 1)[:, 0]
        lo = jax.ops.segment_min(jnp.where(valid, val, jnp.inf), seg,
                                 num_segments=width + 1)[:width]
        hi = jax.ops.segment_max(jnp.where(valid, val, -jnp.inf), seg,
                                 num_segments=width + 1)[:width]
        counts = jax.ops.segment_sum(valid.astype(jnp.float32), seg,
                                     num_segments=width + 1)[:width]
        u = jax.random.uniform(kt, (width,))
        thr = lo + u * (hi - lo)
        # a node is splittable if >1 point and the chosen feature varies
        splittable = (counts > 1.0) & (hi > lo)
        node_ids = start + jnp.arange(width)
        feature = feature.at[node_ids].set(feat)
        threshold = threshold.at[node_ids].set(thr)
        is_leaf = is_leaf.at[node_ids].set(~splittable)
        # route points: left = 2i+1, right = 2i+2; points at leaves stay
        my_leaf = is_leaf[assign] | (assign < start)     # already settled
        go_left = val <= threshold[assign]
        child = jnp.where(go_left, 2 * assign + 1, 2 * assign + 2)
        new_assign = jnp.where(my_leaf | ~valid, assign, child)
        # record child sizes
        width2 = 2 * width
        start2 = 2 ** (d + 1) - 1
        local2 = new_assign - start2
        valid2 = (local2 >= 0) & (local2 < width2)
        seg2 = jnp.where(valid2, local2, width2)
        counts2 = jax.ops.segment_sum(valid2.astype(jnp.float32), seg2,
                                      num_segments=width2 + 1)[:width2]
        size = size.at[start2 + jnp.arange(width2)].set(counts2)
        return feature, threshold, is_leaf, size, new_assign, key

    carry = (feature, threshold, is_leaf, size, assign, key)
    for d in range(max_depth):          # static unroll: max_depth small (8)
        carry = level(d, carry)
    feature, threshold, is_leaf, size, assign, key = carry
    # bottom-level nodes are leaves by construction
    is_leaf = is_leaf.at[first_leaf:].set(True)
    return {"feature": feature, "threshold": threshold,
            "is_leaf": is_leaf, "size": size}


def _path_lengths(forest, x, max_depth: int):
    """Path lengths ``(trees, points)`` of points x (N, F) through every
    tree of the forest: the depth of the leaf a point reaches plus
    ``c(leaf size)``.

    The walk goes level by level over the static ``max_depth + 1`` levels.
    At level d each (tree, point) pair holds its index within the level,
    in ``[0, 2^d)``; a one-hot compare of that index against the level's
    ``2^d`` nodes selects the node's entries, and one against the ``F``
    feature ids selects the point's value.  The leaf flag is folded into
    the feature (-1 at a leaf) and the leaf size into the threshold, so a
    level takes two picks.  No gather runs, and XLA fuses each compare,
    select and sum into a reduce without materializing the one-hot.  A
    pair stops at the first leaf; the bottom level is a leaf by
    construction.
    """
    n_trees = forest["feature"].shape[0]
    n, n_features = x.shape
    shape = (n_trees, n)
    feature = jnp.where(forest["is_leaf"], -1, forest["feature"])
    split = jnp.where(forest["is_leaf"], forest["size"], forest["threshold"])
    pos = jnp.zeros(shape, jnp.int32)
    depth = jnp.zeros(shape, jnp.float32)
    leaf_size = jnp.zeros(shape, jnp.float32)
    done = jnp.zeros(shape, bool)
    x_by_feature = x.T[:, None, :]                                # (F, 1, N)
    feature_ids = jnp.arange(n_features, dtype=jnp.int32)[:, None, None]
    for d in range(max_depth + 1):
        start, width = 2 ** d - 1, 2 ** d
        hit = pos == jnp.arange(width, dtype=jnp.int32)[:, None, None]

        def pick(table, zero):          # each pair's node's entry: (T, N)
            level = table[:, start:start + width].T[:, :, None]  # (2^d, T, 1)
            return jnp.where(hit, level, zero).sum(0)

        if d == max_depth:
            stop, value = ~done, pick(forest["size"], 0.0)
        else:
            feat, value = pick(feature, 0), pick(split, 0.0)
            stop = (feat < 0) & ~done
        leaf_size = jnp.where(stop, value, leaf_size)
        depth = jnp.where(stop, jnp.float32(d), depth)
        done = done | stop
        if d < max_depth:
            val = jnp.where(feat == feature_ids, x_by_feature, 0.0).sum(0)
            pos = jnp.where(val <= value, 2 * pos, 2 * pos + 1)
    # the mean over trees reduces the finished walk in a fusion of its own,
    # so its summation order does not depend on how the levels fused
    depth, leaf_size = jax.lax.optimization_barrier((depth, leaf_size))
    return depth + _c(leaf_size)


@partial(jax.jit, static_argnames=("max_depth",))
def _score(forest, x, psi, max_depth: int):
    eh = _path_lengths(forest, x, max_depth).mean(0)
    return jnp.power(2.0, -eh / jnp.maximum(_c(psi), 1e-6))


@partial(jax.jit, static_argnames=("n_trees", "psi", "max_depth"))
def _fit(key, pts, n_trees: int, psi: int, max_depth: int):
    n = pts.shape[0]
    ks = jax.random.split(key, n_trees)

    def one(k):
        k1, k2 = jax.random.split(k)
        idx = jax.random.randint(k1, (psi,), 0, n)
        return _build_tree(k2, pts[idx], max_depth)

    return jax.vmap(one)(ks)


@dataclass
class IsolationForest:
    n_trees: int = 100
    psi: int = 256                 # subsample size (sklearn default)
    seed: int = 0

    @property
    def max_depth(self) -> int:
        return int(np.ceil(np.log2(self.psi)))

    def fit(self, points):
        pts = jnp.asarray(points, jnp.float32)
        psi = min(self.psi, pts.shape[0])
        forest = _fit(jax.random.key(self.seed), pts, self.n_trees,
                      psi, self.max_depth)
        return {"forest": forest, "psi": jnp.float32(psi)}

    def outlier_scores(self, state, points):
        pts = jnp.asarray(points, jnp.float32)
        return _score(state["forest"], pts, state["psi"], self.max_depth)

    def first_state(self, points):
        return None                     # the first step fits

    def step(self, state, x, train: bool, metrics):
        """Refit on the message when training (the paper's streaming
        model-update pattern: 100 ensemble tasks a message), then score
        it."""
        if train or state is None:
            state = self.fit(x)
        return state, self.outlier_scores(state, x)

    def n_outliers(self, scores) -> int:
        return int((scores > 0.6).sum())

    def make_processor(self, param_service=None, model_name: str = "iforest",
                       train: bool = True):
        return handler.make_processor(self, param_service, model_name, train)
