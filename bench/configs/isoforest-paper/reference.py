"""Plain reference for isoforest-paper, and its control.

An isolation forest (Liu, Ting and Zhou 2008) refit on every message, as
the paper's handler runs it, written from the algorithm and importing
nothing of the program.  The random choices are those the handler's
seed defines, drawn with ``jax.random`` in the same order:

* the forest key ``jax.random.key(model_seed)`` splits into one key per
  tree, and each tree's key into a subsample key and a build key;
* a tree draws ``psi`` row indices with replacement, then, level by
  level, splits its build key in three and draws one feature
  (``randint``) and one uniform per node of the level;
* a node with more than one point whose feature varies splits at
  ``lo + u (hi - lo)`` of its points' values; points at or below go
  left.  The other nodes, and the bottom level, are leaves;
* a point's path length is its depth at the leaf it reaches plus
  ``c(leaf size)``, ``c(n) = 2 (ln(n - 1) + gamma) - 2 (n - 1) / n``; its
  score is ``2 ** (-mean path / c(psi))``;
* the handler answers with the mean score and the number of scores
  above 0.6.  Only the mean score is compared: on the count the control
  reads as the program does (``PERF.md``).

Here a level's per-node minimum, maximum and count are taken over a
point-by-node membership mask, where the program scatters by node id.

The forest depends only on the message, so the replay refits the forests
of a sample of the pool drawn from the run's seed, and of the last
message served, and every call on one of them is compared.

The control is the same reference computed in bfloat16: points, split
values and thresholds.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

EULER_GAMMA = 0.5772156649015329


def _c(n):
    n = jnp.asarray(n, jnp.float32)
    return jnp.where(n > 1.0, 2.0 * (jnp.log(jnp.maximum(n - 1.0, 1.0))
                                     + EULER_GAMMA) - 2.0 * (n - 1.0) / n,
                     0.0)


def _tree(key, pts, psi: int, depth: int):
    n, f = pts.shape
    k_rows, key = jax.random.split(key)
    sub = pts[jax.random.randint(k_rows, (psi,), 0, n)]
    n_nodes = 2 ** (depth + 1) - 1
    feature = jnp.zeros((n_nodes,), jnp.int32)
    threshold = jnp.zeros((n_nodes,), pts.dtype)
    leaf = jnp.zeros((n_nodes,), bool)
    size = jnp.zeros((n_nodes,), jnp.float32).at[0].set(psi)
    node = jnp.zeros((psi,), jnp.int32)
    for d in range(depth):
        key, k_feat, k_u = jax.random.split(key, 3)
        first, width = 2 ** d - 1, 2 ** d
        ids = first + jnp.arange(width)
        feat = jax.random.randint(k_feat, (width,), 0, f)
        u = jax.random.uniform(k_u, (width,)).astype(pts.dtype)
        member = node[:, None] == ids[None, :]              # (psi, width)
        vals = sub[:, feat]                                 # (psi, width)
        lo = jnp.min(jnp.where(member, vals, jnp.inf), axis=0)
        hi = jnp.max(jnp.where(member, vals, -jnp.inf), axis=0)
        thr = lo + u * (hi - lo)
        split = (member.sum(axis=0) > 1) & (hi > lo)
        feature = feature.at[ids].set(feat)
        threshold = threshold.at[ids].set(thr)
        leaf = leaf.at[ids].set(~split)
        j = jnp.clip(node - first, 0, width - 1)
        here = member.any(axis=1)
        left = jnp.take_along_axis(vals, j[:, None], axis=1)[:, 0] <= thr[j]
        node = jnp.where(here & split[j],
                         jnp.where(left, 2 * node + 1, 2 * node + 2), node)
        kids = 2 ** (d + 1) - 1 + jnp.arange(2 * width)
        size = size.at[kids].set(
            (node[:, None] == kids[None, :]).sum(axis=0).astype(jnp.float32))
    leaf = leaf.at[2 ** depth - 1:].set(True)
    return {"feature": feature, "threshold": threshold, "is_leaf": leaf,
            "size": size}


def _path(tree, x, depth: int):
    n = x.shape[0]
    node = jnp.zeros((n,), jnp.int32)
    h = jnp.zeros((n,), jnp.float32)
    done = jnp.zeros((n,), bool)
    rows = jnp.arange(n)
    for _ in range(depth):
        stop = done | tree["is_leaf"][node]
        left = x[rows, tree["feature"][node]] <= tree["threshold"][node]
        h = jnp.where(stop, h, h + 1.0)
        node = jnp.where(stop, node, jnp.where(left, 2 * node + 1,
                                               2 * node + 2))
        done = stop
    return h + _c(tree["size"][node])


@partial(jax.jit, static_argnames=("n_trees", "psi", "depth", "control"))
def _forest_and_scores(key, pts, n_trees: int, psi: int, depth: int,
                       control: bool):
    x = pts.astype(jnp.bfloat16) if control else pts
    psi = min(psi, x.shape[0])
    keys = jax.random.split(key, n_trees)
    forest = jax.vmap(lambda k: _tree(k, x, psi, depth))(keys)
    mean_path = jax.vmap(lambda t: _path(t, x, depth))(forest).mean(axis=0)
    score = jnp.power(2.0, -mean_path / _c(psi))
    return forest, score


def _depth(psi: int) -> int:
    return int(np.ceil(np.log2(psi)))


def replay(config, model_seed, pool, order, seed, published,
           control=False) -> dict:
    """Expected answers of the calls in ``order`` (pool indices) whose
    message is in the sample, and the forest the last call publishes.
    A forest depends on its message alone, so ``published`` is not
    read."""
    m = config["model"]
    distinct = sorted(set(order))
    k = min(config["check_sample"], len(distinct))
    rng = np.random.default_rng([seed, 0x1F])
    sample = set(rng.choice(distinct, k, replace=False).tolist())
    sample.add(order[-1])
    key = jax.random.key(model_seed)
    want, forests = {}, {}
    for i in sorted(sample):
        forest, score = _forest_and_scores(
            key, jnp.asarray(pool[i], jnp.float32), m["n_trees"], m["psi"],
            _depth(m["psi"]), control)
        want[i] = {"mean_score": float(np.asarray(score).mean())}
        forests[i] = jax.tree.map(np.asarray, forest)
    return {"answers": {c: want[i] for c, i in enumerate(order)
                        if i in want},
            "published": [{"forest": forests[order[-1]]}]}


def compare(config, served, published, ref) -> dict:
    """The numbers held to the configuration's limits:

    * ``score_gap``: the largest relative difference in a message's
      mean score;
    * ``model_gap``, over the last published forest: the larger of the
      share of nodes whose feature, leaf flag or size differ and the
      largest threshold difference at a split node, relative to the
      largest threshold there; 1 where nothing was published.
    """
    score = 0.0
    for i, want in ref["answers"].items():
        score = max(score, abs(served[i]["mean_score"] - want["mean_score"])
                    / abs(want["mean_score"]))
    if not published:
        return {"score_gap": score, "model_gap": 1.0}
    got, want = published[-1]["forest"], ref["published"][-1]["forest"]
    differ = ((np.asarray(got["feature"]) != want["feature"])
              | (np.asarray(got["is_leaf"]) != want["is_leaf"])
              | (np.asarray(got["size"]) != want["size"]))
    split = ~want["is_leaf"]
    thr_want = np.asarray(want["threshold"], np.float64)[split]
    thr_got = np.asarray(got["threshold"], np.float64)[split]
    thr_gap = (float(np.abs(thr_got - thr_want).max()
                     / max(np.abs(thr_want).max(), 1e-30))
               if thr_want.size else 0.0)
    return {"score_gap": score,
            "model_gap": max(float(differ.mean()), thr_gap)}
