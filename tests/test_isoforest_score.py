"""The isolation forest's gather-free score against the per-node descent.

``_score`` walks the trees level by level and looks nodes and features up
with one-hot selects.  The oracle below is the descent it replaced: one
tree at a time under ``vmap``, a ``fori_loop`` over the levels, and a
gather per table and level.  Every pick of the walk is exact, so path
lengths and scores must agree bit for bit.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ml.isoforest import IsolationForest, _c, _path_lengths, _score

N_TREES = 16


def _oracle_path_length(tree, x, max_depth: int):
    """Expected path length of points x (N,F) through one tree."""
    n = x.shape[0]

    def step(d, carry):
        node, depth, done = carry
        feat = tree["feature"][node]
        thr = tree["threshold"][node]
        leaf = tree["is_leaf"][node]
        newly_done = leaf & ~done
        go_left = jnp.take_along_axis(x, feat[:, None], 1)[:, 0] <= thr
        child = jnp.where(go_left, 2 * node + 1, 2 * node + 2)
        node = jnp.where(leaf | done, node, child)
        depth = jnp.where(done | newly_done, depth, depth + 1)
        return node, depth, done | newly_done

    node = jnp.zeros((n,), jnp.int32)
    depth = jnp.zeros((n,), jnp.float32)
    done = jnp.zeros((n,), bool)
    node, depth, done = jax.lax.fori_loop(0, max_depth, step,
                                          (node, depth, done))
    leaf_size = tree["size"][node]
    return depth + _c(leaf_size)


@partial(jax.jit, static_argnames=("max_depth",))
def _oracle_path_lengths(forest, x, max_depth: int):
    return jax.vmap(lambda t: _oracle_path_length(t, x, max_depth))(forest)


@partial(jax.jit, static_argnames=("max_depth",))
def _oracle_score(forest, x, psi, max_depth: int):
    pl = jax.vmap(lambda t: _oracle_path_length(t, x, max_depth))(forest)
    eh = pl.mean(0)
    return jnp.power(2.0, -eh / jnp.maximum(_c(psi), 1e-6))


def _data(n: int, kind: str, seed: int):
    """Training points and the points scored: the training points plus as
    many again drawn wider than the training range."""
    rng = np.random.default_rng(seed)
    train = rng.normal(size=(n, 32)).astype(np.float32)
    if kind == "duplicates_and_constant":
        # a third of the rows repeat one row, and four features are constant:
        # nodes go unsplittable and points stop above the bottom level
        train[: n // 3] = train[n // 3]
        train[:, 4:8] = 1.5
    outside = (rng.normal(size=(n, 32)) * 8.0 + 3.0).astype(np.float32)
    return train, np.concatenate([train, outside])


@pytest.mark.parametrize("kind", ["plain", "duplicates_and_constant"])
@pytest.mark.parametrize("n", [25, 1500])
@pytest.mark.parametrize("psi", [32, 256])
def test_score_matches_per_node_descent_bit_for_bit(psi, n, kind):
    train, points = _data(n, kind, seed=psi + n)
    model = IsolationForest(n_trees=N_TREES, psi=psi, seed=n)
    state = model.fit(train)
    forest = state["forest"]
    # and one point per tree that sits on its root's threshold in every
    # feature: a tie, which goes left
    ties = np.repeat(np.asarray(forest["threshold"])[:, :1], 32, axis=1)
    x = jnp.asarray(np.concatenate([points, ties]))
    assert (np.abs(points) > np.abs(train).max()).any()

    want = np.asarray(_oracle_path_lengths(forest, x, model.max_depth))
    got = np.asarray(jax.jit(_path_lengths, static_argnames="max_depth")(
        forest, x, model.max_depth))
    assert np.array_equal(got, want)
    if kind == "duplicates_and_constant":
        assert (want < model.max_depth).any()   # some pairs stop early

    want = np.asarray(_oracle_score(forest, x, state["psi"], model.max_depth))
    got = np.asarray(_score(forest, x, state["psi"], model.max_depth))
    assert np.array_equal(got, want)
