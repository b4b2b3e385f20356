"""Plain reference for kmeans-paper, and its control.

Streaming mini-batch k-means (Sculley 2010) as the paper's handler runs
it, written from the algorithm and importing nothing of the program:

* the model starts from ``n_clusters`` points of the first message, drawn
  without replacement by ``numpy.random.default_rng(model_seed)``;
* each message is scored by the distance of every point to its nearest
  centroid, ``|x|^2 - 2 x.c + |c|^2`` with the product in float32 at
  ``Precision.HIGHEST``, as the configuration states;
* each message then moves every centroid to its members' mean at the
  learning rate ``batch count / total count``;
* the handler answers with the mean score and the number of points
  above ``mean + 3 std`` of the message's scores; the mean score is
  compared, and so is the model last published.

The replay follows the handler calls in the order the benchmark recorded
them, as one ``lax.scan`` over the pool on the device.  Each call starts
from the model the program published after the call before it, so every
answer and every published model is held to one step of the reference:
a rounding that flips a point near a tie between two centroids moves
that step by one point's weight and is not carried on, where a replay
that ran on from its own model would follow the flip into another
trajectory.

The control computes every product with the three bf16 passes of
``Precision.HIGH`` (``hi*hi + hi*lo + lo*hi``), done explicitly so that
it means the same on every backend.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _bf16(a):
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _dot(a, b, control: bool):
    if not control:
        return jnp.matmul(a, b, precision=HIGHEST)
    ah, bh = _bf16(a), _bf16(b)
    al, bl = _bf16(a - ah), _bf16(b - bh)
    return (jnp.matmul(ah, bh, precision=HIGHEST)
            + (jnp.matmul(ah, bl, precision=HIGHEST)
               + jnp.matmul(al, bh, precision=HIGHEST)))


def _step(c, counts, x, control: bool):
    k = c.shape[0]
    d2 = (jnp.sum(x * x, axis=1, keepdims=True)
          - 2.0 * _dot(x, c.T, control) + jnp.sum(c * c, axis=1)[None, :])
    d2 = jnp.maximum(d2, 0.0)
    ids = jnp.argmin(d2, axis=1)
    score = jnp.sqrt(jnp.min(d2, axis=1))
    member = (ids[:, None] == jnp.arange(k)[None, :]).astype(jnp.float32)
    n = member.sum(axis=0)
    sums = _dot(member.T, x, control)
    total = counts + n
    lr = jnp.where(n > 0, n / jnp.maximum(total, 1.0), 0.0)[:, None]
    c = c * (1.0 - lr) + (sums / jnp.maximum(n, 1.0)[:, None]) * lr
    return c, total, score


@partial(jax.jit, static_argnames=("control",))
def _chain(c0, pool, order, control: bool):
    def body(carry, i):
        c, counts = carry
        c, counts, s = _step(c, counts, pool[i], control)
        return (c, counts), (c, counts, jnp.mean(s))

    counts0 = jnp.zeros((c0.shape[0],), jnp.float32)
    _, out = jax.lax.scan(body, (c0, counts0), order)
    return out


@partial(jax.jit, static_argnames=("control",))
def _steps(c_in, counts_in, pool, order, control: bool):
    def body(_, xs):
        c, counts, i = xs
        c, counts, s = _step(c, counts, pool[i], control)
        return None, (c, counts, jnp.mean(s))

    _, out = jax.lax.scan(body, None, (c_in, counts_in, order))
    return out


def replay(config, model_seed, pool, order, seed, published,
           control=False) -> dict:
    """Expected answers of every call in ``order`` (pool indices) and the
    model each publishes, each call from ``published[i - 1]`` (the first
    from the seeded initial model).  Where ``published`` does not hold
    one model per call, the replay runs on from its own models."""
    k = config["model"]["n_clusters"]
    first = pool[order[0]]
    pick = np.random.default_rng(model_seed).choice(len(first), k,
                                                    replace=False)
    c0 = jnp.asarray(first[pick], jnp.float32)
    pool = jnp.asarray(pool, jnp.float32)
    order = jnp.asarray(order, jnp.int32)
    if published is not None and len(published) == len(order):
        c_in = np.stack([c0] + [p["centroids"] for p in published[:-1]])
        n_in = np.stack([np.zeros((k,), np.float32)]
                        + [p["counts"] for p in published[:-1]])
        out = _steps(jnp.asarray(c_in, jnp.float32),
                     jnp.asarray(n_in, jnp.float32), pool, order, control)
    else:
        out = _chain(c0, pool, order, control)
    cs, counts, mean = (np.asarray(a) for a in out)
    return {"answers": {i: {"mean_score": float(mean[i])}
                        for i in range(len(mean))},
            "published": [{"centroids": cs[i], "counts": counts[i]}
                          for i in range(len(mean))]}


def _step_gap(got, want, leaf: str) -> float:
    got = np.stack([p[leaf] for p in got]).astype(np.float64)
    want = np.stack([p[leaf] for p in want]).astype(np.float64)
    axes = tuple(range(1, want.ndim))
    norm = np.maximum(np.linalg.norm(want, axis=axes), 1e-30)
    return float((np.linalg.norm(got - want, axis=axes) / norm).max())


def compare(config, served, published, ref) -> dict:
    """The numbers held to the configuration's limits:

    * ``score_gap``: the largest relative gap of a call's mean score;
    * ``model_gap``: over every published model, the largest gap of a
      leaf (centroids, counts), the norm of the difference over the
      reference leaf's norm; 1 where the program did not publish one
      model per call.

    The outlier counts are not compared: the control reads as the
    program does on them (``PERF.md``).
    """
    gaps = [abs(served[i]["mean_score"] - want["mean_score"])
            / abs(want["mean_score"]) for i, want in ref["answers"].items()]
    want = ref["published"]
    model = (max(_step_gap(published, want, leaf)
                 for leaf in ("centroids", "counts"))
             if len(published) == len(want) else 1.0)
    return {"score_gap": float(np.max(gaps)), "model_gap": model}
