"""Plain reference for autoencoder-paper, and its control.

PyOD's Keras auto-encoder as the paper's handler trains it on every
message, written from Keras' and PyOD's definitions and importing
nothing of the program.  ``jax.numpy`` in float32, every product under
``jax.default_matmul_precision("highest")``:

* dense output widths ``F, F, *hidden, F`` (PyOD's input layer, then the
  hidden list with the input width prepended, then the output layer);
  ReLU, sigmoid on the output; Glorot-uniform kernels, zero biases, the
  kernels drawn from ``split(key(model_seed), n_layers)``;
* a message is standardised by its mean and population deviation (a zero
  deviation taken as 1), then scored by the L2 distance of each point to
  its reconstruction, dropout off, from the model held before the call;
  the answer is the mean score and the number of scores above the
  ``1 - contamination`` percentile;
* the fit: ``k = fold_in(key(model_seed), t)`` with ``t`` the Adam step
  count the call starts from; ``k_hold, k_epochs = split(k)``;
  ``permutation(k_hold, n)`` keeps its first ``int(n (1 -
  validation_size))`` points for training; epoch ``e`` draws
  ``k_order, k_drop = split(fold_in(k_epochs, e))``, visits the training
  points in ``permutation(k_order, n_train)`` order in batches of
  ``batch_size`` and, in batch ``b``, drops hidden layer ``l``'s units
  where ``bernoulli(fold_in(fold_in(k_drop, b), l), 1 - dropout_rate,
  (batch_size, width))`` is false (the first rows for a short batch),
  scaling the rest by ``1 / (1 - dropout_rate)``;
* a batch's loss is Keras': the mean squared error over the batch plus,
  for each dense layer, ``l2 × Σ a²`` over the batch and the layer's
  units (its output before dropout) divided by the batch's size, so a
  short last batch is the mean over its own points;
* Keras' Adam: ``m ← β1 m + (1 - β1) g``, ``v ← β2 v + (1 - β2) g²``,
  ``p ← p - lr √(1 - β2^t) / (1 - β1^t) · m / (√v + ε)``.

The full batches of an epoch are one ``lax.scan``, the short one a step
of its own; the epochs are a ``lax.scan`` around them, so a call's
replay runs on the device.

The replay follows the recorded calls, each from the state the program
published after the call before it, as the k-means reference does, so
that a rounding difference is held to one call's fit.  It replays the
first call (from the initial model: the only call scored by a model with
live hidden units once the activity regulariser has silenced them),
``check_sample`` calls drawn from the run's seed, and the last call.

The control takes every product, forward and backward, from operands
rounded to bfloat16, done explicitly so that it means the same on every
backend: what a float32 matmul at the TPU's default precision computes.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def _bf16(a):
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


@jax.custom_vjp
def _dot_bf16(a, b):
    return jnp.matmul(_bf16(a), _bf16(b))


def _dot_bf16_fwd(a, b):
    return _dot_bf16(a, b), (a, b)


def _dot_bf16_bwd(res, g):
    a, b = res
    return (jnp.matmul(_bf16(g), _bf16(b).T),
            jnp.matmul(_bf16(a).T, _bf16(g)))


_dot_bf16.defvjp(_dot_bf16_fwd, _dot_bf16_bwd)


def _widths(m) -> list:
    f = m["n_features"]
    return [f, f, *m["hidden"], f]


def _init(m, model_seed: int):
    sizes = [m["n_features"]] + _widths(m)
    keys = jax.random.split(jax.random.key(model_seed), len(sizes) - 1)
    layers = []
    for k, fan_in, fan_out in zip(keys, sizes[:-1], sizes[1:]):
        limit = float(np.sqrt(6.0 / (fan_in + fan_out)))
        layers.append({"w": jax.random.uniform(k, (fan_in, fan_out),
                                               jnp.float32, -limit, limit),
                       "b": jnp.zeros((fan_out,), jnp.float32)})
    zeros = jax.tree.map(jnp.zeros_like, layers)
    return {"params": layers, "mu": zeros, "nu": zeros,
            "step": jnp.zeros((), jnp.int32)}


def _standardize(x):
    sd = jnp.std(x, axis=0)
    return (x - jnp.mean(x, axis=0)) / jnp.where(sd == 0.0, 1.0, sd)


def _layers(params, x, drop, keep: float, control: bool):
    """Every dense layer's output; ``drop`` the keep masks of the hidden
    layers, or ``None``."""
    dot = _dot_bf16 if control else jnp.matmul
    outs, h = [], x
    for i, p in enumerate(params):
        z = dot(h, p["w"]) + p["b"]
        if i == len(params) - 1:
            h = 1.0 / (1.0 + jnp.exp(-z))
        else:
            h = jnp.where(z > 0.0, z, 0.0)      # derivative 0 at 0, as TF's
        outs.append(h)
        if drop is not None and i < len(params) - 1:
            h = h * drop[i].astype(jnp.float32) / keep
    return outs


def _batch_loss(params, xb, drop, m, control: bool):
    keep = 1.0 - m["dropout_rate"]
    outs = _layers(params, xb, drop, keep, control)
    mse = jnp.mean(jnp.square(outs[-1] - xb))
    activity = sum(jnp.sum(jnp.square(a)) for a in outs) / xb.shape[0]
    return mse + m["l2_regularizer"] * activity


def _adam(state, grads, opt):
    t = state["step"] + 1
    tf = t.astype(jnp.float32)
    b1, b2 = opt["beta_1"], opt["beta_2"]
    mu = jax.tree.map(lambda m, g: b1 * m + (1.0 - b1) * g,
                      state["mu"], grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1.0 - b2) * g * g,
                      state["nu"], grads)
    size = opt["lr"] * jnp.sqrt(1.0 - b2 ** tf) / (1.0 - b1 ** tf)
    params = jax.tree.map(
        lambda p, m, v: p - size * m / (jnp.sqrt(v) + opt["epsilon"]),
        state["params"], mu, nu)
    return {"params": params, "mu": mu, "nu": nu, "step": t}


def _masks(k_drop, b, rows: int, m):
    kb = jax.random.fold_in(k_drop, b)
    return [jax.random.bernoulli(jax.random.fold_in(kb, l),
                                 1.0 - m["dropout_rate"],
                                 (m["batch_size"], w))[:rows]
            for l, w in enumerate(_widths(m)[:-1])]


@partial(jax.jit, static_argnames=("m", "opt", "control"))
def _call(state, seed_key, raw, m, opt, control: bool):
    """One handler call: the scores from ``state``'s model, then the fit
    of ``epochs`` epochs from ``state``."""
    m, opt = dict(m), dict(opt)
    x = _standardize(raw)
    recon = _layers(state["params"], x, None, 1.0, control)[-1]
    scores = jnp.sqrt(jnp.sum(jnp.square(recon - x), axis=1))

    n = x.shape[0]
    n_train = int(n * (1.0 - m["validation_size"]))
    bs = m["batch_size"]
    full, rest = divmod(n_train, bs)
    k_hold, k_epochs = jax.random.split(
        jax.random.fold_in(seed_key, state["step"]))
    train = x[jax.random.permutation(k_hold, n)[:n_train]]
    grad = jax.grad(_batch_loss)

    def step(st, xb, drop):
        return _adam(st, grad(st["params"], xb, drop, m, control), opt)

    def epoch(st, e):
        k_order, k_drop = jax.random.split(jax.random.fold_in(k_epochs, e))
        xs = train[jax.random.permutation(k_order, n_train)]

        def one(st, b):
            xb = jax.lax.dynamic_slice_in_dim(xs, b * bs, bs)
            return step(st, xb, _masks(k_drop, b, bs, m)), None

        st, _ = jax.lax.scan(one, st, jnp.arange(full))
        if rest:
            st = step(st, xs[full * bs:], _masks(k_drop, full, rest, m))
        return st, None

    state, _ = jax.lax.scan(epoch, state, jnp.arange(m["epochs"]))
    return scores, state


def _frozen(d: dict):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in d.items()))


def _answer(scores, m) -> dict:
    s = np.asarray(scores, np.float64)
    thresh = np.percentile(s, 100.0 * (1.0 - m["contamination"]))
    return {"n_outliers": int((s > thresh).sum()),
            "mean_score": float(s.mean())}


def _sample(config, order, seed) -> list:
    """The calls replayed: the first, ``check_sample`` drawn from the
    seed, and the last."""
    last = len(order) - 1
    inner = list(range(1, last))
    k = min(config["check_sample"], len(inner))
    rng = np.random.default_rng([seed, 0xAE])
    picked = rng.choice(inner, k, replace=False).tolist() if k else []
    return sorted({0, last, *picked})


def replay(config, model_seed, pool, order, seed, published,
           control=False) -> dict:
    """Expected answers of the replayed calls in ``order`` (pool
    indices) and the state each publishes, each call from
    ``published[c - 1]`` (the first from the seeded initial state).
    Where ``published`` does not hold one state per call, every call is
    replayed, each from the state the replay itself reached."""
    m, opt = config["model"], config["optimizer"]
    mf, of = _frozen(m), _frozen(opt)
    seed_key = jax.random.key(model_seed)
    answers = {}
    states = [None] * len(order)
    chained = published is None or len(published) != len(order)
    with jax.default_matmul_precision("highest"):
        state = _init(m, model_seed)
        for c in (range(len(order)) if chained
                  else _sample(config, order, seed)):
            if not chained and c > 0:
                state = jax.tree.map(jnp.asarray, published[c - 1])
            scores, state = _call(state, seed_key,
                                  jnp.asarray(pool[order[c]], jnp.float32),
                                  mf, of, control)
            answers[c] = _answer(scores, m)
            states[c] = jax.tree.map(np.asarray, state)
    return {"answers": answers, "published": states}


# a leaf is judged against at least this share of its part's norm: the
# moments of a unit the regulariser silenced decay towards 0, and a
# relative gap over such a leaf would read its roundings as faults
LEAF_FLOOR = 1e-3


def _norm(a) -> float:
    return float(np.linalg.norm(np.ravel(a).astype(np.float64)))


def leaf_gaps(got, want) -> dict:
    """``‖got − ref‖ / max(‖ref‖, LEAF_FLOOR × ‖ref's part‖)`` of every
    leaf of a published state, keyed ``part/layer/leaf``; the step count
    as ``step``, exact."""
    gaps = {"step": float(int(got["step"]) != int(want["step"]))}
    for part in ("params", "mu", "nu"):
        floor = LEAF_FLOOR * _norm(np.concatenate(
            [np.ravel(a) for a in jax.tree.leaves(want[part])]))
        for i, (g_layer, w_layer) in enumerate(zip(got[part], want[part])):
            for leaf in ("w", "b"):
                w = np.asarray(w_layer[leaf], np.float64)
                diff = _norm(np.asarray(g_layer[leaf], np.float64) - w)
                scale = max(_norm(w), floor)
                gaps[f"{part}/{i}/{leaf}"] = (diff / scale if scale > 0
                                              else float(diff > 0))
    return gaps


def compare(config, served, published, ref) -> dict:
    """The numbers held to the configuration's limits, over the replayed
    calls:

    * ``score_gap``: the largest relative gap of a call's mean score;
    * ``model_gap``: the largest ``leaf_gaps`` of a state a call
      published, over its leaves; 1 where the program did not publish
      one state per call.

    The outlier count is not compared: at the 90th percentile of a
    message's distinct scores it is 1,000 of 10,000 whatever the model.
    """
    score = 0.0
    for c, want in ref["answers"].items():
        got = served[c]
        score = max(score, abs(got["mean_score"] - want["mean_score"])
                    / abs(want["mean_score"]))
    model = 0.0
    for c, want in enumerate(ref["published"]):
        if want is None:
            continue
        if c >= len(published):
            model = 1.0
            break
        model = max(model, *leaf_gaps(published[c], want).values())
    if len(published) != len(ref["published"]):
        model = 1.0
    return {"score_gap": float(score), "model_gap": float(model)}
