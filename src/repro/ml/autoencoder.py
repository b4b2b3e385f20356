"""Auto-encoder outlier detector — the paper's heaviest workload (§III.2).

"We use the Keras-based auto-encoder implementation of PyOD with four hidden
layers with a size of [64, 32, 32, 64], and thus, a total number of 11,552
parameters."

The model is PyOD's Keras ``AutoEncoder`` at its defaults:

* **Topology.**  PyOD prepends the input width to the hidden list and
  builds an input layer of that width first, so over ``F`` features the
  dense output widths are ``F, F, *hidden, F``: at F=32,
  32→32→32→64→32→32→64→32, i.e. widths 32, 32, 64, 32, 32, 64, 32::

      32→32 (1,056) + 32→32 (1,056) + 32→64 (2,112) + 64→32 (2,080)
      + 32→32 (1,056) + 32→64 (2,112) + 64→32 (2,080)  =  11,552

  ReLU on the six hidden layers, sigmoid on the output; Glorot-uniform
  kernels and zero biases.
* **Training.**  ``Dropout(0.2)`` after each hidden layer (inverted: keep
  with probability 0.8, scale by 1/0.8), an L2 activity regulariser of
  0.1 on every dense layer's output (``0.1 × Σ units a²``, averaged over
  the batch, as tf.keras scales it), MSE, and Keras' Adam (lr 1e-3, β
  0.9/0.999, ε 1e-7 added to √v, the bias correction folded into the step
  size).  ``epochs=100`` of shuffled minibatches of ``batch_size=32`` over
  the points a ``validation_size=0.1`` hold-out leaves; a short last batch
  is the mean over its own points, as Keras weights it.
* **Preprocessing.**  Each message is standardised with its own mean and
  population standard deviation, a zero deviation taken as 1.
* **Score.**  The L2 distance of a point to its reconstruction with
  dropout off (PyOD's ``decision_function``); outliers are the scores
  above the ``1 - contamination`` quantile of the message's scores.

A message's whole fit is one jitted program, ``_ae_train``: the hold-out,
every epoch's permutation, every dropout mask and all
``epochs × ceil(n_train / batch_size)`` Adam steps run on the device.
Each epoch's steps run inside one Pallas kernel, ``_ae_epoch``: the
weights and both Adam moments stay in VMEM across the epoch's batches, so
a fit is ``epochs`` kernel launches, not one XLA op per layer per step.
The forward and backward passes and Adam are written out by hand
(``_train_step``); the same step in a ``lax.scan`` (``kernel=False``) is
the XLA formulation that ``repro.cost`` prices.  The score is another
program, ``_ae_score``.  Every product is float32 at
``Precision.HIGHEST``.

**The key rule.**  Every random draw of a call comes from
``k = fold_in(key(seed), t)``, ``t`` the Adam step count of the state the
call starts from, so the draws follow from the published state alone:

* ``k_hold, k_epochs = split(k)``; ``permutation(k_hold, n)``: its first
  ``int(n (1 - validation_size))`` points are trained on, the rest held
  out;
* epoch ``e``: ``k_order, k_drop = split(fold_in(k_epochs, e))``; the
  epoch visits the training points in the order
  ``permutation(k_order, n_train)``, cut into batches;
* batch ``b``, hidden layer ``l``: the keep mask is
  ``bernoulli(fold_in(fold_in(k_drop, b), l), 1 - dropout_rate,
  (batch_size, width_l))``, its rows in the batch's order (a short batch
  uses its first rows).

The initial weights come from ``split(key(seed), n_layers)``, one key per
layer.  The model is warm-started across messages (the continuum's online
model); PyOD's ``fit`` starts fresh.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret
from repro.ml import handler

HIGHEST = jax.lax.Precision.HIGHEST

# Keras' Adam defaults
LR, BETA_1, BETA_2, EPSILON = 1e-3, 0.9, 0.999, 1e-7
# PyOD's AutoEncoder defaults
BATCH_SIZE, DROPOUT_RATE, L2_REGULARIZER = 32, 0.2, 0.1
VALIDATION_SIZE, CONTAMINATION = 0.1, 0.1


def _layer_sizes(n_features: int, hidden: Tuple[int, ...]):
    """Input width, then the output width of each dense layer (see the
    module doc): ``[F, F, F, *hidden, F]``."""
    return [n_features, n_features, n_features, *hidden, n_features]


def ae_init(key, n_features: int = 32,
            hidden: Tuple[int, ...] = (64, 32, 32, 64)):
    """Glorot-uniform kernels, zero biases."""
    sizes = _layer_sizes(n_features, hidden)
    params = []
    keys = jax.random.split(key, len(sizes) - 1)
    for k, din, dout in zip(keys, sizes[:-1], sizes[1:]):
        limit = np.sqrt(6.0 / (din + dout))
        w = jax.random.uniform(k, (din, dout), jnp.float32, -limit, limit)
        params.append({"w": w, "b": jnp.zeros((dout,), jnp.float32)})
    return params


def ae_param_count(params) -> int:
    return sum(int(np.prod(p["w"].shape)) + int(p["b"].shape[0])
               for p in params)


def _standardize(x):
    mu = jnp.mean(x, axis=0)
    sd = jnp.std(x, axis=0)
    return (x - mu) / jnp.where(sd == 0.0, 1.0, sd)


def _forward(params, x):
    """Reconstruction and every dense layer's output, dropout off."""
    h, acts = x, []
    last = len(params) - 1
    for i, p in enumerate(params):
        z = jnp.matmul(h, p["w"], precision=HIGHEST) + p["b"]
        h = jax.nn.sigmoid(z) if i == last else jax.nn.relu(z)
        acts.append(h)
    return h, acts


def _point_loss(params, x, l2: float):
    """Per point: the mean squared reconstruction error plus ``l2`` times
    the squared activity of every dense layer."""
    out, acts = _forward(params, x)
    loss = jnp.mean((out - x) ** 2, axis=1)
    for a in acts:
        loss = loss + l2 * jnp.sum(a * a, axis=1)
    return loss


class Schedule(NamedTuple):
    """What a fit does, fixed at trace time."""
    epochs: int
    batch_size: int
    n_train: int
    dropout_rate: float
    l2: float

    @property
    def n_batches(self) -> int:
        return -(-self.n_train // self.batch_size)

    @property
    def n_last(self) -> int:
        """Points in an epoch's last batch."""
        return self.n_train - (self.n_batches - 1) * self.batch_size


def _dot(a, b, contract):
    """``a · b`` over dims ``contract`` = ``(a's, b's)``, float32 at
    ``HIGHEST``."""
    return jax.lax.dot_general(a, b, (((contract[0],), (contract[1],)),
                                      ((), ())),
                               precision=HIGHEST,
                               preferred_element_type=jnp.float32)


def _adam(p, m, v, g, lr):
    """Keras' Adam on one leaf: ε added to √v, the bias correction folded
    into the step size ``lr``."""
    m = BETA_1 * m + (1.0 - BETA_1) * g
    v = BETA_2 * v + (1.0 - BETA_2) * g * g
    return p - lr * m / (jnp.sqrt(v) + EPSILON), m, v


def _train_step(layers, lr, x, keeps, weight, s: Schedule):
    """One Adam step on the batch ``x``, by hand.  ``layers`` holds, per
    dense layer, ``(w, b, mu_w, mu_b, nu_w, nu_b)`` with the bias terms as
    ``(1, width)`` rows; ``keeps`` one 0/1 mask per hidden layer;
    ``weight`` each row's share of the batch's mean, ``(rows, 1)`` (0 on
    padding).  The loss is ``_point_loss`` with dropout, averaged by
    ``weight``.  Returns the new ``layers``."""
    keep_prob = 1.0 - s.dropout_rate
    last = len(layers) - 1
    ins, acts, h = [], [], x
    for i, (w, b, *_) in enumerate(layers):
        ins.append(h)
        z = _dot(h, w, (1, 0)) + b
        a = jax.nn.sigmoid(z) if i == last else jnp.maximum(z, 0.0)
        acts.append(a)
        if i < last:
            h = jnp.where(keeps[i] > 0.0, a / keep_prob, 0.0)

    def activity(a):
        return (2.0 * s.l2) * weight * a

    out = acts[last]
    dz = ((2.0 / x.shape[1]) * weight * (out - x) + activity(out)) \
        * out * (1.0 - out)
    new = [None] * len(layers)
    for i in range(last, -1, -1):
        w, b, mw, mb, vw, vb = layers[i]
        gw = _dot(ins[i], dz, (0, 0))
        gb = jnp.sum(dz, axis=0, keepdims=True)
        if i > 0:
            a = acts[i - 1]
            dh = jnp.where(keeps[i - 1] > 0.0,
                           _dot(dz, w, (1, 1)) / keep_prob, 0.0)
            dz = jnp.where(a > 0.0, dh + activity(a), 0.0)
        w, mw, vw = _adam(w, mw, vw, gw, lr)
        b, mb, vb = _adam(b, mb, vb, gb, lr)
        new[i] = (w, b, mw, mb, vw, vb)
    return new


def _row_weight(b, rows: int, s: Schedule):
    """Batch ``b``'s row weights: ``1 / n`` on its ``n`` points, 0 on the
    padding of the short last batch."""
    n = jnp.where(b == s.n_batches - 1, s.n_last, rows)
    real = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) < n
    return jnp.where(real, 1.0, 0.0) / n.astype(jnp.float32)


def _group(flat):
    return [tuple(flat[6 * i:6 * i + 6]) for i in range(len(flat) // 6)]


def _epoch_kernel(s: Schedule, n_hidden: int):
    def kernel(lr_ref, x_ref, *refs):
        # the masks, the state handed in, the state handed back
        n_state = (len(refs) - n_hidden) // 2
        keeps, state_in, state = (refs[:n_hidden],
                                  refs[n_hidden:n_hidden + n_state],
                                  refs[n_hidden + n_state:])
        b = pl.program_id(0)

        @pl.when(b == 0)
        def _():
            for src, dst in zip(state_in, state):
                dst[...] = src[...]

        x = x_ref[...]
        new = _train_step(_group([r[...] for r in state]), lr_ref[b], x,
                          [k[...] for k in keeps],
                          _row_weight(b, x.shape[0], s), s)
        for r, v in zip(state, (v for leaves in new for v in leaves)):
            r[...] = v
    return kernel


def _epoch_pallas(flat, lr, xe, keeps, s: Schedule, interpret):
    """One epoch's Adam steps in one kernel: batch ``b`` is grid step
    ``b``; the state's blocks stay in VMEM across the grid and are written
    back once."""
    n_in = 2 + len(keeps)

    def per_batch(a):
        return pl.BlockSpec((None, *a.shape[1:]), lambda b: (b, 0, 0))

    def whole(a):
        return pl.BlockSpec(a.shape, lambda b: (0,) * a.ndim)

    return pl.pallas_call(
        _epoch_kernel(s, len(keeps)),
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in flat],
        grid=(s.n_batches,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), per_batch(xe),
                  *map(per_batch, keeps), *map(whole, flat)],
        out_specs=[whole(a) for a in flat],
        input_output_aliases={n_in + i: i for i in range(len(flat))},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="_ae_epoch",
    )(lr, xe, *keeps, *flat)


def _epoch_scan(flat, lr, xe, keeps, s: Schedule):
    """The same epoch as a ``lax.scan`` over its batches."""
    def step(flat, batch):
        b, lr_b, xb, kb = batch
        new = _train_step(_group(flat), lr_b, xb, kb,
                          _row_weight(b, xb.shape[0], s), s)
        return [v for leaves in new for v in leaves], None
    flat, _ = jax.lax.scan(step, flat, (jnp.arange(s.n_batches), lr, xe,
                                        keeps))
    return flat


def _hold_out(x, key, step, n_train: int):
    """The training points, the held-out points and the epochs' key of
    the call that starts from Adam step ``step`` (the key rule)."""
    k_hold, k_epochs = jax.random.split(jax.random.fold_in(key, step))
    split = jax.random.permutation(k_hold, x.shape[0])
    return x[split[:n_train]], x[split[n_train:]], k_epochs


@jax.jit
def _ae_score(params, x):
    """Per-point L2 distance to the reconstruction, dropout off: the PyOD
    outlier score of the message ``x`` (standardised here)."""
    x = _standardize(x)
    out, _ = _forward(params, x)
    return jnp.sqrt(jnp.sum((out - x) ** 2, axis=1))


@partial(jax.jit, static_argnames=("schedule", "kernel", "interpret"))
def _ae_train(state, x, key, schedule: Schedule, kernel: bool = True,
              interpret=None):
    """One message's whole fit (the module doc's key rule); returns the
    new state.  ``kernel=False`` runs each epoch as a ``lax.scan``."""
    s = schedule
    bs, nb = s.batch_size, s.n_batches
    keep_prob = 1.0 - s.dropout_rate
    widths = [p["w"].shape[1] for p in state["params"][:-1]]
    x_train, _, k_epochs = _hold_out(_standardize(x), key, state["step"],
                                     s.n_train)
    # every epoch's draws up front, so that the loop over the epochs
    # runs a handful of device ops around each kernel
    k_order, k_drop = jax.vmap(lambda e: jax.random.split(
        jax.random.fold_in(k_epochs, e)))(jnp.arange(s.epochs)).T
    orders = jax.vmap(lambda k: jax.random.permutation(k, s.n_train))(k_order)
    orders = jnp.pad(orders, ((0, 0), (0, nb * bs - s.n_train)))

    def masks(k):
        def one(b):
            kb = jax.random.fold_in(k, b)
            return [jax.random.bernoulli(jax.random.fold_in(kb, l),
                                         keep_prob, (bs, w))
                    for l, w in enumerate(widths)]
        return jax.vmap(one)(jnp.arange(nb))

    ts = state["step"] + 1 + jnp.arange(s.epochs * nb).reshape(s.epochs, nb)
    ts = ts.astype(jnp.float32)
    lrs = LR * jnp.sqrt(1.0 - BETA_2 ** ts) / (1.0 - BETA_1 ** ts)

    def epoch(flat, drawn):
        order, keeps, lr = drawn
        xe = x_train[order].reshape(nb, bs, x.shape[1])
        keeps = [k.astype(jnp.float32) for k in keeps]
        if kernel:
            return list(_epoch_pallas(flat, lr, xe, keeps, s,
                                      resolve_interpret(interpret))), None
        return _epoch_scan(flat, lr, xe, keeps, s), None

    flat = [a.reshape(1, -1) if a.ndim == 1 else a
            for parts in zip(state["params"], state["mu"], state["nu"])
            for a in (parts[0]["w"], parts[0]["b"], parts[1]["w"],
                      parts[1]["b"], parts[2]["w"], parts[2]["b"])]
    flat, _ = jax.lax.scan(epoch, flat,
                           (orders, jax.vmap(masks)(k_drop), lrs))
    layers = _group(flat)
    tree = {part: [{"w": leaves[2 * j], "b": leaves[2 * j + 1].reshape(-1)}
                   for leaves in layers]
            for j, part in enumerate(("params", "mu", "nu"))}
    return dict(tree, step=state["step"] + s.epochs * nb)


@partial(jax.jit, static_argnames=("n_train", "l2"))
def _ae_held_loss(params, x, key, step, n_train: int, l2: float):
    """The loss over the points the call from Adam step ``step`` held
    out, dropout off."""
    _, held, _ = _hold_out(_standardize(x), key, step, n_train)
    return jnp.mean(_point_loss(params, held, l2))


@dataclass
class AutoEncoder:
    """PyOD's auto-encoder over ``n_features`` with ``hidden`` widths,
    trained ``epochs`` epochs a message; the other settings are PyOD's
    defaults, the module's constants."""
    n_features: int = 32
    hidden: Tuple[int, ...] = (64, 32, 32, 64)
    epochs: int = 100
    seed: int = 0

    def __post_init__(self):
        self._key = jax.random.key(self.seed)

    def schedule(self, n_points: int) -> Schedule:
        """The fit of a message of ``n_points``."""
        return Schedule(self.epochs, BATCH_SIZE,
                        int(n_points * (1.0 - VALIDATION_SIZE)),
                        DROPOUT_RATE, L2_REGULARIZER)

    def init(self):
        params = ae_init(self._key, self.n_features, self.hidden)
        zeros = jax.tree.map(jnp.zeros_like, params)
        return {"params": params, "mu": zeros,
                "nu": jax.tree.map(jnp.zeros_like, params),
                "step": jnp.zeros((), jnp.int32)}

    def update(self, state, points):
        """One message's fit; returns the new state and its loss over the
        points the fit held out."""
        x = jnp.asarray(points, jnp.float32)
        schedule = self.schedule(x.shape[0])
        new = _ae_train(state, x, self._key, schedule)
        held = _ae_held_loss(new["params"], x, self._key, state["step"],
                             schedule.n_train, schedule.l2)
        return new, float(held)

    def outlier_scores(self, state, points):
        return _ae_score(state["params"], jnp.asarray(points, jnp.float32))

    def first_state(self, points):
        return self.init()

    def step(self, state, x, train: bool, metrics):
        """Score the message with the held model, then fit on it when
        training; counts each fit's Adam steps and epochs in ``metrics``
        (``ae.adam_steps``, ``ae.epochs``)."""
        scores = _ae_score(state["params"], x)
        if train:
            schedule = self.schedule(len(x))
            state = _ae_train(state, x, self._key, schedule)
            if metrics is not None:
                metrics.incr("ae.adam_steps",
                             schedule.epochs * schedule.n_batches)
                metrics.incr("ae.epochs", schedule.epochs)
        return state, scores

    def n_outliers(self, scores) -> int:
        thresh = np.percentile(scores, 100.0 * (1.0 - CONTAMINATION))
        return int((scores > thresh).sum())

    def make_processor(self, param_service=None, model_name: str = "ae",
                       train: bool = True):
        return handler.make_processor(self, param_service, model_name, train)
