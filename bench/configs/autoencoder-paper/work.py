"""Work of one autoencoder-paper message, counted from shapes.

The model has ``W`` kernel weights (11,264 at the published widths) and
``P`` parameters (11,552).  A message of ``N`` points of ``F`` float32
features holds out ``N - n_train`` and trains ``epochs`` epochs over
``n_train = int(N (1 - validation_size))``, in ``ceil(n_train /
batch_size)`` Adam steps an epoch.

* The fit (``_ae_train``): ``6W`` FLOPs per point per epoch (``2W``
  forward, ``4W`` backward) over ``n_train`` points and ``epochs``
  epochs; 12 elementwise operations per parameter per Adam step (3 for
  the first moment, 4 for the second, 5 for the update); ``2W`` per
  held-out point for the held-out loss.  Bytes: the training points read
  once per epoch (``4 n_train F``), the message read once, and the state
  (weights, both moments: ``3 × 4P``, and the step count) read and
  written once.
* The score (``_ae_score``): ``2W`` FLOPs per point; the message read
  and one score per point written.

Activations, biases and the loss's reductions are left out: they are
below a percent of the products.  At 10,000 x 32 the fit is about
6.5e10 FLOPs against 1.2e8 bytes, so it is bound by compute (at the bf16
peak, the repository's convention for every share).
"""
import math

ADAM_OPS_PER_PARAM = 12


def _shape(config):
    m, p = config["model"], config["pool"]
    f = m["n_features"]
    sizes = [f, f, f, *m["hidden"], f]
    weights = sum(a * b for a, b in zip(sizes[:-1], sizes[1:]))
    params = weights + sum(sizes[1:])
    n = p["n_points"]
    n_train = int(n * (1.0 - m["validation_size"]))
    return weights, params, n, f, n_train


def adam_steps(config) -> int:
    """Sequential Adam steps of one message's fit."""
    m = config["model"]
    _, _, _, _, n_train = _shape(config)
    return m["epochs"] * math.ceil(n_train / m["batch_size"])


def train(config):
    """``(flops, bytes)`` of one message's fit."""
    weights, params, n, f, n_train = _shape(config)
    epochs = config["model"]["epochs"]
    flops = (6 * weights * n_train * epochs
             + ADAM_OPS_PER_PARAM * params * adam_steps(config)
             + 2 * weights * (n - n_train))
    nbytes = 4 * n_train * f * epochs + 4 * n * f + 2 * (3 * 4 * params + 4)
    return float(flops), float(nbytes)


def score(config):
    """``(flops, bytes)`` of scoring one message."""
    weights, _, n, f, _ = _shape(config)
    return float(2 * weights * n), float(4 * n * f + 4 * n)


def message(config):
    """``(flops, bytes)`` of the whole handler for one message."""
    (a, b), (c, d) = train(config), score(config)
    return a + c, b + d
