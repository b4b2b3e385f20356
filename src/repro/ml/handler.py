"""The served handler every detector shares: the model's lifecycle, the
host↔device protocol and the spans of the paper's model-update pattern
(the model is updated on the incoming data and shared through the
parameter service).

A detector supplies what is its own:

* ``first_state(points)`` — the state of a handler that finds no
  published model (``None`` when the first ``step`` makes it);
* ``step(state, x, train, metrics)`` — the device work on the float32
  points ``x``: returns the new state and the per-point outlier scores,
  both still on the device; ``metrics`` is the parameter service's
  registry, or ``None``;
* ``n_outliers(scores)`` — the host count of the scores above the
  detector's threshold.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.monitoring import span


def make_processor(detector, param_service=None, model_name: str = "model",
                   train: bool = True):
    """FaaS ``process_cloud`` handler of ``detector``: pick up a newer
    published model, step on the message, publish the new state when
    training, answer with the outlier count and the mean score."""
    holder = {"state": None, "version": 0}
    metrics = getattr(param_service, "metrics", None)

    def process_cloud(context, data=None):
        pts = np.asarray(data, np.float64)
        # versions start at 1, so the first call takes whatever is there
        newer = None if param_service is None else \
            param_service.fetch_if_newer(model_name, holder["version"])
        if newer is not None:
            holder["version"] = newer[0]
            holder["state"] = jax.tree.map(jnp.asarray, newer[1])
        elif holder["state"] is None:
            holder["state"] = detector.first_state(pts)
        # the points reach the device once, as float32; the scores' host
        # copy starts as the step is dispatched, so the pull finds them
        # on the host
        with span("pilot.step") as sp:
            x = jnp.asarray(pts, jnp.float32)
            sp.nbytes = x.nbytes
            holder["state"], scores = detector.step(holder["state"], x,
                                                    train, metrics)
            scores.copy_to_host_async()
        if train and param_service is not None:
            holder["version"] = param_service.publish(model_name,
                                                      holder["state"])
        with span("pilot.pull", nbytes=scores.nbytes):
            s = np.asarray(scores)
        return {"n_outliers": detector.n_outliers(s),
                "mean_score": float(s.mean())}

    return process_cloud
