"""The seeded message pool the edge devices hand out in turn.

A copy of ``repro.ml.datagen.MiniAppGenerator``'s mixture (the paper's
MiniApp data): ``n_clusters`` centres uniform in a box of half-width
``spread``, Gaussian points of ``cluster_std`` around them, and a share
``outlier_frac`` of each message replaced by points uniform in a box four
times as wide.  It is drawn in bulk before the window, so generation does
not compete with the system for the host's cores.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def make_pool(seed: int, *, n_messages: int, n_points: int,
              n_features: int, n_clusters: int, outlier_frac: float,
              cluster_std: float, spread: float) -> np.ndarray:
    """``(n_messages, n_points, n_features)`` float64, C-contiguous."""
    rng = np.random.default_rng([seed, 0x9001])
    centers = rng.uniform(-spread, spread, size=(n_clusters, n_features))
    which = rng.integers(0, n_clusters, size=(n_messages, n_points))
    pool = centers[which]
    pool += rng.normal(0.0, cluster_std, size=pool.shape)
    n_out = int(round(outlier_frac * n_points))
    if n_out:
        idx = np.argsort(rng.random((n_messages, n_points)), axis=1)[:, :n_out]
        rows = np.arange(n_messages)[:, None]
        pool[rows, idx] = rng.uniform(-4 * spread, 4 * spread,
                                      size=(n_messages, n_out, n_features))
    return np.ascontiguousarray(pool)


def fingerprints(pool: np.ndarray) -> Dict[bytes, int]:
    """First row's bytes -> pool index: how the handler wrapper tells
    which pool message a deserialized payload is."""
    index = {pool[i, 0].tobytes(): i for i in range(len(pool))}
    if len(index) != len(pool):
        raise ValueError("pool messages share a first row")
    return index
