"""The parameter service's publish and the handlers' early score copy.

``ParameterService.publish`` starts every device leaf's host copy before
it reads any, and the auto-encoder and k-means handlers start the
scores' copy as they dispatch the score.  These tests hold both to the
per-leaf copies they replace: the same host bytes, the same answers, a
writable snapshot, and every copy started before the first read.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.monitoring import MetricsRegistry
from repro.core.params_service import ParameterService
from repro.ml.autoencoder import (CONTAMINATION, AutoEncoder, _ae_score,
                                  _ae_train)
from repro.ml.isoforest import IsolationForest
from repro.ml.kmeans import KMeans

N_POINTS, N_FEATURES = 256, 32


def _per_leaf(tree):
    return jax.tree.map(lambda x: np.array(x, copy=True), tree)


def _assert_bit_identical(got, want):
    got_leaves, got_def = jax.tree.flatten(got)
    want_leaves, want_def = jax.tree.flatten(want)
    assert got_def == want_def
    for g, w in zip(got_leaves, want_leaves):
        assert isinstance(g, np.ndarray)
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()


def _counters(metrics):
    return (metrics.counter("params.leaves_published"),
            metrics.counter("params.leaves_copied_async"))


LEAVES = {
    "float32": lambda: jnp.linspace(-1.0, 1.0, 24).reshape(4, 6),
    "int32": lambda: jnp.arange(-7, 17, dtype=jnp.int32).reshape(2, 12),
    "bool": lambda: jnp.arange(10) % 3 == 0,
    "scalar": lambda: jnp.asarray(7, jnp.int32),
    "float32_scalar": lambda: jnp.float32(0.25),
}


@pytest.mark.parametrize("kind", sorted(LEAVES))
def test_publish_copies_device_leaves_bit_identical(kind):
    metrics = MetricsRegistry()
    ps = ParameterService(metrics=metrics)
    tree = {"a": LEAVES[kind](), "b": [LEAVES[kind]() * 2]}
    ps.publish("m", tree)
    _assert_bit_identical(ps.fetch("m")[1], _per_leaf(tree))
    assert _counters(metrics) == (2, 2)


def test_publish_of_a_mixed_tree_is_bit_identical():
    metrics = MetricsRegistry()
    ps = ParameterService(metrics=metrics)
    tree = {k: make() for k, make in LEAVES.items()}
    tree["host"] = np.arange(3.0)
    ps.publish("m", tree)
    _assert_bit_identical(ps.fetch("m")[1], _per_leaf(tree))
    assert _counters(metrics) == (len(LEAVES) + 1, len(LEAVES))


# the states the served detectors publish, and a tree of three dtypes
# beside a large leaf and a host leaf
STATES = {
    "autoencoder": lambda: AutoEncoder(n_features=N_FEATURES).init(),
    "kmeans": lambda: KMeans(n_clusters=4, n_features=N_FEATURES).init(
        np.random.default_rng(2).standard_normal((N_POINTS, N_FEATURES))),
    "isoforest": lambda: IsolationForest(n_trees=8, psi=64).fit(
        np.random.default_rng(3).standard_normal((N_POINTS, N_FEATURES))),
    "three_dtypes": lambda: {
        "f": [jnp.full((4,), i, jnp.float32) for i in range(8)],
        "i": [jnp.arange(i, i + 3, dtype=jnp.int32) for i in range(9)],
        "b": [jnp.asarray(i % 2 == 0) for i in range(8)],
        "big": jnp.ones((200, 100), jnp.float32),
        "host": np.arange(4)},
}


@pytest.mark.parametrize("name", sorted(STATES))
def test_a_model_state_publishes_as_writable_bit_identical_copies(name):
    metrics = MetricsRegistry()
    ps = ParameterService(metrics=metrics)
    tree = STATES[name]()
    ps.publish("m", tree)
    got = ps.fetch("m")[1]
    _assert_bit_identical(got, _per_leaf(tree))
    leaves = jax.tree.leaves(got)
    assert all(x.flags.writeable and x.flags.owndata for x in leaves)
    n_device = sum(isinstance(x, jax.Array) for x in jax.tree.leaves(tree))
    assert _counters(metrics) == (len(leaves), n_device)


def test_published_leaves_are_writable_snapshots():
    ps = ParameterService()
    w = jnp.arange(8.0)
    ps.publish("m", {"w": w})
    got = ps.fetch("m")[1]["w"]
    cached = np.asarray(w)              # the device value's host copy
    assert got.flags.writeable and got.flags.owndata
    assert not np.shares_memory(got, cached)
    got[:] = -1.0
    np.testing.assert_array_equal(np.asarray(w), np.arange(8.0))


class _RecordingLeaf:
    """A device-like leaf that logs when its copy starts and when it is
    read."""

    def __init__(self, name, value, log):
        self.name, self.value, self.log = name, value, log
        self.nbytes = value.nbytes

    def copy_to_host_async(self):
        self.log.append(("start", self.name))

    def __array__(self, dtype=None, copy=None):
        self.log.append(("read", self.name))
        return np.array(self.value, dtype=dtype, copy=copy)


def test_every_copy_starts_before_any_leaf_is_read():
    log = []
    names = [f"leaf{i}" for i in range(6)]
    tree = {n: _RecordingLeaf(n, np.full(3, i, np.float32), log)
            for i, n in enumerate(names)}
    metrics = MetricsRegistry()
    ParameterService(metrics=metrics).publish("m", tree)
    starts = [i for i, (what, _) in enumerate(log) if what == "start"]
    reads = [i for i, (what, _) in enumerate(log) if what == "read"]
    assert sorted(n for what, n in log if what == "start") == names
    assert sorted(n for what, n in log if what == "read") == names
    assert max(starts) < min(reads)
    assert _counters(metrics) == (6, 6)


def test_numpy_tree_publishes_as_before():
    metrics = MetricsRegistry()
    ps = ParameterService(metrics=metrics)
    tree = {"w": np.ones((2, 3)), "b": [np.zeros(3, np.int32), 1.5]}
    v = ps.publish("m", tree)
    assert v == 1
    _assert_bit_identical(ps.fetch("m")[1], _per_leaf(tree))
    assert _counters(metrics) == (3, 0)


def test_autoencoder_handler_starts_every_leafs_copy():
    metrics = MetricsRegistry()
    ps = ParameterService(metrics=metrics)
    handler = AutoEncoder(n_features=N_FEATURES, epochs=1).make_processor(ps)
    pts = np.random.default_rng(0).standard_normal((N_POINTS, N_FEATURES))
    handler(None, data=pts)
    assert _counters(metrics) == (43, 43)
    assert len(jax.tree.leaves(ps.fetch("ae")[1])) == 43


# A copy of each handler's steps, with every host copy made per leaf and
# none started ahead: what each handler computed before the early copies.

def _ae_steps(detector):
    state = {"s": detector.init()}

    def step(pts):
        x = jnp.asarray(pts, jnp.float32)
        scores = _ae_score(state["s"]["params"], x)
        state["s"] = _ae_train(state["s"], x, detector._key,
                               detector.schedule(len(pts)))
        published = _per_leaf(state["s"])
        s = np.asarray(scores)
        thresh = np.percentile(s, 100.0 * (1.0 - CONTAMINATION))
        return ({"n_outliers": int((s > thresh).sum()),
                 "mean_score": float(s.mean())}, published)
    return step


def _kmeans_steps(detector):
    state = {"s": None}

    def step(pts):
        if state["s"] is None:
            state["s"] = detector.init(pts)
        state["s"], _, scores = detector.assign_update(state["s"], pts)
        published = _per_leaf(state["s"])
        s = np.asarray(scores)
        thresh = s.mean() + 3.0 * s.std()
        return ({"n_outliers": int((s > thresh).sum()),
                 "mean_score": float(s.mean())}, published)
    return step


def _forest_steps(detector):
    def step(pts):
        state = detector.fit(pts)
        published = _per_leaf(state)
        s = np.asarray(detector.outlier_scores(state, pts))
        return ({"n_outliers": int((s > 0.6).sum()),
                 "mean_score": float(s.mean())}, published)
    return step


# detector, its steps without early copies, leaves it publishes
HANDLERS = {
    "autoencoder": (lambda: AutoEncoder(n_features=N_FEATURES, epochs=2),
                    _ae_steps, 43),
    "kmeans": (lambda: KMeans(n_clusters=4, n_features=N_FEATURES),
               _kmeans_steps, 2),
    "isoforest": (lambda: IsolationForest(n_trees=8, psi=64),
                  _forest_steps, 5),
}


@pytest.mark.parametrize("name", sorted(HANDLERS))
def test_handler_answers_and_publishes_as_without_early_copies(name):
    make, steps, n_leaves = HANDLERS[name]
    metrics = MetricsRegistry()
    ps = ParameterService(metrics=metrics)
    handler = make().make_processor(ps, model_name="m")
    reference = steps(make())
    rng = np.random.default_rng(1)
    for call in range(1, 4):
        pts = rng.standard_normal((N_POINTS, N_FEATURES))
        pts[:5] *= 6.0
        want, want_tree = reference(pts)
        assert handler(None, data=pts) == want
        version, tree = ps.fetch("m")
        assert version == call
        _assert_bit_identical(tree, want_tree)
        assert _counters(metrics) == (call * n_leaves, call * n_leaves)
