"""The served auto-encoder against the benchmark's plain reference
(``bench/configs/autoencoder-paper/reference.py``), on the CPU at a small
size: 512 points of 8 features, hidden (16, 8, 8, 16), 3 epochs of batches
of 32 (460 training points, so a short last batch of 12), seeded weights.

On the CPU a float32 product at ``HIGHEST`` is a float32 product, so the
program and the reference differ only in the order of their sums (the
reference takes Keras' per-batch form of the loss, the program a masked
mean over a padded last batch) and in the sigmoid's formula.  A call
holds 45 Adam steps; Adam divides each gradient by its own scale, so a
rounding of one part in 1e7 stays about that size through them.  The
tolerances below are 10-100 times those roundings and far below what a
dropped epoch, dropout or regulariser moves."""
import importlib.util
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import ParameterService
from repro.core.monitoring import MetricsRegistry
from repro.ml import AutoEncoder, autoencoder
from repro.ml.autoencoder import _layer_sizes, ae_param_count

ROOT = Path(__file__).resolve().parents[1]
CONFIG = ROOT / "bench" / "configs" / "autoencoder-paper"
SMALL = {"n_features": 8, "hidden": [16, 8, 8, 16], "epochs": 3,
         "batch_size": 32}
N_POINTS, N_CALLS, SEED = 512, 4, 77
# float32 roundings (about 1e-7), carried through 45 Adam steps
SCORE_RTOL = 1e-6
STATE_RTOL = 1e-5


def _module(stem):
    spec = importlib.util.spec_from_file_location(
        f"ae_paper_{stem}", CONFIG / f"{stem}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def reference():
    return _module("reference")


@pytest.fixture(scope="module")
def config():
    cfg = json.loads((CONFIG / "config.json").read_text())
    cfg["model"].update(SMALL)
    cfg["pool"].update(n_points=N_POINTS, n_features=SMALL["n_features"])
    return cfg


@pytest.fixture(scope="module")
def pool():
    rng = np.random.default_rng(3)
    pts = rng.normal(size=(N_CALLS, N_POINTS, SMALL["n_features"]))
    pts[:, :10] *= 8.0                        # a few far points
    return pts


def _detector(config, epochs=None):
    m = config["model"]
    return AutoEncoder(n_features=m["n_features"], hidden=tuple(m["hidden"]),
                       epochs=epochs or m["epochs"], seed=SEED)


class _Recording(ParameterService):
    def __init__(self):
        super().__init__(metrics=MetricsRegistry())
        self.history = []

    def publish(self, name, tree):
        version = super().publish(name, tree)
        self.history.append(self.fetch(name)[1])
        return version


def _serve(detector, pool):
    params = _Recording()
    handler = detector.make_processor(params, train=True)
    served = [handler(None, data=pool[i]) for i in range(len(pool))]
    return served, params


@pytest.fixture(scope="module")
def run(config, pool, reference):
    served, params = _serve(_detector(config), pool)
    order = list(range(len(pool)))
    ref = reference.replay(config, SEED, pool, order, 5, params.history)
    return served, params, ref


def test_dense_widths_are_pyods():
    sizes = _layer_sizes(32, (64, 32, 32, 64))
    assert sizes[1:] == [32, 32, 64, 32, 32, 64, 32]
    params = AutoEncoder().init()["params"]
    assert [p["w"].shape for p in params] == list(zip(sizes[:-1],
                                                      sizes[1:]))
    assert ae_param_count(params) == 11_552


def test_served_scores_and_state_match_the_reference(run):
    served, params, ref = run
    assert sorted(ref["answers"]) == list(range(N_CALLS))
    for c, want in ref["answers"].items():
        assert served[c]["mean_score"] == pytest.approx(
            want["mean_score"], rel=SCORE_RTOL)
        assert served[c]["n_outliers"] == want["n_outliers"]
    for got, want in zip(params.history, ref["published"]):
        assert int(got["step"]) == int(want["step"])
        for part in ("params", "mu", "nu"):
            for g, w in zip(jax.tree.leaves(got[part]),
                            jax.tree.leaves(want[part])):
                scale = max(float(np.abs(w).max()), 1e-30)
                assert np.abs(np.asarray(g) - w).max() <= STATE_RTOL * scale


def test_adam_step_counter_matches_work(run, config):
    _, params, _ = run
    work = _module("work")
    steps = work.adam_steps(dict(config, pool=dict(config["pool"])))
    assert steps == 3 * 15                    # ceil(460 / 32) = 15
    assert params.metrics.counter("ae.adam_steps") == N_CALLS * steps
    assert params.metrics.counter("ae.epochs") == N_CALLS * 3
    assert int(params.history[-1]["step"]) == N_CALLS * steps


@pytest.mark.parametrize("fault", [
    ("epochs", 2), ("DROPOUT_RATE", 0.0), ("L2_REGULARIZER", 0.0)],
    ids=["one_epoch_fewer", "no_dropout", "no_regulariser"])
def test_compare_fails_a_program_that_trains_otherwise(
        config, pool, reference, monkeypatch, fault):
    name, value = fault
    if name == "epochs":
        detector = _detector(config, epochs=value)
    else:
        monkeypatch.setattr(autoencoder, name, value)
        detector = _detector(config)
    served, params = _serve(detector, pool)
    ref = reference.replay(config, SEED, pool, list(range(len(pool))), 5,
                           params.history)
    gaps = reference.compare(config, served, params.history, ref)
    limits = json.loads((CONFIG / "config.json").read_text())["limits"]
    assert gaps["model_gap"] > limits["model_gap"], gaps


def test_compare_reads_nought_for_the_program_against_itself(run,
                                                             reference,
                                                             config):
    served, params, ref = run
    gaps = reference.compare(config, served, params.history, ref)
    limits = json.loads((CONFIG / "config.json").read_text())["limits"]
    assert set(gaps) == set(limits)
    assert all(v <= limits[k] for k, v in gaps.items()), gaps


def test_model_gap_judges_each_leaf(run, reference, config):
    """A fault in one small leaf reads at its own scale, not diluted by
    the rest of its part; a leaf near 0 is judged at the part's floor."""
    _, params, ref = run
    want = ref["published"][-1]
    got = jax.tree.map(np.array, want)
    got["params"][-1]["b"] = got["params"][-1]["b"] * (1.0 + 1e-2)
    gaps = reference.leaf_gaps(got, want)
    out_bias = f"params/{len(want['params']) - 1}/b"
    assert gaps[out_bias] == pytest.approx(1e-2, rel=1e-3)
    assert max(gaps.values()) == gaps[out_bias]
    got = jax.tree.map(np.array, want)
    got["mu"][0]["b"] = np.zeros_like(got["mu"][0]["b"]) + 1e-30
    want = jax.tree.map(np.array, want)
    want["mu"][0]["b"] = np.zeros_like(want["mu"][0]["b"])
    assert reference.leaf_gaps(got, want)["mu/0/b"] < 1e-20


def test_epoch_kernel_matches_its_scan(config, pool):
    """The fit's Pallas kernel (interpreted here) and the same steps as
    a ``lax.scan`` give the same state."""
    ae = _detector(config)
    state = ae.init()
    x = jax.numpy.asarray(pool[0], jax.numpy.float32)
    schedule = ae.schedule(N_POINTS)
    got = autoencoder._ae_train(state, x, ae._key, schedule)
    want = autoencoder._ae_train(state, x, ae._key, schedule, kernel=False)
    assert int(got["step"]) == int(want["step"]) == 45
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-6, atol=1e-12)
