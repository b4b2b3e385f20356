"""Paper ML workload tests: detector quality on labeled synthetic data,
the paper's exact AE topology, streaming-update convergence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ml import AutoEncoder, IsolationForest, KMeans, MiniAppGenerator
from repro.ml.autoencoder import ae_param_count
from repro.ml.datagen import PAPER_POINTS, message_nbytes


def test_message_sizes_match_paper():
    """25–10,000 points x 32 feat = 7 KB–2.6 MB (paper §III.1)."""
    assert abs(message_nbytes(25) - 6_400) < 1_000
    assert abs(message_nbytes(10_000) - 2_560_000) < 10_000
    assert PAPER_POINTS == (25, 250, 2_500, 10_000)


def test_generator_determinism_and_outlier_frac():
    g1 = MiniAppGenerator(n_points=1000, seed=5)
    g2 = MiniAppGenerator(n_points=1000, seed=5)
    np.testing.assert_array_equal(g1.sample(), g2.sample())
    pts, is_out = MiniAppGenerator(n_points=5000, outlier_frac=0.02,
                                   seed=1).sample_with_labels()
    assert 0.01 <= is_out.mean() <= 0.03


def test_ae_param_count_is_papers_11552():
    ae = AutoEncoder()
    assert ae_param_count(ae.init()["params"]) == 11_552


def test_ae_learns_and_detects():
    gen = MiniAppGenerator(n_points=2000, outlier_frac=0.02, seed=2)
    pts, is_out = gen.sample_with_labels()
    # one epoch a message (PyOD's 100 would be 228,000 CPU steps here)
    ae = AutoEncoder(epochs=1)
    st = ae.init()
    losses = []
    for _ in range(40):
        st, loss = ae.update(st, pts)
        losses.append(loss)
    assert losses[-1] < losses[0] * 0.9
    s = np.asarray(ae.outlier_scores(st, pts))
    pred = s > s.mean() + 2 * s.std()
    tp = (pred & is_out).sum()
    assert tp / max(pred.sum(), 1) > 0.8          # precision
    assert tp / max(is_out.sum(), 1) > 0.5        # recall


def test_kmeans_converges_and_detects():
    gen = MiniAppGenerator(n_points=2500, outlier_frac=0.02, seed=1)
    pts, is_out = gen.sample_with_labels()
    km = KMeans(n_clusters=25)
    st = km.init(pts)
    inert = [km.inertia(st, pts)]
    for _ in range(10):
        st = km.update(st, pts)
        inert.append(km.inertia(st, pts))
    assert inert[-1] < inert[0]
    s = np.asarray(km.outlier_scores(st, pts))
    pred = s > s.mean() + 3 * s.std()
    assert (pred & is_out).sum() / max(pred.sum(), 1) > 0.9


def test_kmeans_pallas_impl_matches():
    gen = MiniAppGenerator(n_points=500, seed=3)
    pts = gen.sample()
    km_j = KMeans(n_clusters=25, impl="jnp")
    km_p = KMeans(n_clusters=25, impl="pallas")
    st = km_j.init(pts)
    ids_j, d_j = km_j.assign(st, pts)
    ids_p, d_p = km_p.assign(st, pts)
    np.testing.assert_array_equal(np.asarray(ids_j), np.asarray(ids_p))
    # the ||x||^2-2xc+||c||^2 expansion cancels catastrophically at d~0
    # (init seeds centroids FROM sample points): absolute error floor is
    # sqrt(eps*||x||^2) ~ 0.05 for ||x||^2 ~ 2e4, regardless of impl.
    np.testing.assert_allclose(np.asarray(d_j), np.asarray(d_p),
                               atol=0.05, rtol=1e-3)


@pytest.mark.slow
def test_isoforest_separates_outliers():
    gen = MiniAppGenerator(n_points=1500, outlier_frac=0.03, seed=4)
    pts, is_out = gen.sample_with_labels()
    f = IsolationForest(n_trees=50)
    st = f.fit(pts)
    s = np.asarray(f.outlier_scores(st, pts))
    # outliers must score strictly higher on average
    assert s[is_out].mean() > s[~is_out].mean() + 0.05
    # AUC-ish check via rank statistics
    order = np.argsort(s)
    ranks = np.empty_like(order, float)
    ranks[order] = np.arange(len(s))
    auc = (ranks[is_out].mean() - ranks.mean()) / len(s) + 0.5
    assert auc > 0.85


# the served detectors at a tiny size, over 4 features
SHARED = {
    "autoencoder": lambda: AutoEncoder(n_features=4, epochs=1),
    "isoforest": lambda: IsolationForest(n_trees=4, psi=32),
    "kmeans": lambda: KMeans(n_clusters=5, n_features=4),
}


class _Ctx:
    attempt = 0


@pytest.mark.parametrize("name", sorted(SHARED))
def test_processors_share_via_param_service(name):
    """A ``train=False`` handler scores with the model a training handler
    published, picks up each newer version, and publishes nothing."""
    from repro.core import ParameterService
    ps = ParameterService()
    det = SHARED[name]()
    gen = MiniAppGenerator(n_points=200, n_features=4, n_clusters=5,
                           seed=0)
    proc_a = det.make_processor(ps, "m")
    proc_b = det.make_processor(ps, "m", train=False)
    proc_a(_Ctx(), data=gen.sample())
    for version in (1, 2):
        assert ps.version("m") == version
        pts = gen.sample()
        published = jax.tree.map(jnp.asarray, ps.fetch("m")[1])
        want = np.asarray(det.outlier_scores(published, pts))
        out = proc_b(_Ctx(), data=pts)
        assert out == {"n_outliers": det.n_outliers(want),
                       "mean_score": float(want.mean())}
        assert ps.version("m") == version   # train=False published nothing
        proc_a(_Ctx(), data=gen.sample())


class _MeanShift:
    """A detector the served handler has never seen: its state is the
    running mean of the points, its score a point's distance to it."""

    def __init__(self):
        self.first_points = []

    def first_state(self, points):
        self.first_points.append(points)
        return {"mean": jnp.zeros(points.shape[1], jnp.float32), "n": 0}

    def step(self, state, x, train, metrics):
        scores = jnp.linalg.norm(x - state["mean"], axis=1)
        if train:
            n = state["n"] + 1
            state = {"mean": state["mean"] + (x.mean(0) - state["mean"]) / n,
                     "n": n}
            if metrics is not None:
                metrics.incr("shift.fits")
        return state, scores

    def n_outliers(self, scores):
        return int((scores > 3.0).sum())


def test_a_new_detector_needs_only_its_state_step_and_threshold():
    from repro.core import MetricsRegistry, ParameterService
    from repro.ml import handler
    metrics = MetricsRegistry()
    metrics.record_spans()
    ps = ParameterService(metrics=metrics)
    det = _MeanShift()
    proc = handler.make_processor(det, ps, "shift")
    rng = np.random.default_rng(0)
    msgs = [rng.standard_normal((50, 3)) + 1.0 for _ in range(3)]
    mean = np.zeros(3, np.float32)
    for i, pts in enumerate(msgs, start=1):
        with metrics.span("pilot.handler", msg_id=f"m{i}"):
            out = proc(_Ctx(), data=pts)
        x = pts.astype(np.float32)
        scores = np.linalg.norm(x - mean, axis=1)
        assert out["n_outliers"] == int((scores > 3.0).sum())
        assert out["mean_score"] == pytest.approx(float(scores.mean()),
                                                  rel=1e-5)
        mean = mean + (x.mean(0) - mean) / i
        version, tree = ps.fetch("shift")
        assert version == i and tree["n"] == i
        np.testing.assert_allclose(tree["mean"], mean, rtol=1e-5)
    # the state is made once, from the first message's float64 points
    assert len(det.first_points) == 1
    assert det.first_points[0].dtype == np.float64
    assert metrics.counter("shift.fits") == 3
    counts = {}
    for r in metrics.spans():
        counts.setdefault(r.msg_id, []).append((r.name, r.nbytes))
    for i in range(1, 4):
        assert sorted(counts[f"m{i}"]) == sorted([
            ("pilot.handler", None), ("pilot.step", 50 * 3 * 4),
            ("pilot.publish", 3 * 4 + 8), ("pilot.pull", 50 * 4)])
    # a second handler takes the published model, not a first state
    out = handler.make_processor(det, ps, "shift", train=False)(
        _Ctx(), data=msgs[0])
    assert len(det.first_points) == 1 and ps.version("shift") == 3
    scores = np.linalg.norm(msgs[0].astype(np.float32) - mean, axis=1)
    assert out["mean_score"] == pytest.approx(float(scores.mean()), rel=1e-5)


def test_kmeans_update_threads_impl_to_fused_kernel(monkeypatch):
    """Satellite bugfix regression: KMeans(impl='pallas').update() must
    reach the fused Pallas kernel — historically _update re-ran _assign
    with the *default* impl, silently bypassing it."""
    import repro.kernels.kmeans as pallas_kmeans
    calls = []
    real = pallas_kmeans.kmeans_assign_update

    def counting(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(pallas_kmeans, "kmeans_assign_update", counting)
    jax.clear_caches()                 # force a retrace through the spy
    gen = MiniAppGenerator(n_points=300, seed=5)
    pts = gen.sample()
    km = KMeans(n_clusters=10, impl="pallas")
    st = km.init(pts)
    st = km.update(st, pts)
    assert calls, "update() never reached the fused Pallas kernel"
    assert st["counts"].sum() == 300


@pytest.mark.parametrize("precision", ["bf16", "int8"])
def test_kmeans_precision_variant_still_converges(precision):
    """Reduced-precision streaming k-means still drives inertia down to
    fp32-comparable clustering quality (individual centroids may settle
    in different basins after a boundary flip — quality, not bitwise
    trajectory, is the contract)."""
    gen = MiniAppGenerator(n_points=1000, seed=6)
    pts = gen.sample()
    km = KMeans(n_clusters=25, precision=precision)
    ref = KMeans(n_clusters=25)
    st, st_ref = km.init(pts), ref.init(pts)
    inert0 = km.inertia(st, pts)
    for _ in range(5):
        st = km.update(st, pts)
        st_ref = ref.update(st_ref, pts)
    assert km.inertia(st, pts) < inert0
    assert km.inertia(st, pts) < 1.25 * ref.inertia(st_ref, pts)
