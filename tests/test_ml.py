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


def test_processors_share_via_param_service():
    from repro.core import ParameterService
    ps = ParameterService()
    km = KMeans(n_clusters=5, n_features=4)
    gen = MiniAppGenerator(n_points=200, n_features=4, n_clusters=5,
                           seed=0)

    class Ctx:
        attempt = 0

    proc_a = km.make_processor(ps, "m")
    proc_a(Ctx(), data=gen.sample())
    assert ps.version("m") == 1
    # a second (fresh) processor picks up the published model
    proc_b = km.make_processor(ps, "m", train=False)
    out = proc_b(Ctx(), data=gen.sample())
    assert "n_outliers" in out
    assert ps.version("m") == 1     # train=False published nothing


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
