"""Per-kernel shape/dtype sweeps: every Pallas kernel (interpret mode)
against its ref.py pure-jnp oracle (assignment requirement)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.kmeans import kmeans_assign, kmeans_assign_update
from repro.kernels.ssd import ssd_chunk_scan

RNG = np.random.default_rng(42)


def _tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # (b, sq, sk, h, hkv, d, causal, window)
    (1, 128, 128, 2, 2, 64, True, None),
    (2, 256, 256, 4, 2, 64, True, None),        # GQA 2x
    (1, 384, 384, 8, 1, 32, True, None),        # MQA
    (1, 128, 128, 4, 4, 128, False, None),      # bidirectional
    (2, 200, 200, 2, 2, 64, True, 64),          # unaligned + window
    (1, 512, 512, 2, 1, 64, True, 128),         # long + window
    (1, 96, 96, 2, 2, 16, True, None),          # small head_dim, sub-block
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_vs_ref(case, dtype):
    b, sq, sk, h, hkv, d, causal, window = case
    q = jnp.asarray(RNG.standard_normal((b, sq, h, d)), dtype)
    k = jnp.asarray(RNG.standard_normal((b, sk, hkv, d)), dtype)
    v = jnp.asarray(RNG.standard_normal((b, sk, hkv, d)), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          interpret=True)
    exp = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


def test_flash_attention_block_shapes():
    """Different BlockSpec tilings give identical results."""
    q = jnp.asarray(RNG.standard_normal((1, 256, 2, 64)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((1, 256, 2, 64)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((1, 256, 2, 64)), jnp.float32)
    base = flash_attention(q, k, v, block_q=128, block_k=128,
                           interpret=True)
    for bq, bk in [(64, 64), (128, 64), (64, 128), (256, 256)]:
        out = flash_attention(q, k, v, block_q=bq, block_k=bk,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                                   atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# kmeans assignment
# ---------------------------------------------------------------------------

KMEANS_CASES = [
    (100, 32, 25), (1000, 32, 25), (257, 7, 3), (4096, 64, 100),
    (25, 32, 25), (513, 128, 128), (2500, 32, 25),
]


@pytest.mark.parametrize("case", KMEANS_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kmeans_assign_vs_ref(case, dtype):
    n, f, k = case
    pts = jnp.asarray(RNG.standard_normal((n, f)) * 5, dtype)
    cent = jnp.asarray(RNG.standard_normal((k, f)) * 5, dtype)
    ids, dmin = kmeans_assign(pts, cent, interpret=True)
    ids_r, dmin_r = ref.kmeans_assign_ref(pts, cent)
    # argmin ties under low precision: allow id mismatch only if distances
    # are ~equal
    mism = np.asarray(ids) != np.asarray(ids_r)
    if mism.any():
        np.testing.assert_allclose(np.asarray(dmin)[mism],
                                   np.asarray(dmin_r)[mism],
                                   atol=1e-2, rtol=1e-2)
    np.testing.assert_allclose(np.asarray(dmin, np.float32),
                               np.asarray(dmin_r, np.float32),
                               **_tol(dtype))


# ---------------------------------------------------------------------------
# SSD
# ---------------------------------------------------------------------------

SSD_CASES = [
    # (b, s, nh, hd, g, ds, chunk)
    (1, 64, 2, 16, 1, 16, 16),
    (2, 128, 4, 32, 1, 16, 32),
    (1, 256, 8, 64, 2, 32, 64),
    (1, 256, 24, 64, 1, 128, 64),     # mamba2-130m dims
    (2, 128, 4, 32, 4, 16, 128),      # chunk == seq
]


@pytest.mark.parametrize("case", SSD_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_ssd_vs_ref(case, dtype):
    b, s, nh, hd, g, ds, chunk = case
    xh = jnp.asarray(RNG.standard_normal((b, s, nh, hd)), dtype)
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, (b, s, nh)), jnp.float32)
    A = -jnp.asarray(RNG.uniform(0.5, 2.0, (nh,)), jnp.float32)
    B_ = jnp.asarray(RNG.standard_normal((b, s, g, ds)), dtype)
    C_ = jnp.asarray(RNG.standard_normal((b, s, g, ds)), dtype)
    D = jnp.asarray(RNG.standard_normal((nh,)), jnp.float32)
    y, fin = ssd_chunk_scan(xh, dt, A, B_, C_, D, chunk=chunk,
                            interpret=True)
    y_r, fin_r = ref.ssd_ref(xh, dt, A, B_, C_, D)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_r, np.float32),
                               atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(fin), np.asarray(fin_r),
                               atol=1e-3, rtol=1e-3)


def test_ssd_matches_layers_impl():
    """kernels/ssd == models/layers.ssd_chunked (the model's jnp path)."""
    from repro.models.layers import ssd_chunked
    b, s, nh, hd, g, ds, chunk = 2, 128, 4, 32, 1, 16, 32
    xh = jnp.asarray(RNG.standard_normal((b, s, nh, hd)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, (b, s, nh)), jnp.float32)
    A = -jnp.asarray(RNG.uniform(0.5, 2.0, (nh,)), jnp.float32)
    B_ = jnp.asarray(RNG.standard_normal((b, s, g, ds)), jnp.float32)
    C_ = jnp.asarray(RNG.standard_normal((b, s, g, ds)), jnp.float32)
    D = jnp.asarray(RNG.standard_normal((nh,)), jnp.float32)
    y_k, fin_k = ssd_chunk_scan(xh, dt, A, B_, C_, D, chunk=chunk,
                                interpret=True)
    y_l, fin_l = ssd_chunked(xh, dt, A, B_, C_, D, chunk,
                             return_state=True)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_l),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(fin_k), np.asarray(fin_l),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# entry points route correctly with their default interpret
# ---------------------------------------------------------------------------

def test_ops_wrappers():
    """Called as the model and detector call sites call them (no
    ``interpret``), the entry points run on this backend."""
    q = jnp.asarray(RNG.standard_normal((1, 128, 2, 64)), jnp.float32)
    out = flash_attention(q, q, q)
    assert out.shape == q.shape
    pts = jnp.asarray(RNG.standard_normal((100, 32)), jnp.float32)
    cent = jnp.asarray(RNG.standard_normal((25, 32)), jnp.float32)
    ids, dmin = kmeans_assign(pts, cent)
    assert ids.shape == (100,) and dmin.shape == (100,)


def test_kernel_entry_points_resolve_interpret_from_backend(monkeypatch):
    """No Pallas entry point defaults to interpreting: ``interpret=None``
    compiles on a TPU backend and interprets only elsewhere."""
    import inspect

    import repro.kernels as K
    for fn in (flash_attention, kmeans_assign, kmeans_assign_update,
               ssd_chunk_scan):
        assert inspect.signature(fn).parameters["interpret"].default is None
    assert K.resolve_interpret(None) is True          # this CPU backend
    assert K.resolve_interpret(False) is False
    monkeypatch.setattr(K.jax, "default_backend", lambda: "tpu")
    assert K.resolve_interpret(None) is False
    assert K.resolve_interpret(True) is True


def test_model_uses_pallas_attention():
    """gqa_forward(impl='pallas') matches impl='dense'."""
    from repro.configs import get_arch
    from repro.models import transformer as T
    cfg = get_arch("internlm2-1.8b").reduced()
    params = T.init_params(jax.random.key(0), cfg)
    inputs = {"tokens": jnp.ones((1, 128), jnp.int32),
              "labels": jnp.zeros((1, 128), jnp.int32)}
    ld, _ = T.forward(params, cfg, inputs, impl="dense", remat=False)
    lp, _ = T.forward(params, cfg, inputs, impl="pallas", remat=False)
    np.testing.assert_allclose(np.asarray(ld), np.asarray(lp),
                               atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# fused kmeans assign+update (tentpole): Pallas kernel, precision axis
# ---------------------------------------------------------------------------

FUSED_CASES = [(257, 7, 3), (1000, 32, 25), (25, 32, 25), (513, 128, 128),
               (2500, 32, 25)]


def _blob(n, f, k):
    pts = jnp.asarray(RNG.standard_normal((n, f)) * 5, jnp.float32)
    return pts, pts[:k]


@pytest.mark.parametrize("case", FUSED_CASES)
@pytest.mark.parametrize("precision", ["fp32", "bf16", "int8"])
def test_kmeans_fused_kernel_matches_jnp_lowering(case, precision):
    """The fused Pallas kernel (interpret mode) and the fused jnp lowering
    are the same computation: ids exact, counts exact, updated centroids
    within accumulation-order tolerance."""
    from repro.ml.kmeans import _assign_update
    n, f, k = case
    pts, cent = _blob(n, f, k)
    counts0 = jnp.zeros((k,), jnp.float32)
    jcent, jc, jids, jd = _assign_update(cent, counts0, pts,
                                         impl="fused", precision=precision)
    pcent, pc, pids, pd = _assign_update(cent, counts0, pts,
                                         impl="pallas", precision=precision)
    np.testing.assert_array_equal(np.asarray(jids), np.asarray(pids))
    np.testing.assert_array_equal(np.asarray(jc), np.asarray(pc))
    np.testing.assert_allclose(np.asarray(jcent), np.asarray(pcent),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(jd), np.asarray(pd),
                               atol=0.05, rtol=1e-3)


@pytest.mark.parametrize("precision", ["fp32", "bf16", "int8"])
def test_kmeans_fused_vs_two_pass_impl_parity(precision):
    """impl='fused' (distance pass + scatter-add) and impl='jnp' (the
    historical two-pass one-hot matmul) agree bit-for-bit on ids/counts
    and to accumulation tolerance on the updated centroids."""
    from repro.ml.kmeans import _assign_update
    pts, cent = _blob(2500, 32, 25)
    counts0 = jnp.full((25,), 7.0, jnp.float32)
    fcent, fc, fids, _ = _assign_update(cent, counts0, pts,
                                        impl="fused", precision=precision)
    jcent, jc, jids, _ = _assign_update(cent, counts0, pts,
                                        impl="jnp", precision=precision)
    np.testing.assert_array_equal(np.asarray(fids), np.asarray(jids))
    np.testing.assert_array_equal(np.asarray(fc), np.asarray(jc))
    np.testing.assert_allclose(np.asarray(fcent), np.asarray(jcent),
                               rtol=1e-5, atol=1e-4)


def test_kmeans_fused_kernel_counts_every_point():
    """Padded tail rows must not leak into the accumulators: counts sum
    to exactly n for a deliberately non-block-aligned n."""
    pts, cent = _blob(257, 7, 3)
    ids, dmin, sums, counts = kmeans_assign_update(pts, cent)
    assert float(jnp.sum(counts)) == 257.0
    np.testing.assert_allclose(
        np.asarray(jnp.sum(sums, axis=0)), np.asarray(jnp.sum(pts, axis=0)),
        rtol=1e-5, atol=1e-3)


def test_kmeans_assign_skips_repad_when_aligned():
    """Satellite perf fix: _pad2 is a no-op (same array object) when the
    input is already block-aligned."""
    from repro.kernels.kmeans import _pad2
    a = jnp.ones((256, 128), jnp.float32)
    assert _pad2(a, 256, 128) is a
    b = _pad2(jnp.ones((100, 32), jnp.float32), 128, 128)
    assert b.shape == (128, 128)
    assert float(jnp.sum(b)) == 100 * 32        # zero padding


def test_kmeans_int8_quantization_roundtrip():
    """quant helpers: symmetric per-feature scales bound the dequant error
    by scale/2, and fake_quantize == dequantize(quantize)."""
    from repro.kernels import quant
    pts, cent = _blob(500, 16, 8)
    scales = quant.symmetric_scales(pts, cent)
    assert scales.shape == (16,) and bool(jnp.all(scales > 0))
    q = quant.quantize(pts, scales)
    assert q.dtype == jnp.int8
    dq = quant.dequantize(q, scales)
    assert bool(jnp.all(jnp.abs(dq - pts) <= 0.5 * scales[None, :] + 1e-7))
    np.testing.assert_array_equal(np.asarray(quant.fake_quantize(pts, scales)),
                                  np.asarray(dq))
    # shared scales cover the centroids too
    qc = quant.quantize(cent, scales)
    assert int(jnp.max(jnp.abs(qc.astype(jnp.int32)))) <= 127


def test_kmeans_precision_agreement_on_probe():
    """Acceptance pin: the reduced-precision variants agree with fp32 on
    >= 99% of assignments on the fixed MiniAppGenerator probe."""
    from repro.ml.kmeans import assignment_agreement
    assert assignment_agreement("bf16") >= 0.99
    assert assignment_agreement("int8") >= 0.99
    assert assignment_agreement("fp32") == 1.0


def test_kmeans_autotune_block_n_deterministic_and_cached():
    """The block_n sweep picks from the candidate set, caches per shape,
    and is deterministic under an injected timer."""
    from repro.kernels import kmeans as kk
    state = {"t": 0.0, "step": 1.0, "calls": 0}

    def fake_clock():
        # ever-growing tick: earlier-swept candidates time faster, so the
        # first candidate deterministically wins
        state["calls"] += 1
        state["t"] += state["step"]
        state["step"] *= 2.0
        return state["t"]

    kk._autotune_cache.clear()
    best = kk.autotune_block_n(1000, 32, 25, precision="fp32",
                               interpret=True, candidates=(128, 256),
                               probe_n=512, timer=fake_clock)
    assert best == 128
    n_calls = state["calls"]
    assert n_calls > 0
    again = kk.autotune_block_n(1000, 32, 25, precision="fp32",
                                interpret=True, candidates=(128, 256),
                                probe_n=512, timer=fake_clock)
    assert again == best and state["calls"] == n_calls     # cache hit
