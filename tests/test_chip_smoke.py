"""``chip_smoke.py``'s contract, checked on the CPU: it refuses to run
without a TPU, its phases pass at tiny sizes (kernels interpreted), and a
pipeline whose handler always fails is reported at once, not after the run's
timeout."""
import importlib.util
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.compile_cache import REPO_CACHE_DIR, enable_compilation_cache
from repro.ml import AutoEncoder, IsolationForest, KMeans

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_smoke_refuses_without_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main() != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out
    assert "platform=cpu" in out


def test_smoke_kernel_phase_at_tiny_size(smoke):
    report = smoke.kernel_phase(n_points=1_000, interpret=True)
    assert set(report) == {"fp32", "bf16", "int8"}
    for r in report.values():
        assert r["id_agreement"] >= 0.999
        assert r["counts_err"] == 0.0


@pytest.mark.parametrize("name,served,reference", [
    ("kmeans-pallas", KMeans(impl="pallas"), KMeans(impl="jnp")),
    ("kmeans-fused", KMeans(), KMeans(impl="jnp")),
    ("autoencoder", AutoEncoder(epochs=2), AutoEncoder(epochs=2)),
    ("isoforest", IsolationForest(n_trees=4), IsolationForest(n_trees=4)),
])
def test_smoke_pipeline_phase_at_tiny_size(smoke, name, served, reference):
    r = smoke.pipeline_phase(name, served, reference, n_points=200,
                             n_messages=3, timeout_s=60.0)
    assert r["messages"] == 3


def test_smoke_pipeline_check_fails_fast_on_failing_handler(smoke):
    def always_raises(context, data=None):
        raise RuntimeError("injected handler failure")

    t0 = time.monotonic()
    with pytest.raises(smoke.SmokeFailure, match="injected handler failure"):
        smoke.run_pipeline(always_raises, n_points=50, n_messages=4, seed=0,
                           timeout_s=600.0)
    assert time.monotonic() - t0 < 30.0


def test_smoke_pipeline_check_rejects_model_off_device(smoke):
    """A handler that publishes host (numpy) state fails the placement
    check: the smoke holds the served model to the device."""
    class HostKMeans(KMeans):
        def assign_update(self, state, points):
            new, ids, dmin = super().assign_update(state, points)
            return jax.tree.map(np.asarray, new), ids, dmin

    with pytest.raises(smoke.SmokeFailure, match="published model"):
        smoke.pipeline_phase("kmeans-fused", HostKMeans(), KMeans(impl="jnp"),
                             n_points=200, n_messages=2, timeout_s=60.0)


@pytest.fixture
def cache_config():
    saved = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", saved)


def test_compile_cache_placement(monkeypatch, tmp_path, cache_config):
    monkeypatch.delenv("REPRO_NO_JAX_CACHE", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compilation_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert enable_compilation_cache() == str(ROOT / ".jax_cache")
    assert REPO_CACHE_DIR == ROOT / ".jax_cache"
    assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
    monkeypatch.setenv("REPRO_NO_JAX_CACHE", "1")
    assert enable_compilation_cache() is None


def test_compile_cache_env_dir_receives_entries(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, a compile lands there."""
    probe = ("from repro.compile_cache import enable_compilation_cache\n"
             "import jax, jax.numpy as jnp\n"
             "print(enable_compilation_cache())\n"
             "jax.config.update("
             "'jax_persistent_cache_min_compile_time_secs', 0)\n"
             "jax.jit(lambda x: x * 2 + 1)(jnp.ones(7)).block_until_ready()\n")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    env.pop("REPRO_NO_JAX_CACHE", None)
    out = subprocess.run([sys.executable, "-c", probe], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == str(tmp_path)
    assert any(tmp_path.iterdir())
