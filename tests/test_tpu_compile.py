"""Compile guard: the served path's device programs compile for a described
TPU v5e (no chip attached), at the sizes the chip smoke runs them.

Interpret-mode kernel tests cannot catch what only the TPU compiler refuses
(misaligned tiles, VMEM overuse, programs that do not fit the device).  The
topology is described inside a fixture, never while a module is imported,
so only the worker given this file loads the TPU compiler.  Every test that
needs it lives in this one file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.kmeans import kmeans_assign_update
from repro.ml import autoencoder, isoforest

N_KERNEL, N_MESSAGE, N_FEATURES, N_CLUSTERS = 1_000_000, 10_000, 32, 25


@pytest.fixture(scope="module")
def topo():
    """A described ``v5e:2x2`` topology, with JAX's persistent compile
    cache off while it is in use: an entry compiled for a described chip
    cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described "
                        f"({type(e).__name__})")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        compilation_cache.reset_cache()
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    """Shapes of ``tree`` placed on ``sharding``."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


@pytest.fixture(scope="module")
def message(one_chip):
    return jax.ShapeDtypeStruct((N_MESSAGE, N_FEATURES), jnp.float32,
                                sharding=one_chip)


@pytest.mark.parametrize("precision", ["fp32", "bf16", "int8"])
def test_kmeans_fused_kernel_compiles_for_v5e(one_chip, precision):
    pts = jax.ShapeDtypeStruct((N_KERNEL, N_FEATURES), jnp.float32,
                               sharding=one_chip)
    cent = jax.ShapeDtypeStruct((N_CLUSTERS, N_FEATURES), jnp.float32,
                                sharding=one_chip)
    compiled = kmeans_assign_update.lower(
        pts, cent, interpret=False, precision=precision).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_autoencoder_train_step_compiles_for_v5e(one_chip, message):
    """The whole-message fit: 100 epochs of 282 Adam steps in one
    program, each epoch one Pallas kernel."""
    ae = autoencoder.AutoEncoder()
    state = _on(one_chip, jax.eval_shape(ae.init))
    key = _on(one_chip, jax.eval_shape(lambda: jax.random.key(0)))
    compiled = autoencoder._ae_train.lower(
        state, message, key, ae.schedule(N_MESSAGE),
        interpret=False).compile()
    assert compiled.memory_analysis() is not None
    assert "tpu_custom_call" in compiled.as_text()


FOREST = isoforest.IsolationForest(n_trees=100)


@pytest.fixture(scope="module")
def forest_key(one_chip):
    return _on(one_chip, jax.eval_shape(lambda: jax.random.key(0)))


def test_isoforest_fit_compiles_for_v5e(forest_key, message):
    compiled = isoforest._fit.lower(forest_key, message, FOREST.n_trees,
                                    FOREST.psi, FOREST.max_depth).compile()
    assert compiled.memory_analysis() is not None


@pytest.fixture(scope="module")
def compiled_score(one_chip, forest_key, message):
    forest = _on(one_chip, jax.eval_shape(
        lambda k, x: isoforest._fit(k, x, FOREST.n_trees, FOREST.psi,
                                    FOREST.max_depth), forest_key, message))
    psi = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    return isoforest._score.lower(forest, message, psi,
                                  FOREST.max_depth).compile()


def test_isoforest_score_compiles_for_v5e(compiled_score):
    assert compiled_score.memory_analysis() is not None


# Reading at 10,000 x 32 with 100 trees of 256: temp_size_in_bytes 0 (the
# level state stays in on-chip memory).  One level's one-hot materialized
# over its 16 nodes would already take 64 MB.
SCORE_TEMP_LIMIT = 32 * 2**20


def test_isoforest_score_walks_levels_without_gathers(compiled_score):
    """The score looks nodes and features up with one-hot selects: the
    program holds no gather and no loop, and no one-hot is materialized."""
    text = compiled_score.as_text()
    assert re.search(r"\bgather\(", text) is None
    assert re.search(r"\bwhile\(", text) is None
    assert compiled_score.memory_analysis().temp_size_in_bytes \
        < SCORE_TEMP_LIMIT
