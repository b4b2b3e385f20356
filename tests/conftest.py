"""Shared fixtures. NOTE: no XLA_FLAGS device-count override here — smoke
tests must see the real single CPU device (the 512-device override belongs
exclusively to launch/dryrun.py).

The jit-heavy tests dominate tier-1 wall time, so the persistent XLA
compilation cache is enabled (:mod:`repro.compile_cache`; disable with
REPRO_NO_JAX_CACHE=1).  First runs pay full compile cost; reruns and CI
with a restored cache directory get the compile time back.
"""
import numpy as np
import pytest

from repro.compile_cache import enable_compilation_cache

enable_compilation_cache()


@pytest.fixture
def rng():
    return np.random.default_rng(0)
