"""Pallas TPU kernels for the continuum's hot spots and their pure-jnp
oracles (``ref.py``).  Call sites use each kernel's entry point directly;
its ``interpret`` default resolves here."""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Every Pallas entry point's ``interpret`` default: ``None`` runs the
    compiled kernel on a TPU backend and the interpreter elsewhere, so a
    kernel on the chip is interpreted only when a caller asks for it."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
