"""Parameter service — the paper's Redis analog (§II-B step 2.2).

"Further, it provides a Redis-based parameter server for sharing model
weights across the continuum." Model updates in the paper's experiments are
"managed via the parameter service".

Implementation: a versioned, thread-safe pytree store.

* ``publish(name, tree)`` — store a new version (monotonic version numbers);
  values are host-side numpy copies so publishers can keep mutating device
  arrays.  The copy is the ``pilot.publish`` span, with its bytes; the
  registry counts the leaves published (``params.leaves_published``) and
  those whose device copy was started ahead of the first read
  (``params.leaves_copied_async``).
* ``fetch(name)`` / ``fetch_if_newer(name, have_version)`` — consumers poll
  for updates (the paper's model-update pattern: the inference task refreshes
  its model when the trainer publishes).
* ``subscribe(name, callback)`` — push notification within-process.
* ``place(name, sharding)`` — device_put the current version onto a pilot's
  mesh with the given sharding: the continuum broadcast (across the 'pod'
  axis on the multi-pod mesh, this is the DCN weight broadcast).

Versioning gives the same monotonic-read consistency Redis-with-version-keys
gives the paper; there is no cross-version tear because publish swaps the
whole tree atomically under the lock.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.core.monitoring import span


@dataclass
class _Entry:
    version: int
    tree: Any
    published_at: float


def _to_host(tree):
    """Host snapshot of ``tree`` as ``(snapshot, n_leaves, n_started)``.

    ``jax.device_get`` starts every device leaf's copy before it reads
    any, so the host waits once for the tree and not once per leaf;
    ``n_started`` counts those leaves.  np.array(copy=True): published
    versions must be snapshots, writable and immune to later in-place
    mutation by the publisher."""
    leaves = jax.tree.leaves(tree)
    started = sum(hasattr(x, "copy_to_host_async") for x in leaves)
    host = jax.tree.map(lambda x: np.array(x, copy=True),
                        jax.device_get(tree))
    return host, len(leaves), started


def _tree_bytes(tree) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(tree)
               if hasattr(x, "nbytes"))


class ParameterService:
    def __init__(self, metrics=None):
        self._lock = threading.Lock()
        self._store: Dict[str, _Entry] = {}
        self._subs: Dict[str, List[Callable[[int, Any], None]]] = {}
        self.metrics = metrics

    def publish(self, name: str, tree: Any) -> int:
        # the host copy waits for the computation that produced the tree
        opener = span if self.metrics is None else self.metrics.span
        with opener("pilot.publish") as sp:
            host_tree, n_leaves, n_started = _to_host(tree)
            sp.nbytes = _tree_bytes(host_tree)
        if self.metrics is not None:
            self.metrics.incr("params.leaves_published", n_leaves)
            self.metrics.incr("params.leaves_copied_async", n_started)
        with self._lock:
            version = (self._store[name].version + 1
                       if name in self._store else 1)
            self._store[name] = _Entry(version, host_tree,
                                       time.monotonic())
            subs = list(self._subs.get(name, ()))
        for cb in subs:
            cb(version, host_tree)
        return version

    def fetch(self, name: str) -> Tuple[int, Any]:
        with self._lock:
            if name not in self._store:
                raise KeyError(name)
            e = self._store[name]
            return e.version, e.tree

    def fetch_if_newer(self, name: str,
                       have_version: int) -> Optional[Tuple[int, Any]]:
        with self._lock:
            e = self._store.get(name)
            if e is None or e.version <= have_version:
                return None
            return e.version, e.tree

    def version(self, name: str) -> int:
        with self._lock:
            e = self._store.get(name)
            return e.version if e else 0

    def subscribe(self, name: str,
                  callback: Callable[[int, Any], None]) -> None:
        with self._lock:
            self._subs.setdefault(name, []).append(callback)

    def place(self, name: str, sharding) -> Tuple[int, Any]:
        """Fetch + device_put under ``sharding`` (a NamedSharding or a pytree
        of them) — the cross-continuum weight broadcast."""
        version, tree = self.fetch(name)
        if isinstance(sharding, (jax.sharding.NamedSharding,
                                 jax.sharding.SingleDeviceSharding)):
            placed = jax.tree.map(lambda x: jax.device_put(x, sharding),
                                  tree)
        else:
            placed = jax.tree.map(jax.device_put, tree, sharding)
        return version, placed

    def names(self) -> List[str]:
        with self._lock:
            return sorted(self._store)
