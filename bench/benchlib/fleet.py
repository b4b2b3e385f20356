"""The paper's deployment shape, built from the program's own parts.

``edge_to_cloud`` builds the two-stage ``EdgeToCloudPipeline`` of the
paper's testbed from a configuration's ``fleet``: edge devices on their
own partitions, the broker, and a cloud stage whose consumers run the
detector's own handler and publish to a ``ParameterService``.  Around the
program the benchmark supplies only what it records:

* ``StampLog``: the program's ``MetricsRegistry``, also logging every
  per-message stamp (``produced``, ``consumed``, ``processed``, ...);
* ``PublishLog``: the program's ``ParameterService``, also keeping every
  published tree (the host copy the service stores), and counting
  published leaves that are not arrays on the device the handler ran
  on.

A configuration whose pipeline has another shape builds it in its own
``system.py``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, List, Optional, Tuple

import jax

from repro.core import (ComputeResource, EdgeToCloudPipeline,
                        ParameterService, PilotManager)
from repro.core.monitoring import MetricsRegistry

# a first call compiles; consumers and sources must not be declared lost
# while it does
HEARTBEAT_TIMEOUT_S = 900.0


class StampLog(MetricsRegistry):
    def __init__(self):
        super().__init__()
        self.log: List[Tuple[str, str, float, Optional[int]]] = []

    def stamp(self, msg_id, event, *, t=None, **meta):
        t = super().stamp(msg_id, event, t=t, **meta)
        self.log.append((msg_id, event, t, meta.get("partition")))
        return t


class PublishLog(ParameterService):
    def __init__(self, metrics=None):
        super().__init__(metrics=metrics)
        self.device = jax.devices()[0]
        self.off_device_leaves = 0
        self.history: List[Any] = []

    def publish(self, name, tree):
        for leaf in jax.tree.leaves(tree):
            if not (isinstance(leaf, jax.Array)
                    and leaf.devices() == {self.device}):
                self.off_device_leaves += 1
        version = super().publish(name, tree)
        self.history.append(self.fetch(name)[1])
        return version


@dataclass
class System:
    pipe: EdgeToCloudPipeline
    metrics: StampLog
    params: PublishLog
    release: Callable[[], None]


def edge_to_cloud(fleet: dict,
                  make_handler: Callable[[ParameterService], Callable],
                  produce: Callable, wrap: Callable[[Callable], Callable]
                  ) -> System:
    """``fleet``: ``edge_devices``, ``partitions``, ``cloud_consumers``
    and ``retention_messages`` (the broker's log truncation batch)."""
    metrics = StampLog()
    params = PublishLog(metrics)
    manager = PilotManager()
    edge = manager.submit_pilot(ComputeResource(
        tier="edge", n_workers=fleet["edge_devices"]))
    cloud = manager.submit_pilot(ComputeResource(
        tier="cloud", n_workers=fleet["cloud_consumers"]))
    pipe = EdgeToCloudPipeline(
        pilot_cloud_processing=cloud, pilot_edge=edge,
        produce_function_handler=produce,
        process_cloud_function_handler=wrap(make_handler(params)),
        n_edge_devices=fleet["edge_devices"],
        n_partitions=fleet["partitions"],
        cloud_consumers=fleet["cloud_consumers"],
        metrics=metrics, parameter_service=params,
        heartbeat_timeout_s=HEARTBEAT_TIMEOUT_S,
        truncate_logs=fleet["retention_messages"])
    return System(pipe=pipe, metrics=metrics, params=params,
                  release=manager.release_all)
