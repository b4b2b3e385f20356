"""Quickstart — the paper's Listings 1 & 2 in ~40 lines.

Acquire an edge pilot and a cloud pilot (step 1), define the three FaaS
functions, instantiate the EdgeToCloudPipeline (step 2), run 128 messages,
and read the linked metrics (step 3).

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core import ComputeResource, EdgeToCloudPipeline, PilotManager
from repro.ml import KMeans, MiniAppGenerator

# --- step 1: acquire pilots (resource management, no workload code) --------
manager = PilotManager()
pilot_edge = manager.submit_pilot(
    ComputeResource(tier="edge", n_workers=4, memory_gb=4))     # RasPi-class
pilot_cloud = manager.submit_pilot(
    ComputeResource(tier="cloud", n_workers=4, memory_gb=44))   # LRZ large VM

# --- FaaS functions (Listing 1) ---------------------------------------------
generator = MiniAppGenerator(n_points=2_500, n_clusters=25, seed=7)
produce_edge = generator.make_producer()            # sensing / data generation


def process_edge(context, data=None):
    """Edge pre-processing: drop non-finite rows before the WAN hop."""
    return data[np.isfinite(data).all(axis=1)]


kmeans = KMeans(n_clusters=25, n_features=32)
process_cloud = kmeans.make_processor(train=True)   # score + update model

# --- step 2: instantiate + run (Listing 2) -----------------------------------
pipeline = EdgeToCloudPipeline(
    pilot_cloud_processing=pilot_cloud,
    pilot_edge=pilot_edge,
    produce_function_handler=produce_edge,
    process_edge_function_handler=process_edge,
    process_cloud_function_handler=process_cloud,
    function_context={"model": "kmeans", "n_clusters": 25},
)
result = pipeline.run(n_messages=128)

# --- step 3: monitoring -------------------------------------------------------
print(f"processed {result.n_processed}/{result.n_produced} messages "
      f"in {result.wall_s:.2f}s")
tp = result.throughput()
print(f"throughput: {tp['msgs_per_s']:.0f} msg/s, "
      f"{tp['bytes_per_s']/1e6:.1f} MB/s")
print(f"end-to-end latency: {result.latency()}")
print("per-hop latency:")
for hop, stats in result.per_hop().items():
    print(f"  {hop:25s} mean {stats['mean_s']*1e3:7.2f} ms")
outliers = sum(r["n_outliers"] for r in result.results)
print(f"outliers flagged across stream: {outliers}")
manager.release_all()
if result.n_processed != result.n_produced:
    raise SystemExit(f"lost {result.n_produced - result.n_processed} "
                     f"messages: {result.metrics.events('task_error')[:1]}")
