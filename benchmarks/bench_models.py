"""Paper Fig 3 (left): throughput/latency by model type and message size.

Streams each message-size sweep through the three outlier detectors
(k-means / isolation forest / auto-encoder) on the cloud pilot and reports
throughput + latency per model — the paper's model-complexity trade-off
(k-means ≫ isolation forest ≫ auto-encoder; ~5× at 10k points).

``--fused`` additionally runs the beyond-paper variant: instead of the
paper-faithful per-message python loop, consumers batch k messages and run
one jitted vectorized call — the §Perf "batched consumer" optimization.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.compile_cache import enable_compilation_cache
from repro.core import ComputeResource, EdgeToCloudPipeline, PilotManager
from repro.ml import AutoEncoder, IsolationForest, KMeans, MiniAppGenerator
from repro.ml.datagen import message_nbytes


def make_processor(model_name: str, train: bool = True):
    if model_name == "kmeans":
        return KMeans(n_clusters=25).make_processor(train=train)
    if model_name == "iforest":
        return IsolationForest(n_trees=100).make_processor(train=train)
    if model_name == "autoencoder":
        return AutoEncoder().make_processor(train=train)
    raise ValueError(model_name)


def run_model(model_name: str, n_points: int, n_messages: int,
              partitions: int = 4, repeats: int = 1):
    rows = []
    for rep in range(repeats):
        mgr = PilotManager()
        edge = mgr.submit_pilot(ComputeResource(tier="edge",
                                                n_workers=partitions))
        cloud = mgr.submit_pilot(ComputeResource(tier="cloud",
                                                 n_workers=partitions))
        gen = MiniAppGenerator(n_points=n_points, seed=rep)
        pipe = EdgeToCloudPipeline(
            pilot_cloud_processing=cloud, pilot_edge=edge,
            produce_function_handler=gen.make_producer(),
            process_cloud_function_handler=make_processor(model_name),
            n_edge_devices=partitions)
        res = pipe.run(n_messages=n_messages, timeout_s=1200)
        tp = res.throughput()
        lat = res.latency()
        rows.append({
            "model": model_name, "n_points": n_points, "rep": rep,
            "processed": res.n_processed,
            "msgs_per_s": tp["msgs_per_s"],
            "mb_per_s": tp["bytes_per_s"] / 1e6,
            "latency_mean_ms": lat.get("mean_s", 0) * 1e3,
            "proc_ms": np.mean(res.metrics.latencies(
                "consumed", "processed")) * 1e3,
        })
        mgr.release_all()
    return rows


def run_fused(model_name: str, n_points: int, n_messages: int,
              batch: int = 8):
    """Beyond-paper: one jitted call over `batch` stacked messages."""
    import jax.numpy as jnp
    gen = MiniAppGenerator(n_points=n_points, seed=0)
    msgs = [gen.sample() for _ in range(n_messages)]
    if model_name == "kmeans":
        km = KMeans(n_clusters=25)
        st = km.init(msgs[0])
        fn = lambda x: km.assign(st, x.reshape(-1, 32))
    elif model_name == "autoencoder":
        ae = AutoEncoder()
        st = ae.init()
        fn = lambda x: ae.outlier_scores(st, x.reshape(-1, 32))
    else:
        return None
    stacked = [np.stack(msgs[i:i + batch])
               for i in range(0, n_messages - batch + 1, batch)]
    fn(stacked[0])                                      # compile
    t0 = time.monotonic()
    for s in stacked:
        r = fn(s)
    (r[0] if isinstance(r, tuple) else r).block_until_ready()
    dt = time.monotonic() - t0
    msgs_done = len(stacked) * batch
    return {"model": f"{model_name}+fused", "n_points": n_points,
            "msgs_per_s": msgs_done / dt,
            "mb_per_s": msgs_done * message_nbytes(n_points) / dt / 1e6}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--messages", type=int, default=48)
    ap.add_argument("--points", type=int, nargs="*",
                    default=[250, 2_500, 10_000])
    ap.add_argument("--models", nargs="*",
                    default=["kmeans", "iforest", "autoencoder"])
    ap.add_argument("--fused", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    all_rows = []
    print(f"{'model':>14} {'points':>7} {'msg/s':>9} {'MB/s':>8} "
          f"{'lat ms':>9} {'proc ms':>9}")
    for model in args.models:
        for n_points in args.points:
            n_msgs = args.messages if model != "iforest" else max(
                8, args.messages // 4)       # iforest is slow on CPU
            rows = run_model(model, n_points, n_msgs)
            m = np.mean([r["msgs_per_s"] for r in rows])
            mb = np.mean([r["mb_per_s"] for r in rows])
            lat = np.mean([r["latency_mean_ms"] for r in rows])
            pr = np.mean([r["proc_ms"] for r in rows])
            print(f"{model:>14} {n_points:7d} {m:9.2f} {mb:8.2f} "
                  f"{lat:9.1f} {pr:9.1f}")
            all_rows.extend(rows)
    if args.fused:
        for model in ("kmeans", "autoencoder"):
            for n_points in args.points:
                row = run_fused(model, n_points, args.messages)
                if row:
                    print(f"{row['model']:>14} {n_points:7d} "
                          f"{row['msgs_per_s']:9.2f} "
                          f"{row['mb_per_s']:8.2f}         -         -")
                    all_rows.append(row)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(all_rows, f, indent=1)
    return all_rows


if __name__ == "__main__":
    enable_compilation_cache()
    main()
