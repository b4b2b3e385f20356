"""Paper Fig 2: baseline throughput + latency by message size and partition
count. Edge data source, broker and processing in one "cloud" (this host);
message sizes 25–10,000 points × 32 features (7 KB–2.6 MB); partitions
1/2/4 with one partition per simulated edge device; 512 messages per run in
the paper — scaled by --messages for CPU time budgets.
"""
from __future__ import annotations

import argparse
import json

import numpy as np

from repro.compile_cache import enable_compilation_cache
from repro.core import ComputeResource, EdgeToCloudPipeline, PilotManager
from repro.ml import MiniAppGenerator, message_nbytes
from repro.ml.datagen import PAPER_POINTS


def run_cell(n_points: int, n_partitions: int, n_messages: int,
             repeats: int = 3, process=None):
    rows = []
    for rep in range(repeats):
        mgr = PilotManager()
        edge = mgr.submit_pilot(
            ComputeResource(tier="edge", n_workers=n_partitions))
        cloud = mgr.submit_pilot(
            ComputeResource(tier="cloud", n_workers=n_partitions))
        gen = MiniAppGenerator(n_points=n_points, seed=rep)
        proc = process or (lambda ctx, data=None: float(np.mean(data)))
        pipe = EdgeToCloudPipeline(
            pilot_cloud_processing=cloud, pilot_edge=edge,
            produce_function_handler=gen.make_producer(),
            process_cloud_function_handler=proc,
            n_edge_devices=n_partitions, n_partitions=n_partitions)
        res = pipe.run(n_messages=n_messages, timeout_s=600)
        tp = res.throughput()
        lat = res.latency()
        rows.append({
            "n_points": n_points, "partitions": n_partitions, "rep": rep,
            "msg_bytes": message_nbytes(n_points),
            "processed": res.n_processed,
            "msgs_per_s": tp["msgs_per_s"],
            "mb_per_s": tp["bytes_per_s"] / 1e6,
            "latency_mean_ms": lat.get("mean_s", 0) * 1e3,
            "latency_p95_ms": lat.get("p95_s", 0) * 1e3,
        })
        mgr.release_all()
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--messages", type=int, default=128,
                    help="messages per run (paper: 512)")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--points", type=int, nargs="*",
                    default=list(PAPER_POINTS))
    ap.add_argument("--partitions", type=int, nargs="*", default=[1, 2, 4])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    all_rows = []
    print(f"{'points':>7} {'parts':>5} {'KB/msg':>8} {'msg/s':>9} "
          f"{'MB/s':>8} {'lat ms':>8}")
    for n_points in args.points:
        for parts in args.partitions:
            rows = run_cell(n_points, parts, args.messages, args.repeats)
            m = np.mean([r["msgs_per_s"] for r in rows])
            mb = np.mean([r["mb_per_s"] for r in rows])
            lat = np.mean([r["latency_mean_ms"] for r in rows])
            print(f"{n_points:7d} {parts:5d} "
                  f"{message_nbytes(n_points)/1e3:8.0f} {m:9.1f} "
                  f"{mb:8.1f} {lat:8.1f}")
            all_rows.extend(rows)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(all_rows, f, indent=1)
    # paper's qualitative claim: throughput (MB/s) grows with message size
    # and with partition count
    return all_rows


if __name__ == "__main__":
    enable_compilation_cache()
    main()
