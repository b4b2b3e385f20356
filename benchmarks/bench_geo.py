"""Paper Fig 3 (right): geographic distribution. The data source sits on
"XSEDE (US)" and processing on "LRZ (Germany)"; the WAN between them is
the paper's measured band (140–160 ms RTT, 60–100 Mbit/s). We sweep the
WAN parameters across that band and compare against the local baseline
for the light (baseline/k-means) vs heavy (auto-encoder) workloads —
reproducing the paper's finding that intercontinental transfer caps the
light models while the compute-bound models don't notice the network.

Measured on the DES, not the wall clock: this bench reuses the scale
benchmark's open-loop driver — a Poisson arrival plan on a ``SimClock``
under ``SimExecutor``, per-message compute priced by the *calibrated*
cost model (``CostModel.service_model``) and the WAN as a deterministic
``sleep=False`` shaper — so a cell takes milliseconds of wall time and
every number is bit-reproducible for a given seed.  (The seed-era
version ran threaded consumers with real ``time.sleep`` shaping and real
kernel compute on the driver: minutes of wall clock per sweep, numbers
that moved with host load.)

Throughput is ``processed / makespan`` in *virtual* seconds: offered
load (``--rate-hz``) is set above the WAN band's drain rate, so a
network-capped cell shows up as a stretched makespan, exactly like the
paper's saturated pipeline.
"""
from __future__ import annotations

import argparse
import json

from repro.compile_cache import enable_compilation_cache
from repro.core import (ComputeResource, EdgeToCloudPipeline, PilotManager,
                        WanShaper)
from repro.core.executor import SimExecutor
from repro.core.monitoring import MetricsRegistry
from repro.cost.model import default_cost_model
from repro.ml.datagen import message_nbytes
from repro.sim.clock import SimClock
from repro.sim.scenarios import arrival_process


def run(model_name: str, n_points: int, n_messages: int,
        band: tuple | None, *, rate_hz: float, partitions: int = 4,
        seed: int = 0):
    # fresh shaper per run: its token bucket (_available_at) is absolute
    # virtual time, and every run starts a new clock at zero
    wan = (None if band is None else
           WanShaper(bandwidth_bps=band[0], rtt_s=band[1], sleep=False))
    cost = default_cost_model()
    clock = SimClock()
    metrics = MetricsRegistry(clock=clock)
    mgr = PilotManager()
    edge = mgr.submit_pilot(ComputeResource(tier="edge",
                                            n_workers=partitions))
    cloud = mgr.submit_pilot(ComputeResource(tier="cloud",
                                             n_workers=partitions))
    nbytes = message_nbytes(n_points)
    payload = bytes(nbytes)     # raw bytes: compute is *priced*, not run
    pipe = EdgeToCloudPipeline(
        pilot_cloud_processing=cloud, pilot_edge=edge,
        produce_function_handler=lambda ctx: payload,
        process_cloud_function_handler=lambda ctx, data=None: None,
        n_edge_devices=partitions, n_partitions=partitions,
        cloud_consumers=partitions, topic_name="geo",
        wan_shaper=wan, metrics=metrics, clock=clock)
    # calibrated per-stage charges: edge pre-aggregation next to the
    # generator, the full model on the cloud consumers ("baseline" is
    # the old raw-mean pass: effectively free, pure network)
    if model_name == "baseline":
        stage_times = {}
    else:
        stage_times = {
            "produce": cost.preprocess_s(model_name, n_points, "edge"),
            "process_cloud": cost.model_compute_s(model_name, n_points,
                                                  "cloud"),
        }
    times = arrival_process("poisson", rate_hz).times(n_messages, seed)
    plan = [times[i::partitions] for i in range(partitions)]
    ex = SimExecutor(clock, service_model=cost.service_model(stage_times))
    res = pipe.run(scheduler=ex, timeout_s=float(times[-1]) + 1200.0,
                   collect_results=False, arrival_plan=plan)
    m = res.metrics
    first = m.first_stamp("produced") or 0.0
    last = m.last_stamp("processed") or first
    makespan = max(last - first, 1e-9)
    lat = m.latencies("produced", "processed")
    lat.sort()
    mgr.release_all()
    return {"model": model_name, "n_points": n_points,
            "wan": "none" if wan is None else
            f"{wan.bandwidth_bps/1e6:.0f}Mbit/{wan.rtt_s*1e3:.0f}ms",
            "processed": res.n_processed,
            "msgs_per_s": res.n_processed / makespan,
            "mb_per_s": res.n_processed * nbytes / makespan / 1e6,
            "latency_mean_ms": (sum(lat) / len(lat) * 1e3) if lat else 0.0,
            "latency_p95_ms": (lat[min(len(lat) - 1,
                                       int(0.95 * len(lat)))] * 1e3)
                              if lat else 0.0}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--messages", type=int, default=200)
    ap.add_argument("--points", type=int, default=2_500)
    ap.add_argument("--rate-hz", type=float, default=40.0,
                    help="aggregate open-loop offered rate (set above the "
                         "WAN band's drain rate so a network cap shows as "
                         "a stretched makespan)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--models", nargs="*",
                    default=["baseline", "kmeans", "autoencoder"])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    # the paper's iPerf band endpoints + local baseline
    bands = [None, (100e6, 0.140), (60e6, 0.160)]
    rows = []
    print(f"message: {message_nbytes(args.points)/1e3:.0f} KB, "
          f"{args.messages} msgs at {args.rate_hz:.0f} Hz offered")
    print(f"{'model':>12} {'wan':>15} {'msg/s':>9} {'MB/s':>8} "
          f"{'lat ms':>9} {'p95 ms':>9}")
    for model in args.models:
        for band in bands:
            r = run(model, args.points, args.messages, band,
                    rate_hz=args.rate_hz, seed=args.seed)
            rows.append(r)
            print(f"{r['model']:>12} {r['wan']:>15} "
                  f"{r['msgs_per_s']:9.2f} {r['mb_per_s']:8.2f} "
                  f"{r['latency_mean_ms']:9.1f} {r['latency_p95_ms']:9.1f}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return rows


if __name__ == "__main__":
    enable_compilation_cache()
    main()
