"""DES-backed placement-advisor sweep: for each calibrated workload the
:class:`~repro.cost.advisor.PlacementAdvisor` emulates the *real*
pipeline under ``SimExecutor`` across
{edge, cloud, hybrid, fog} × {10/50/100 Mbit/s WAN} — the fog cells run
a genuine 3-stage edge→fog→cloud ``ContinuumPipeline`` and every row
carries its per-stage tier vector — each cell with the
workload's calibrated lognormal service noise — and ranks the placements
multi-objectively (throughput + p50/p95/p99 latency tail + WAN bytes,
optionally under ``--latency-budget`` / ``--wan-budget`` constraints and
a ``--hybrid-reduce`` sweep, with ``--speculative-factor`` straggler
speculation in the loop) — the paper's "evaluate task placement based on
multiple factors" claim as a reproducible benchmark::

    PYTHONPATH=src python benchmarks/bench_placement.py --check-determinism

``--check-determinism`` runs the whole advisory three times and fails
(non-zero exit) unless every ranked row is identical. ``--out`` writes the
rows as JSON; the row shape is pinned by
``benchmarks/BENCH_placement.schema.json`` (CI validates and uploads the
file as the ``BENCH_placement`` artifact on every run).
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from repro.compile_cache import enable_compilation_cache
from repro.cost.advisor import PlacementAdvisor
from repro.sim.scenarios import MODELS, PLACEMENTS, WAN_BANDS


def run_advisories(args):
    adv = PlacementAdvisor(n_messages=args.messages,
                           n_devices=args.devices,
                           n_points=args.points, seed=args.seed,
                           service_sigma=args.service_sigma,
                           speculative_factor=args.speculative_factor)
    reports = [adv.advise(m, placements=args.placements, bands=args.bands,
                          latency_budget=args.latency_budget,
                          wan_budget=args.wan_budget,
                          hybrid_reduce=args.hybrid_reduce,
                          metro_bands=args.metro_bands)
               for m in args.models]
    rows = [row for rep in reports for row in rep.rows()]
    return reports, rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--messages", type=int, default=32)
    ap.add_argument("--devices", type=int, default=4)
    ap.add_argument("--points", type=int, default=2_500)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--service-sigma", type=float, default=None,
                    help="lognormal service-noise sigma (default: each "
                         "workload's calibrated sigma from "
                         "calibration.json; 0 = noise-free)")
    ap.add_argument("--speculative-factor", type=float, default=0.0,
                    help="DES straggler speculation: launch a backup for "
                         "any service charge running past factor x the "
                         "trailing median (0 = off)")
    ap.add_argument("--latency-budget", type=float, default=None,
                    help="cap predicted p95 latency (s): cells over "
                         "budget are flagged infeasible and ranked last")
    ap.add_argument("--wan-budget", type=float, default=None,
                    help="cap advisory WAN megabytes per cell (same "
                         "filter-then-rank semantics)")
    ap.add_argument("--metro-bands", nargs="+", default=None,
                    help="sweep the fog placement's edge->fog metro band "
                         "(profile metro_bands names), the way --bands "
                         "sweeps the WAN hop")
    ap.add_argument("--hybrid-reduce", type=int, nargs="+", default=None,
                    help="sweep the hybrid placement's edge "
                         "pre-aggregation factor over these values")
    # nargs='+': an empty list would make --check-determinism pass
    # vacuously on zero advisory cells
    ap.add_argument("--models", nargs="+", default=sorted(MODELS),
                    choices=sorted(MODELS))
    ap.add_argument("--placements", nargs="+", default=list(PLACEMENTS),
                    choices=list(PLACEMENTS))
    ap.add_argument("--bands", nargs="+",
                    default=sorted(WAN_BANDS,
                                   key=lambda b: WAN_BANDS[b][0]),
                    choices=sorted(WAN_BANDS))
    ap.add_argument("--check-determinism", action="store_true",
                    help="run the advisory three times; fail unless the "
                         "ranked rows are identical across all runs")
    ap.add_argument("--out", default=None, help="write rows as JSON")
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    reports, rows = run_advisories(args)
    wall = time.perf_counter() - t0
    for rep in reports:
        print(rep.table())
        for band in args.bands:
            best = rep.best(band)
            flag = "" if best.feasible else " [over budget]"
            print(f"  -> {rep.model} @ {band}: place on "
                  f"{best.placement} ({best.throughput_msgs_s:.2f} msg/s, "
                  f"p95 {best.latency_p95_s:.3f} s, "
                  f"p99 {best.latency_p99_s:.3f} s){flag}")
        print()
    print(f"{len(rows)} advisory cells in {wall*1e3:.0f} ms of wall time")

    rc = 0
    if args.check_determinism:
        reruns = [run_advisories(args)[1] for _ in range(2)]
        if all(rows == other for other in reruns):
            print("determinism: OK (identical advisories across three "
                  "runs of the real pipeline under SimExecutor)")
        else:
            print("determinism: FAILED — advisories differ across runs")
            rc = 1

    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1, default=float)
    return rc


if __name__ == "__main__":
    enable_compilation_cache()
    sys.exit(main())
