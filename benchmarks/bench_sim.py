"""Emulated Fig-3 sweep: {k-means, autoencoder} × {edge, cloud, hybrid,
fog} × {10/50/100 Mbit/s WAN} in virtual time — on the *real* pipeline
(fog cells run the genuine 3-stage edge→fog→cloud ``ContinuumPipeline``;
every row carries its per-stage tier vector).

Each cell runs a genuine ``EdgeToCloudPipeline`` under
``run(scheduler=SimExecutor(...))`` (no harness replica): broker offsets,
consumer groups, dedup and metrics are the production code paths, only
time is virtual. The real-time version of this table
(benchmarks/bench_geo.py) needs minutes of wall clock per cell because
the WAN shaper actually sleeps; this grid finishes in about a second,
bit-reproducibly::

    PYTHONPATH=src python benchmarks/bench_sim.py --check-determinism

``--check-determinism`` runs the sweep three times and fails (non-zero
exit) unless all three produce identical rows. ``--out`` writes the rows
as JSON; the row shape is pinned by ``benchmarks/BENCH_sim.schema.json``
(CI uploads the file as the ``BENCH_sim.json`` artifact on every run).
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from repro.compile_cache import enable_compilation_cache
from repro.sim.scenarios import (AUTOENCODER, KMEANS, MODELS, PLACEMENTS,
                                 FailureSpec, Scenario, format_table,
                                 run_scenario, sweep)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--messages", type=int, default=64)
    ap.add_argument("--devices", type=int, default=4)
    ap.add_argument("--points", type=int, default=2_500)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--models", nargs="*", default=list(MODELS),
                    choices=list(MODELS))
    ap.add_argument("--placements", nargs="*", default=list(PLACEMENTS),
                    choices=list(PLACEMENTS))
    from repro.sim.scenarios import WAN_BANDS
    ap.add_argument("--bands", nargs="*",
                    default=["10mbit", "50mbit", "100mbit"],
                    choices=list(WAN_BANDS))
    ap.add_argument("--with-failures", action="store_true",
                    help="crash consumer 0 mid-run (restart after 1 s) "
                         "in every scenario")
    ap.add_argument("--service-sigma", type=float, default=0.0,
                    help="lognormal service-noise sigma for every model "
                         "(0 = the noise-free Fig-3 pins)")
    ap.add_argument("--calibrated-sigma", action="store_true",
                    help="use each model's calibrated sigma from "
                         "calibration.json instead of --service-sigma")
    ap.add_argument("--speculative-factor", type=float, default=0.0,
                    help="DES straggler speculation: backup any service "
                         "charge past factor x trailing median (0 = off)")
    ap.add_argument("--check-determinism", action="store_true",
                    help="run the sweep three times; fail unless metrics "
                         "are identical across all runs")
    ap.add_argument("--out", default=None, help="write rows as JSON")
    args = ap.parse_args(argv)

    failures = (FailureSpec(at_s=2.0, consumer_idx=0,
                            restart_after_s=1.0),) \
        if args.with_failures else ()
    kw = dict(models=[MODELS[m] for m in args.models],
              placements=args.placements, bands=args.bands,
              n_messages=args.messages, n_devices=args.devices,
              n_points=args.points, seed=args.seed, failures=failures,
              service_sigma=(None if args.calibrated_sigma
                             else args.service_sigma),
              speculative_factor=args.speculative_factor)

    t0 = time.perf_counter()
    results = sweep(**kw)
    wall = time.perf_counter() - t0
    print(format_table(results))
    total_virtual = sum(r.makespan_s for r in results)
    print(f"\n{len(results)} scenarios · {total_virtual:.1f} s of virtual "
          f"pipeline time emulated in {wall*1e3:.0f} ms of wall time")

    rc = 0
    if args.check_determinism:
        rows_a = [r.row() for r in results]
        reruns = [[r.row() for r in sweep(**kw)] for _ in range(2)]
        if all(rows_a == rows_n for rows_n in reruns):
            print("determinism: OK (identical metrics across three runs "
                  "of the real pipeline under SimExecutor)")
        else:
            print("determinism: FAILED — metrics differ across runs")
            rc = 1

    if args.out:
        with open(args.out, "w") as f:
            json.dump([r.row() for r in results], f, indent=1, default=float)
    return rc


if __name__ == "__main__":
    enable_compilation_cache()
    sys.exit(main())
