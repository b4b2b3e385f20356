"""Band-drop golden: mid-run WAN degradation, online re-advisory, live
placement hot-swap — static vs. re-advised, unsharded vs. tier-cut
sharded, all bit-reproducible.

The cell is a cloud placement on the 100 Mbit/s WAN whose link drops to
10 Mbit/s at t=8 s virtual (a :class:`~repro.sim.scenarios.DriftSpec`
scheduled as an ordinary DES event).  The *static* run rides out the
degraded band; the *re-advised* run has a
:class:`~repro.cost.readvisor.ReAdvisor` watching the observed hop
delay, which re-places the processing stage cloud→fog mid-run
(``rebind_stage`` + epoch-based consumer migration) and recovers the
tail.  The same re-advised scenario then runs under the 2-shard tier
cut (:func:`~repro.sim.shard.run_drift_sharded`, decisions shipped over
the window-sync control channel) and must match the unsharded run
bit-for-bit on the :data:`~repro.sim.shard.DRIFT_PARITY_COLS`.

The report (``--out``) is pinned by ``benchmarks/BENCH_drift.schema.json``
and committed at the repo root as ``BENCH_drift.json``; CI re-runs the
golden end-to-end with ``--check-determinism`` (three sweeps, identical
rows required) and validates the fresh report against the schema::

    PYTHONPATH=src python benchmarks/bench_drift.py --check-determinism \\
        --out BENCH_drift.json
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from dataclasses import replace

from repro.compile_cache import enable_compilation_cache
from repro.cost.readvisor import ReAdviseSpec
from repro.sim.scenarios import DriftSpec, Scenario, run_scenario
from repro.sim.shard import DRIFT_PARITY_COLS, run_drift_sharded


def golden(args) -> Scenario:
    """The re-advised band-drop cell (static variant: ``readvise=None``).

    Producers are paced (``gen_s_per_point``) to ~64 % utilisation of
    the healthy 100 Mbit/s WAN, so the pre-drift baseline is stable and
    the advisor's quiet period is a real property, not an accident of
    saturation.  After the drop to 10 Mbit/s the observed hop delay
    (~5 s+ per message) dwarfs the fog prediction by far more than the
    3x hysteresis, so the swap decision is unambiguous."""
    return Scenario(
        placement="cloud", wan_band="100mbit",
        n_messages=args.messages, n_points=args.points,
        gen_s_per_point=1.28e-4, seed=args.seed,
        speculative_factor=2.0,
        drift=(DriftSpec(at_s=args.drift_at, kind="band",
                         band=args.drift_band),),
        readvise=ReAdviseSpec(interval_s=2.0, min_samples=2,
                              hysteresis=3.0),
    )


def run_cell(sc: Scenario, *, shard_mode: str) -> dict:
    """One full golden evaluation: static row, re-advised row, and the
    shards=1 vs shards=2 parity projections.  Everything in the
    returned dict is deterministic (virtual-time) data."""
    static_sc = replace(sc, readvise=None)
    static = run_scenario(static_sc).row()
    readvised = run_scenario(sc).row()
    parity1 = run_drift_sharded(sc, shards=1)
    parity2 = run_drift_sharded(sc, shards=2, mode=shard_mode)
    return {"static": static, "readvised": readvised,
            "parity1": parity1, "parity2": parity2}


def check_cell(cell: dict) -> list:
    """Golden acceptance: swap happened, tail recovered, shards agree.
    Returns a list of violation strings (empty = pass)."""
    bad = []
    static, readvised = cell["static"], cell["readvised"]
    if static["swaps"]:
        bad.append(f"static run swapped: {static['swaps']}")
    swaps = readvised["swaps"]
    if len(swaps) != 1 or swaps[0]["from"] != "cloud" \
            or swaps[0]["to"] != "fog":
        bad.append(f"expected exactly one cloud->fog swap, got {swaps}")
    if not readvised["lat_p95_s"] < static["lat_p95_s"]:
        bad.append(f"re-advised p95 {readvised['lat_p95_s']:.3f} s did "
                   f"not beat static {static['lat_p95_s']:.3f} s")
    if readvised["processed"] != readvised["messages"]:
        bad.append(f"re-advised run processed {readvised['processed']} "
                   f"of {readvised['messages']} (exactly-once broke "
                   f"across the migration)")
    for col in DRIFT_PARITY_COLS:
        if cell["parity1"][col] != cell["parity2"][col]:
            bad.append(f"shard parity: {col} differs — "
                       f"shards=1 {cell['parity1'][col]!r} vs "
                       f"shards=2 {cell['parity2'][col]!r}")
    return bad


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--messages", type=int, default=60)
    ap.add_argument("--points", type=int, default=25_000)
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--drift-at", type=float, default=8.0,
                    help="virtual time of the WAN band drop")
    ap.add_argument("--drift-band", default="10mbit",
                    help="degraded WAN band name (profile wan_bands)")
    ap.add_argument("--shard-mode", default="inline",
                    choices=["inline", "mp"],
                    help="transport for the shards=2 parity run")
    ap.add_argument("--check-determinism", action="store_true",
                    help="run the golden three times; fail unless all "
                         "deterministic columns are identical")
    ap.add_argument("--out", default=None, help="write the report as JSON")
    args = ap.parse_args(argv)

    sc = golden(args)
    t0 = time.perf_counter()
    cell = run_cell(sc, shard_mode=args.shard_mode)
    wall = time.perf_counter() - t0

    static, readvised = cell["static"], cell["readvised"]
    speedup = static["lat_p95_s"] / readvised["lat_p95_s"]
    print(f"static:     p95 {static['lat_p95_s']:8.3f} s   makespan "
          f"{static['makespan_s']:7.2f} s   swaps {len(static['swaps'])}")
    print(f"re-advised: p95 {readvised['lat_p95_s']:8.3f} s   makespan "
          f"{readvised['makespan_s']:7.2f} s   swaps "
          f"{len(readvised['swaps'])}")
    for s in readvised["swaps"]:
        print(f"  swap {s['stage']}: {s['from']} -> {s['to']} "
              f"(decided t={s['t_decided']:.2f} s, applied "
              f"t={s['t_applied']:.2f} s, observed hop "
              f"{s['observed_hop_s']:.2f} s)")
    print(f"tail recovery: {speedup:.1f}x on p95; shards=2 "
          f"({cell['parity2']['mode']}) synced "
          f"{cell['parity2']['windows']} windows "
          f"[{wall*1e3:.0f} ms wall]")

    rc = 0
    bad = check_cell(cell)
    for b in bad:
        print(f"golden violation: {b}")
        rc = 1

    if args.check_determinism and rc == 0:
        reruns = [run_cell(sc, shard_mode=args.shard_mode)
                  for _ in range(2)]
        if all(cell == other for other in reruns):
            print("determinism: OK (identical static/re-advised/sharded "
                  "metrics — swap timestamps included — across three "
                  "runs)")
        else:
            print("determinism: FAILED — metrics differ across runs")
            rc = 1

    if args.out:
        report = {
            "config": {
                "messages": args.messages, "points": args.points,
                "seed": args.seed, "drift_at_s": args.drift_at,
                "drift_band": args.drift_band,
                "shard_mode": args.shard_mode,
            },
            "headline": {
                "static_p95_s": static["lat_p95_s"],
                "readvised_p95_s": readvised["lat_p95_s"],
                "p95_speedup": speedup,
                "parity_ok": not any("parity" in b for b in bad),
            },
            "static": static,
            "readvised": readvised,
            "parity": {"shards1": cell["parity1"],
                       "shards2": cell["parity2"]},
        }
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1, default=float)
    return rc


if __name__ == "__main__":
    enable_compilation_cache()
    sys.exit(main())
