"""Million/ten-million-event DES scale benchmark: simulator speed *and
memory* as tracked perf surfaces.

Drives the *real* ``EdgeToCloudPipeline`` under ``SimExecutor`` with
open-loop arrival processes (Poisson / diurnal / flash-crowd / recorded
trace replay) and raw ``bytes`` payloads, so the measured cost is the
event loop itself — scheduler heap, actor stepping, broker fan-out,
poll/wake — not numpy serialization.  The headline cell is the
full-size Poisson run; the sweep adds diurnal, flash-crowd, and (with
``--trace``) trace-replay cells at a tenth the size so every arrival
process stays on the tracked surface.

Memory mode (the 10M-event configuration)::

    PYTHONPATH=src python benchmarks/bench_des_scale.py \\
        --messages 2500000 --streaming-metrics --truncate-logs 4096 \\
        --rss --trace benchmarks/traces/azure_functions_like.txt \\
        --out BENCH_des_scale.json

``--streaming-metrics`` folds message traces into fixed-memory latency
sketches (``MetricsRegistry(streaming=True)``), ``--truncate-logs N``
reclaims broker-log prefixes below the committed offsets in batches of
``N``, and ``--rss`` measures *per-cell* peak RSS (``VmHWM`` reset via
``/proc/self/clear_refs`` before each cell) instead of the process-
lifetime high-water mark — together they hold peak RSS flat in run
length.  ``--max-rss-mb`` turns the headline cell's peak RSS into a
hard gate (CI's memory ceiling).

Two kinds of numbers per row:

* **deterministic** (virtual time, event counts, latency percentiles,
  bytes, truncation counters) — bit-identical for a given seed, gated
  by ``--check-determinism`` (three full sweeps must agree);
* **wall-clock** (``wall_s``, ``events_per_s``, ``rss_mb``,
  ``peak_rss_mb``) — the perf trajectory.  Excluded from the
  determinism comparison.

The committed ``BENCH_des_scale.json`` records the pre-rework baseline
(measured on this machine before the event-loop fixes) next to the
headline events/s, so the speedup is auditable.

Row shape is pinned by ``benchmarks/BENCH_des_scale.schema.json``
(validated in CI by ``tools/check_bench_schema.py``; the file is
uploaded as a CI artifact on every run).
"""
from __future__ import annotations

import argparse
import cProfile
import io
import json
import pstats
import resource
import sys
import time

from repro.compile_cache import enable_compilation_cache
from repro.core import ComputeResource, EdgeToCloudPipeline, PilotManager
from repro.core.executor import SimExecutor
from repro.core.monitoring import MetricsRegistry
from repro.sim.clock import SimClock
from repro.sim.scenarios import arrival_process
from repro.sim.shard import run_scale_sharded

# Pre-rework event-loop throughput, measured on the commit just before
# the compacting-heap / actor-slot-reuse / waiter-index changes (same
# machine, same SimExecutor surface).  Kept in the committed JSON so the
# headline speedup is anchored to a recorded number, not folklore.
BASELINE = {
    "events_per_s": 3188.0,
    "config": ("20000 msgs / 100 devices / 1000 consumers, kmeans cloud "
               "100mbit closed-loop (pre-rework event loop: O(n) "
               "cancelled-event sweeps, per-step event allocation, "
               "O(all-tasks) append scans, per-join wake-all)"),
}

# row keys compared by --check-determinism (wall-clock keys excluded)
DETERMINISTIC_KEYS = (
    "arrival", "messages", "devices", "consumers", "payload_bytes",
    "seed", "streaming_metrics", "processed", "duplicates", "events",
    "truncated_msgs", "makespan_s", "lat_p50_s", "lat_p95_s", "wan_bytes",
)


# row keys that must be bit-identical between the single-process and
# sharded runs of the same cell (--shard-parity); "events" is excluded:
# each shard runs its own monitor ticks, so the *scheduler* event count
# differs even though every message-level column is identical
PARITY_KEYS = (
    "processed", "duplicates", "truncated_msgs", "makespan_s",
    "lat_p50_s", "lat_p95_s", "wan_bytes",
)


def _arrival(kind: str, rate_hz: float, trace: str = None):
    # the bench's arrival parameters live in repro.sim.scenarios so the
    # sharded runner draws the *same* streams (shard parity depends on
    # bit-identical arrival times)
    return arrival_process(kind, rate_hz, trace)


def _reset_peak_rss() -> bool:
    """Reset the kernel's per-process RSS high-water mark (``VmHWM``).
    Returns False where unsupported (non-Linux/procfs)."""
    try:
        with open("/proc/self/clear_refs", "w") as f:
            f.write("5")
        return True
    except OSError:
        return False


def _peak_rss_mb() -> float:
    """Peak RSS in MB since the last ``_reset_peak_rss`` (``VmHWM``),
    falling back to the process-lifetime ``ru_maxrss``."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmHWM:"):
                    return int(line.split()[1]) / 1024.0
    except (OSError, ValueError, IndexError):
        pass
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def run_cell(*, arrival: str, messages: int, devices: int, consumers: int,
             rate_hz: float, payload_bytes: int, service_s: float,
             seed: int, streaming: bool = False, truncate_logs=None,
             trace: str = None, per_cell_rss: bool = False) -> dict:
    """One open-loop run on the genuine pipeline; returns a bench row."""
    if per_cell_rss:
        _reset_peak_rss()
    clock = SimClock()
    metrics = MetricsRegistry(clock=clock, streaming=streaming)
    mgr = PilotManager(devices=())
    edge = mgr.submit_pilot(ComputeResource(tier="edge", n_workers=devices))
    cloud = mgr.submit_pilot(
        ComputeResource(tier="cloud", n_workers=consumers))
    payload = bytes(payload_bytes)   # raw bytes: passthrough serialization
    pipe = EdgeToCloudPipeline(
        pilot_cloud_processing=cloud, pilot_edge=edge,
        produce_function_handler=lambda ctx: payload,
        process_cloud_function_handler=lambda ctx, data=None: None,
        n_edge_devices=devices, n_partitions=devices,
        cloud_consumers=consumers, topic_name="des-scale",
        truncate_logs=truncate_logs, metrics=metrics, clock=clock)
    times = _arrival(arrival, rate_hz, trace).times(messages, seed)
    plan = [times[i::devices] for i in range(devices)]
    ex = SimExecutor(
        clock,
        service_model=((lambda stage, ctx, data: service_s)
                       if service_s > 0.0 else None))

    t0 = time.perf_counter()
    res = pipe.run(timeout_s=float(times[-1]) + 120.0,
                   collect_results=False, scheduler=ex, arrival_plan=plan)
    wall = time.perf_counter() - t0
    topic_name = pipe._topics[0].name
    truncated = sum(t.truncated_msgs for t in pipe._topics)
    mgr.release_all()

    m = res.metrics
    if streaming:
        p50 = m.percentile(0.50, "produced", "processed")
        p95 = m.percentile(0.95, "produced", "processed")
    else:
        lat = m.latencies("produced", "processed")
        lat.sort()
        n = len(lat)
        p50 = lat[n // 2] if n else 0.0
        p95 = lat[min(n - 1, int(0.95 * n))] if n else 0.0
    first = m.first_stamp("produced") or 0.0
    last = m.last_stamp("processed") or first
    events = ex.sched.executed
    # ru_maxrss is the process-lifetime high-water mark (KB on Linux):
    # monotone across cells, so the largest cell owns the reported peak.
    # peak_rss_mb is the per-cell VmHWM when --rss reset it above,
    # otherwise it duplicates the lifetime mark.
    rss_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
    return {
        "arrival": arrival, "messages": messages, "devices": devices,
        "consumers": consumers, "payload_bytes": payload_bytes,
        "seed": seed,
        "streaming_metrics": streaming,
        "processed": res.n_processed,
        "duplicates": int(m.counter("pipeline.duplicates_dropped")),
        "events": events,
        "truncated_msgs": truncated,
        "makespan_s": max(last - first, 1e-9),
        "lat_p50_s": p50,
        "lat_p95_s": p95,
        "wan_bytes": m.counter(f"topic.{topic_name}.bytes_in"),
        "wall_s": wall,
        "events_per_s": events / max(wall, 1e-9),
        "rss_mb": rss_mb,
        "peak_rss_mb": _peak_rss_mb() if per_cell_rss else rss_mb,
    }


def run_sweep(args) -> list:
    cells = [
        # headline: full size, Poisson
        dict(arrival="poisson", messages=args.messages),
        # arrival-process coverage at a tenth the size
        dict(arrival="diurnal", messages=max(args.messages // 10, 1000)),
        dict(arrival="flash", messages=max(args.messages // 10, 1000)),
    ]
    if args.trace:
        cells.append(
            dict(arrival="trace", messages=max(args.messages // 10, 1000)))
    rows = []
    for cell in cells:
        row = run_cell(arrival=cell["arrival"], messages=cell["messages"],
                       devices=args.devices, consumers=args.consumers,
                       rate_hz=args.rate_hz,
                       payload_bytes=args.payload_bytes,
                       service_s=args.service_s, seed=args.seed,
                       streaming=args.streaming_metrics,
                       truncate_logs=args.truncate_logs,
                       trace=args.trace, per_cell_rss=args.rss)
        print(f"  {row['arrival']:>8}  {row['messages']:>9,} msgs  "
              f"{row['events']:>9,} events  {row['wall_s']:6.1f} s wall  "
              f"{row['events_per_s']:>9,.0f} ev/s  "
              f"{row['peak_rss_mb']:6.0f} MB peak rss  "
              f"{row['truncated_msgs']:>9,} truncated")
        rows.append(row)
    return rows


def run_profile(args, out_path: str = "PROFILE_des.txt") -> None:
    """cProfile a reduced headline cell and report the top-25 functions
    by cumulative time — the single-thread hot-loop map that guided the
    lock-elision / attribute-hoisting squeeze.  Prints to stdout and
    writes the same table to ``out_path`` (a CI artifact)."""
    messages = min(args.messages, 30_000)
    prof = cProfile.Profile()
    prof.enable()
    run_cell(arrival="poisson", messages=messages, devices=args.devices,
             consumers=args.consumers, rate_hz=args.rate_hz,
             payload_bytes=args.payload_bytes, service_s=args.service_s,
             seed=args.seed, streaming=args.streaming_metrics,
             truncate_logs=args.truncate_logs)
    prof.disable()
    buf = io.StringIO()
    stats = pstats.Stats(prof, stream=buf)
    stats.sort_stats("cumulative").print_stats(25)
    table = buf.getvalue()
    header = (f"cProfile of one reduced headline cell "
              f"({messages:,} msgs / {args.devices} devices / "
              f"{args.consumers} consumers), top 25 by cumulative time\n")
    print(f"\n{header}{table}")
    with open(out_path, "w") as f:
        f.write(header + table)
    print(f"wrote {out_path}")


def run_sharded(args) -> dict:
    """The sharded headline cell: same messages/seed/arrival as the
    single-process headline, split ``--shards`` ways."""
    row = run_scale_sharded(
        arrival="poisson", messages=args.messages, devices=args.devices,
        consumers=args.consumers, rate_hz=args.rate_hz,
        payload_bytes=args.payload_bytes, service_s=args.service_s,
        seed=args.seed, shards=args.shards,
        streaming=args.streaming_metrics,
        truncate_logs=args.truncate_logs, mode=args.shard_mode)
    print(f"  sharded x{row['shards']} ({row['mode']}):  "
          f"{row['messages']:>9,} msgs  {row['events']:>9,} events  "
          f"{row['wall_s']:6.1f} s wall  "
          f"{row['agg_events_per_s']:>9,.0f} ev/s aggregate  "
          f"({row['cpu_critical_s']:.1f} s critical-path cpu, "
          f"{row['windows']} window(s))")
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--messages", type=int, default=1_000_000,
                    help="messages in the headline Poisson cell "
                         "(diurnal/flash/trace cells run a tenth of this)")
    ap.add_argument("--devices", type=int, default=100)
    ap.add_argument("--consumers", type=int, default=1000)
    ap.add_argument("--rate-hz", type=float, default=20_000.0,
                    help="aggregate open-loop arrival rate")
    ap.add_argument("--payload-bytes", type=int, default=64)
    ap.add_argument("--service-s", type=float, default=0.001,
                    help="deterministic per-message service charge")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None, metavar="FILE",
                    help="also run a trace-replay cell from this "
                         "timestamp file (see benchmarks/traces/)")
    ap.add_argument("--streaming-metrics", action="store_true",
                    help="MetricsRegistry(streaming=True): sketch-backed "
                         "percentiles, memory independent of run length")
    ap.add_argument("--truncate-logs", type=int, default=None, metavar="N",
                    help="reclaim broker-log prefixes below the committed "
                         "offsets in batches of N messages")
    ap.add_argument("--rss", action="store_true",
                    help="measure per-cell peak RSS (VmHWM reset before "
                         "each cell) instead of the process-lifetime mark")
    ap.add_argument("--max-rss-mb", type=float, default=None,
                    help="fail unless the headline cell's peak RSS stays "
                         "under this ceiling (CI memory gate)")
    ap.add_argument("--check-determinism", action="store_true",
                    help="run the sweep three times; fail unless every "
                         "deterministic column is identical")
    ap.add_argument("--profile", action="store_true",
                    help="cProfile a reduced headline cell first: top-25 "
                         "cumulative functions to stdout + PROFILE_des.txt")
    ap.add_argument("--shards", type=int, default=0, metavar="N",
                    help="also run the headline cell sharded N ways "
                         "(conservative time-window parallel DES)")
    ap.add_argument("--shard-mode", choices=("mp", "inline"), default="mp",
                    help="sharded run backend: one OS process per shard "
                         "(mp) or sequential in-process (inline)")
    ap.add_argument("--shard-parity", action="store_true",
                    help="fail unless the sharded run's deterministic "
                         "columns are bit-identical to the single-process "
                         "headline cell")
    ap.add_argument("--out", default=None, help="write the report as JSON")
    args = ap.parse_args(argv)

    if args.profile:
        run_profile(args)

    t0 = time.perf_counter()
    rows = run_sweep(args)
    total_wall = time.perf_counter() - t0
    headline = rows[0]
    speedup = headline["events_per_s"] / BASELINE["events_per_s"]
    print(f"\nheadline: {headline['events_per_s']:,.0f} events/s at "
          f"{headline['messages']:,} msgs x {headline['consumers']} "
          f"consumers ({speedup:.1f}x the recorded "
          f"{BASELINE['events_per_s']:,.0f} ev/s pre-rework baseline)")

    rc = 0
    sharded = None
    if args.shards > 0:
        sharded = run_sharded(args)
        sharded["parity_vs_single"] = all(
            sharded[k] == headline[k] for k in PARITY_KEYS)
        sharded["speedup_vs_single"] = (
            sharded["agg_events_per_s"] / max(headline["events_per_s"],
                                              1e-9))
        print(f"  sharded aggregate speedup: "
              f"{sharded['speedup_vs_single']:.1f}x the single-process "
              f"headline rate")
        if args.shard_parity:
            if sharded["parity_vs_single"]:
                print("shard parity: OK (deterministic columns "
                      "bit-identical to the single-process headline)")
            else:
                diffs = [f"{k}: single={headline[k]!r} "
                         f"sharded={sharded[k]!r}"
                         for k in PARITY_KEYS
                         if sharded[k] != headline[k]]
                print("shard parity: FAILED — " + "; ".join(diffs))
                rc = 1
    if args.max_rss_mb is not None:
        peak = headline["peak_rss_mb"]
        if peak > args.max_rss_mb:
            print(f"peak RSS gate: FAILED — headline cell peaked at "
                  f"{peak:.0f} MB > {args.max_rss_mb:.0f} MB ceiling")
            rc = 1
        else:
            print(f"peak RSS gate: OK ({peak:.0f} MB <= "
                  f"{args.max_rss_mb:.0f} MB ceiling)")
    if args.check_determinism:
        def det(rs):
            return [[r[k] for k in DETERMINISTIC_KEYS] for r in rs]
        reruns = [run_sweep(args) for _ in range(2)]
        if all(det(rows) == det(rn) for rn in reruns):
            print("determinism: OK (identical deterministic columns "
                  "across three full sweeps)")
        else:
            print("determinism: FAILED — deterministic columns differ")
            rc = 1

    if args.out:
        report = {
            "config": {"messages": args.messages, "devices": args.devices,
                       "consumers": args.consumers, "rate_hz": args.rate_hz,
                       "payload_bytes": args.payload_bytes,
                       "service_s": args.service_s, "seed": args.seed,
                       "trace": args.trace,
                       "streaming_metrics": args.streaming_metrics,
                       "truncate_logs": args.truncate_logs,
                       "shards": args.shards},
            "baseline": BASELINE,
            "headline": {"events_per_s": headline["events_per_s"],
                         "speedup_vs_baseline": speedup},
            "rows": rows,
        }
        if sharded is not None:
            report["sharded"] = sharded
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1, default=float)
        print(f"wrote {args.out} ({total_wall:.1f} s total)")
    return rc


if __name__ == "__main__":
    enable_compilation_cache()
    sys.exit(main())
