#!/usr/bin/env python3
"""Knee sweep: one configuration at a ladder of offered rates.

    python3 bench/sweep.py --config <config> --rates 50,100,... \
        --seconds <s> --seed <n>

One process on one chip builds the configuration once (as a run of a
cell does), warms it up, then offers each rate in turn as one open-loop
Poisson window of ``--seconds`` (``benchlib.traffic``) and stops the
pipeline when the window closes.  Per rate it prints one JSON line: the
offered and completed rates, the consumer's lag (messages produced but
not yet processed) a quarter into the window and at its close, and the
median and 95th-percentile latency of the messages that completed.  The
knee is the highest offered rate whose completed rate keeps up and whose
lag does not grow over the window.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(0, str(ROOT / "src"))


def lag_at(msgs, t: float) -> int:
    produced = sum(1 for m in msgs
                   if m["produced"] is not None and m["produced"] <= t)
    done = sum(1 for m in msgs
               if m["processed"] is not None and m["processed"] <= t)
    return produced - done


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, msgs/s")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    from benchlib import harness, numbers, spec, traffic
    from benchlib.pool import make_pool
    try:
        harness.require_devices(1)
    except harness.NoAccelerator as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    harness.enable_cache()
    cfg = spec.load_config(spec.load_benchmark(ROOT), args.config, ROOT)
    data = cfg.data
    probe = harness.Probe(make_pool(args.seed, **data["pool"]))
    system = cfg.system.build(data, harness.model_seed(args.seed), probe)
    n_dev = data["fleet"]["edge_devices"]
    try:
        system.pipe.run(n_messages=data["warmup_messages"], timeout_s=900.0)
        for rate in (float(r) for r in args.rates.split(",")):
            plan = traffic.arrival_plan(rate, args.seconds, args.seed,
                                        n_dev, harness.LEAD_S)
            w_open = harness.LEAD_S
            w_close = w_open + args.seconds
            system.metrics.log.clear()
            t0 = time.monotonic()
            system.pipe.run(arrival_plan=plan, timeout_s=w_close)
            msgs = harness.messages(list(system.metrics.log),
                                    [t0 + p for p in plan])
            done = [m["processed"] - m["due"] for m in msgs
                    if m["processed"] is not None]
            print(json.dumps({
                "config": args.config, "offered_hz": rate,
                "due": len(msgs),
                "completed_hz": numbers.window_rate(
                    [m["processed"] for m in msgs], t0 + w_close,
                    args.seconds),
                "lag_quarter": lag_at(msgs, t0 + w_open
                                      + args.seconds / 4),
                "lag_close": lag_at(msgs, t0 + w_close),
                "p50_ms": 1e3 * numbers.percentile(done, 50)
                if done else None,
                "p95_ms": 1e3 * numbers.percentile(done, 95)
                if done else None,
            }), flush=True)
    finally:
        system.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
