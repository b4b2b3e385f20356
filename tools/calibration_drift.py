#!/usr/bin/env python
"""Calibration-drift report: re-measure the kernels on *this* host —
roofline HLO flops plus a live efficiency/sigma service refit — and
compare against the committed ``calibration.json``.

The committed calibration pins the paper-testbed service fit so every
consumer stays deterministic; this tool answers "how far has this
container drifted from it": the achieved-fraction-of-peak (efficiency)
and lognormal service-noise sigma refit live, next to the committed
values, as a JSON artifact CI uploads on every slow-lane run (the
ROADMAP's calibration-drift follow-up).

Usage::

    PYTHONPATH=src python tools/calibration_drift.py \\
        --messages 5 --out CALIBRATION_drift.json

Exit code is 0 unless ``--max-kernel-drift R`` is given and a kernel's
re-measured HLO flops/point drifts beyond a factor of R from the
committed value (jax/XLA version drift changes fusion decisions, not
orders of magnitude — the service fit is expected to drift and is never
gated).
"""
from __future__ import annotations

import argparse
import json
import sys


def drift_report(models=None, n_messages: int = 5, tier: str = "cloud"):
    """Refit each model live and pair the numbers with the committed
    calibration.  Returns ``{"meta": ..., "models": [row, ...]}``."""
    from repro.compile_cache import enable_compilation_cache
    from repro.cost.calibrate import Calibrator, load_calibration
    # persist XLA compiles so CI's restored cache actually shortens the
    # kernel measurements
    enable_compilation_cache()
    committed = load_calibration()
    cal = Calibrator()
    rows = []
    for name in models or sorted(committed):
        c = committed[name]
        kf, kb = cal.measure_kernel(name)
        eff, sigma = cal.measure_service(
            name, n_messages=n_messages, tier=tier,
            kernel_flops_per_point=kf)
        rows.append({
            "model": name,
            "kernel_flops_per_point": round(kf, 3),
            "committed_kernel_flops_per_point": c.kernel_flops_per_point,
            "kernel_flops_ratio": kf / c.kernel_flops_per_point,
            "kernel_bytes_per_point": round(kb, 3),
            "achieved_fraction_of_peak": eff,
            "committed_efficiency": c.efficiency,
            "efficiency_ratio": eff / c.efficiency,
            "sigma": sigma,
            "committed_sigma": c.sigma,
        })
    import jax
    return {
        "meta": {"n_messages": n_messages, "tier": tier,
                 "jax_version": jax.__version__,
                 "generated_by": "tools/calibration_drift.py"},
        "models": rows,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None,
                    help="write the drift report as JSON")
    ap.add_argument("--messages", type=int, default=5,
                    help="live service samples per model")
    ap.add_argument("--models", nargs="+", default=None,
                    help="restrict to these calibrated models")
    ap.add_argument("--tier", default="cloud",
                    help="tier whose peak rate the efficiency is "
                         "measured against")
    ap.add_argument("--max-kernel-drift", type=float, default=None,
                    help="fail (exit 1) if any kernel's re-measured HLO "
                         "flops drift beyond this factor of the "
                         "committed value")
    args = ap.parse_args(argv)

    report = drift_report(models=args.models, n_messages=args.messages,
                          tier=args.tier)
    hdr = (f"{'model':>12} {'flops/pt':>12} {'committed':>12} "
           f"{'ratio':>6} {'eff':>8} {'committed':>9} {'sigma':>7}")
    print(hdr)
    print("-" * len(hdr))
    for r in report["models"]:
        print(f"{r['model']:>12} {r['kernel_flops_per_point']:>12.1f} "
              f"{r['committed_kernel_flops_per_point']:>12.1f} "
              f"{r['kernel_flops_ratio']:>6.2f} "
              f"{r['achieved_fraction_of_peak']:>8.5f} "
              f"{r['committed_efficiency']:>9.3f} {r['sigma']:>7.3f}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1, default=float)
            f.write("\n")
        print(f"wrote {args.out}")

    if args.max_kernel_drift is not None:
        bad = [r for r in report["models"]
               if not (1.0 / args.max_kernel_drift
                       <= r["kernel_flops_ratio"]
                       <= args.max_kernel_drift)]
        if bad:
            for r in bad:
                print(f"KERNEL DRIFT: {r['model']} flops ratio "
                      f"{r['kernel_flops_ratio']:.2f} exceeds factor "
                      f"{args.max_kernel_drift}")
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
