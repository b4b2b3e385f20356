#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \
        --control-seeds 1,2,3 --seconds <s>

One process on one chip.  For every seed it runs the cell's window as a
run of the benchmark does and prints one JSON line with the numbers
compared (the program against the reference) and the time the reference
took.  For each control seed it also puts the reference one precision
down in the program's place, over the same calls, and prints the same
numbers for it (``"control": true``).  The lower reading of a number is
the largest over the program's seeds, the upper the smallest over the
control's; ``PERF.md`` gives both and the limit set between them.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from benchlib import harness
    try:
        harness.require_devices(1)
    except harness.NoAccelerator as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    harness.enable_cache()
    control = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        w = harness.run_window(args.workload, seed, args.seconds, False,
                               root=ROOT)
        exact = {k: v for k, (v, _) in w.checks.items()}
        t0 = time.monotonic()
        program = harness.compare(w)
        line = {"seed": seed, "calls": len(w.order), "exact": exact,
                "program": program,
                "reference_s": time.monotonic() - t0}
        if seed in control:
            line["control"] = harness.compare(w, control=True)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
