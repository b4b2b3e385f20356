#!/usr/bin/env python3
"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the chips the cell
asks for, in one process that holds them.  It checks the device first
and exits non-zero with no result when JAX finds no TPU or too few
chips.  Then it runs the cell named in ``BENCHMARK.json`` once
(``benchlib.harness``) and prints, as the last line of standard output,
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics untraced, its per-layer ones with
``--trace 1``), ``device`` and, traced, ``breakdown``; last comes
``checks``, every number compared with its limit, which also close
standard error.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchlib import spec
    cell = spec.workload(spec.load_benchmark(ROOT), args.workload)
    from benchlib import harness
    try:
        device = harness.require_devices(cell["chips"])
    except harness.NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.enable_cache()
    print(f"bench: {time.monotonic() - T_PROCESS:.3f} s to the device "
          f"check", file=sys.stderr, flush=True)
    result = harness.run_workload(
        args.workload, args.seed, args.seconds, bool(args.trace),
        t_process=T_PROCESS, root=ROOT, device=device)
    print(json.dumps(result), flush=True)
    harness.print_checks(result["checks"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
