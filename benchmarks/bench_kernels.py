"""Fused k-means kernel microbench: the two-pass one-hot baseline vs the
fused assign+update lowering, across the precision axis (fp32 / bf16 /
int8) — the kernel-level half of the "precision as a placement axis"
story (``bench_placement.py`` sweeps the system-level half)::

    PYTHONPATH=src python benchmarks/bench_kernels.py --min-speedup 1.5

Per (shape, precision) cell the bench times one streaming k-means
message under both hot paths: the *seed's* two-pass path (an outlier-
scoring distance pass, then the historical update — a second distance
pass plus the ``(N,K)`` one-hot materialization and ``(K,N)@(N,F)``
matmul) vs the fused single pass (``impl='fused'``: one distance pass
yields scores *and* the scatter-add membership stats — the formulation
the Pallas kernel implements on TPU).  It also checks the fused Pallas
kernel (interpret mode on CPU) against the jnp lowering on a small
probe, and records assignment agreement vs the fp32 reference.

``--check-determinism`` re-runs everything three times and fails unless
the *deterministic* columns (checksums, agreement, parity — everything
except wall times, speedup and the host-dependent autotuned ``block_n``)
are bit-identical.  ``--out`` writes rows as JSON; the row shape is
pinned by ``benchmarks/BENCH_kernels.schema.json``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compilation_cache
from repro.kernels.kmeans import autotune_block_n
from repro.ml.kmeans import PRECISIONS, _assign, _assign_update

# shapes fixed apart from the headline point count: (n_points, f, k)
SECONDARY_SHAPES = ((100_000, 32, 25),)
PARITY_SHAPE = (2_048, 32, 25)   # small enough for interpret-mode Pallas


def _make_data(n: int, f: int, k: int):
    """Deterministic clustered blob: k centers, gaussian spread."""
    kc, kn, ki = jax.random.split(jax.random.key(0), 3)
    centers = jax.random.normal(kc, (k, f)) * 10.0
    ids = jax.random.randint(ki, (n,), 0, k)
    pts = centers[ids] + jax.random.normal(kn, (n, f))
    # seed centroids from the first k points (distinct enough post-noise)
    return jnp.asarray(pts, jnp.float32), jnp.asarray(pts[:k], jnp.float32)


def _time(fn, repeats: int) -> float:
    fn()                                       # compile + warm
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _checksum_ids(ids) -> int:
    # host-side numpy: jax x64 is disabled, int32 would overflow at 1M rows
    import numpy as np
    ids = np.asarray(ids, np.int64)
    w = np.arange(ids.shape[0], dtype=np.int64) % 1_009
    return int(np.sum(ids * (w + 1)) % (2 ** 31))


def _pallas_parity(precision: str) -> bool:
    """Fused Pallas kernel vs the fused jnp lowering on a small probe:
    ids exact, counts exact, updated centroids allclose (accumulation
    order inside the kernel's per-block dots differs from segment_sum)."""
    n, f, k = PARITY_SHAPE
    pts, cent = _make_data(n, f, k)
    counts0 = jnp.zeros((k,), jnp.float32)
    jcent, jc, jids, _ = _assign_update(cent, counts0, pts, impl="fused",
                                        precision=precision)
    pcent, pc, pids, _ = _assign_update(cent, counts0, pts, impl="pallas",
                                        precision=precision)
    return (bool(jnp.all(pids == jids)) and bool(jnp.all(pc == jc))
            and bool(jnp.allclose(pcent, jcent, rtol=1e-5, atol=1e-4)))


def run_rows(args):
    rows = []
    shapes = [(args.headline_points, 32, 25)] + list(SECONDARY_SHAPES)
    shapes = [s for s in shapes if s[0] <= args.headline_points] or shapes[:1]
    for n, f, k in shapes:
        pts, cent = _make_data(n, f, k)
        counts0 = jnp.zeros((k,), jnp.float32)
        fp32_ids = None
        for precision in PRECISIONS:

            def step_two_pass(precision=precision):
                # the seed's per-message hot path: outlier scoring (one
                # full distance pass), then the two-pass update (a second
                # distance pass + the one-hot matmul)
                s = _assign(cent, pts, impl="jnp", precision=precision)
                u = _assign_update(cent, counts0, pts, impl="jnp",
                                   precision=precision)
                jax.block_until_ready((s, u))
                return u

            def step_fused(precision=precision):
                out = _assign_update(cent, counts0, pts, impl="fused",
                                     precision=precision)
                jax.block_until_ready(out)
                return out

            two_pass = _time(step_two_pass, args.repeats)
            fused = _time(step_fused, args.repeats)
            new_cent, new_counts, ids, _ = step_fused()
            if precision == "fp32":
                fp32_ids = ids
                agreement = 1.0
            else:
                agreement = float(jnp.mean(
                    (ids == fp32_ids).astype(jnp.float32)))
            parity = (_pallas_parity(precision)
                      if not args.skip_parity else None)
            block_n = (autotune_block_n(n, f, k, precision=precision)
                       if not args.skip_autotune else None)
            rows.append({
                "n_points": n, "n_features": f, "n_clusters": k,
                "precision": precision,
                "two_pass_wall_s": two_pass, "fused_wall_s": fused,
                "speedup": two_pass / max(fused, 1e-12),
                "ids_checksum": _checksum_ids(ids),
                "counts_total": int(jnp.sum(new_counts)),
                "centroid_l2": float(jnp.sqrt(jnp.sum(
                    jnp.asarray(new_cent) ** 2))),
                "agreement_vs_fp32": agreement,
                "pallas_parity": parity,
                "block_n": block_n,
            })
    return rows


# wall times, speedup and the autotuned block size are host/run dependent
NONDETERMINISTIC = ("two_pass_wall_s", "fused_wall_s", "speedup", "block_n")


def _deterministic(rows):
    return [{k: v for k, v in r.items() if k not in NONDETERMINISTIC}
            for r in rows]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--headline-points", type=int, default=1_000_000,
                    help="N of the headline 1M x 32 x 25 cell (CI runs "
                         "a reduced size)")
    ap.add_argument("--repeats", type=int, default=3,
                    help="timing repeats (min-of wins)")
    ap.add_argument("--min-speedup", type=float, default=None,
                    help="fail unless fused beats two-pass by this factor "
                         "on the headline fp32 cell")
    ap.add_argument("--skip-parity", action="store_true",
                    help="skip the interpret-mode Pallas parity probe")
    ap.add_argument("--skip-autotune", action="store_true",
                    help="skip the block_n autotune sweep")
    ap.add_argument("--check-determinism", action="store_true",
                    help="run three times; fail unless the deterministic "
                         "columns are identical across runs")
    ap.add_argument("--out", default=None, help="write rows as JSON")
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    rows = run_rows(args)
    wall = time.perf_counter() - t0
    hdr = (f"{'n':>9} {'prec':>5} {'two-pass':>10} {'fused':>10} "
           f"{'speedup':>8} {'agree':>7} {'parity':>6} {'block_n':>7}")
    print(hdr)
    for r in rows:
        print(f"{r['n_points']:>9} {r['precision']:>5} "
              f"{r['two_pass_wall_s'] * 1e3:>8.1f}ms "
              f"{r['fused_wall_s'] * 1e3:>8.1f}ms "
              f"{r['speedup']:>7.2f}x {r['agreement_vs_fp32']:>7.4f} "
              f"{str(r['pallas_parity']):>6} {str(r['block_n']):>7}")
    print(f"{len(rows)} cells in {wall:.1f} s of wall time")

    rc = 0
    if args.min_speedup is not None:
        head = rows[0]
        assert head["precision"] == "fp32"
        if head["speedup"] < args.min_speedup:
            print(f"speedup check: FAILED — headline fp32 fused speedup "
                  f"{head['speedup']:.2f}x < {args.min_speedup:.2f}x")
            rc = 1
        else:
            print(f"speedup check: OK ({head['speedup']:.2f}x >= "
                  f"{args.min_speedup:.2f}x)")
    if rc == 0 and any(r["pallas_parity"] is False for r in rows):
        print("parity check: FAILED — Pallas kernel diverges from the "
              "fused jnp lowering")
        rc = 1
    if args.check_determinism:
        ref = _deterministic(rows)
        reruns = [_deterministic(run_rows(args)) for _ in range(2)]
        if all(ref == other for other in reruns):
            print("determinism: OK (identical checksums/agreement/parity "
                  "across three runs)")
        else:
            print("determinism: FAILED — deterministic columns differ "
                  "across runs")
            rc = 1
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1, default=float)
    return rc


if __name__ == "__main__":
    enable_compilation_cache()
    sys.exit(main())
