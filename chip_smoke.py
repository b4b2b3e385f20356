#!/usr/bin/env python
"""Bring-up smoke of the served continuum path on one TPU chip.

    python chip_smoke.py

Run from the root of a checkout, with no arguments, in one process:

1. **Device check.**  The default JAX device must be a TPU; otherwise the
   script exits 1 having run nothing.
2. **Compile cache.**  Placed by :mod:`repro.compile_cache` before the
   first compile (``JAX_COMPILATION_CACHE_DIR``, else ``<repo>/.jax_cache``).
3. **Kernel phase.**  The fused Pallas k-means assign+update kernel,
   compiled with ``interpret=False``, on one seeded 1,000,000 x 32
   message with 25 centroids, in fp32, bf16 and int8.  Each compiled
   program must hold a ``tpu_custom_call``.  Its ids, sums and counts are
   held to the ``kernels/ref.py`` oracles at highest matmul precision, and
   its ids to a float64 host argmin, on the values the precision computes
   on (bf16-rounded, or int8 fake-quantized with the kernel's scales).
4. **Pipeline phase.**  The paper's ``EdgeToCloudPipeline`` under the
   default ``ThreadedExecutor``: an edge pilot streams ``MiniAppGenerator``
   messages of 10,000 x 32 points through the broker to a cloud pilot
   whose handler is a detector's own ``make_processor(train=True)``,
   publishing to a ``ParameterService``.  Every message must be processed
   with no task error, every published model must live on the chip, and
   each message's ``{n_outliers, mean_score}`` must match the same handler
   replayed in a host loop on the float32 reference path at highest
   matmul precision.  One partition and one consumer keep the training
   order replayable.

Compile, first-call and steady times and peak device bytes are printed on
the way as bring-up facts, not metrics.  The last line of stdout, printed
only when every check passed, is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compilation_cache  # noqa: E402
from repro.core import (ComputeResource, EdgeToCloudPipeline,  # noqa: E402
                        ParameterService, PilotManager)
from repro.kernels import quant, ref  # noqa: E402
from repro.kernels.kmeans import kmeans_assign_update  # noqa: E402
from repro.ml import (AutoEncoder, IsolationForest, KMeans,  # noqa: E402
                      MiniAppGenerator)

# -- thresholds ---------------------------------------------------------------
# Kernel phase, per precision, against the oracle on that precision's values.
# An id that disagrees with the float64 argmin is an honest near-tie only when
# the kernel's centroid is within KERNEL_GAP_TOL of the true nearest one, in
# float64 squared distance relative to ||x||^2 + ||c||^2 (the terms the
# distance expansion cancels).  2^-7 admits the rounding of one bf16 MXU pass
# over those terms and nothing coarser.
KERNEL_MIN_ID_AGREEMENT = {"fp32": 0.999, "bf16": 0.999, "int8": 0.999}
KERNEL_GAP_TOL = 2.0 ** -7
KERNEL_MAX_COUNTS_ERR = 2e-3      # sum |counts - oracle| / n
KERNEL_MAX_SUMS_ERR = 1e-2        # ||sums - oracle||_F / ||oracle||_F

# Pipeline phase, per message: (n_outliers absolute, mean_score relative)
# against the highest-precision replay.  The isolation forest does no matmul,
# so it must match exactly.  The auto-encoder takes every product at
# Precision.HIGHEST and its replay runs the same two programs on the same
# chip: on a v5e, with each epoch in the Pallas kernel, it read 0 and 0.0
# over the phase's 32 messages of 10,000 points, warm-started through
# 902,400 Adam steps.  1e-6 leaves room for a sum order only, against the
# 4e-7 to 3e-5 that bf16 products move the first mean score.
PIPELINE_TOL = {
    "kmeans-pallas": (1, 1e-3),
    "kmeans-fused": (1, 1e-3),
    "autoencoder": (0, 1e-6),
    "isoforest": (0, 1e-5),
}


class SmokeFailure(RuntimeError):
    """A check of the smoke failed."""


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def _peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# -- kernel phase ---------------------------------------------------------------


def _precision_values(x, c, precision: str):
    """The float32 values the kernel computes on at ``precision``."""
    if precision == "bf16":
        return quant.round_to_bf16(x), quant.round_to_bf16(c)
    if precision == "int8":
        scales = quant.symmetric_scales(x, c)
        return quant.fake_quantize(x, scales), quant.fake_quantize(c, scales)
    return x, c


def _oracle(x, c, precision: str):
    with jax.default_matmul_precision("highest"):
        if precision == "int8":
            return ref.kmeans_assign_update_int8_ref(x, c)
        return ref.kmeans_assign_update_ref(*_precision_values(x, c,
                                                               precision))


def _sq_dists64(x, c):
    """float64 squared distances (N, K) on the host."""
    return ((x * x).sum(1)[:, None] - 2.0 * (x @ c.T)
            + (c * c).sum(1)[None, :])


def kernel_phase(*, n_points: int = 1_000_000, n_features: int = 32,
                 n_clusters: int = 25, seed: int = 0,
                 interpret: bool = False) -> dict:
    """The fused kernel in each precision against its oracles; returns
    the per-precision numbers, raises :class:`SmokeFailure` on a miss."""
    sample = MiniAppGenerator(n_points=n_points, n_features=n_features,
                              n_clusters=n_clusters, seed=seed).sample()
    x = jnp.asarray(sample, jnp.float32)
    c = KMeans(n_clusters=n_clusters, n_features=n_features,
               seed=seed).init(sample)["centroids"]
    report = {}
    for precision in ("fp32", "bf16", "int8"):
        t0 = time.perf_counter()
        compiled = kmeans_assign_update.lower(
            x, c, interpret=interpret, precision=precision).compile()
        compile_s = time.perf_counter() - t0
        if not interpret:
            _check("tpu_custom_call" in compiled.as_text(),
                   f"kernel {precision}: no tpu_custom_call in the "
                   f"compiled program")
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(x, c))
        first_s = time.perf_counter() - t0
        steady = []
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(compiled(x, c))
            steady.append(time.perf_counter() - t0)
        ids, dmin, sums, counts = (np.asarray(a) for a in out)

        o_ids, _, o_sums, o_counts = (np.asarray(a)
                                      for a in _oracle(x, c, precision))
        xv, cv = (np.asarray(a, np.float64)
                  for a in _precision_values(x, c, precision))
        d2 = _sq_dists64(xv, cv)
        true_ids = d2.argmin(1)
        rows = np.arange(n_points)
        off = ids != true_ids
        scale = (xv * xv).sum(1)[off] + (cv * cv).sum(1)[true_ids[off]]
        gaps = (d2[rows[off], ids[off]] - d2[rows[off], true_ids[off]]) \
            / scale
        r = {
            "compile_s": compile_s, "first_call_s": first_s,
            "steady_s": statistics.median(steady),
            "id_agreement": float(1.0 - off.mean()),
            "oracle_id_agreement": float((o_ids == true_ids).mean()),
            "max_gap": float(gaps.max()) if gaps.size else 0.0,
            "dmin_max_abs_err": float(np.abs(
                dmin - np.sqrt(np.maximum(d2[rows, ids], 0.0))).max()),
            "counts_err": float(np.abs(counts - o_counts).sum() / n_points),
            "sums_err": float(np.linalg.norm(sums - o_sums)
                              / np.linalg.norm(o_sums)),
            "peak_bytes_in_use": _peak_bytes(),
        }
        print(f"kernel {precision} {n_points}x{n_features}x{n_clusters}: "
              + " ".join(f"{k}={v}" for k, v in r.items()), flush=True)
        _check(r["id_agreement"] >= KERNEL_MIN_ID_AGREEMENT[precision],
               f"kernel {precision}: id agreement {r['id_agreement']} < "
               f"{KERNEL_MIN_ID_AGREEMENT[precision]}")
        _check(r["max_gap"] <= KERNEL_GAP_TOL,
               f"kernel {precision}: a disagreeing id is {r['max_gap']} "
               f"from the nearest centroid (> {KERNEL_GAP_TOL})")
        _check(r["counts_err"] <= KERNEL_MAX_COUNTS_ERR,
               f"kernel {precision}: counts error {r['counts_err']}")
        _check(r["sums_err"] <= KERNEL_MAX_SUMS_ERR,
               f"kernel {precision}: sums error {r['sums_err']}")
        report[precision] = r
    return report


# -- pipeline phase -------------------------------------------------------------


class _PlacementRecordingParams(ParameterService):
    """A ParameterService that records the devices of every published
    leaf (``None`` for a leaf that is not a JAX array)."""

    def __init__(self):
        super().__init__()
        self.placements = set()

    def publish(self, name, tree):
        for leaf in jax.tree.leaves(tree):
            self.placements.add(frozenset(leaf.devices())
                                if isinstance(leaf, jax.Array) else None)
        return super().publish(name, tree)


def run_pipeline(handler, *, n_points: int, n_messages: int, seed: int,
                 timeout_s: float):
    """Stream ``n_messages`` seeded messages through one edge and one
    cloud pilot into ``handler``; every message must be processed with no
    task error.  Returns the :class:`PipelineResult`."""
    manager = PilotManager()
    edge = manager.submit_pilot(ComputeResource(tier="edge", n_workers=1))
    cloud = manager.submit_pilot(ComputeResource(tier="cloud", n_workers=1))
    pipe = EdgeToCloudPipeline(
        pilot_cloud_processing=cloud, pilot_edge=edge,
        produce_function_handler=MiniAppGenerator(
            n_points=n_points, seed=seed).make_producer(),
        process_cloud_function_handler=handler,
        n_edge_devices=1, n_partitions=1, cloud_consumers=1,
        heartbeat_timeout_s=timeout_s)     # the first call compiles
    try:
        res = pipe.run(n_messages=n_messages, timeout_s=timeout_s)
    finally:
        manager.release_all()
    errors = res.metrics.counter("runtime.task_errors")
    _check(res.n_processed == res.n_produced == n_messages and errors == 0,
           f"pipeline processed {res.n_processed} of {res.n_produced} "
           f"produced ({n_messages} sent) with {errors:g} task errors: "
           f"{res.metrics.events('task_error')[:3]}")
    return res


def replay(handler, *, n_points: int, n_messages: int, seed: int) -> list:
    """``handler`` on the same seeded messages, in a plain host loop, at
    highest matmul precision."""
    gen = MiniAppGenerator(n_points=n_points, seed=seed)
    with jax.default_matmul_precision("highest"):
        return [handler(None, data=gen.sample()) for _ in range(n_messages)]


def detectors() -> dict:
    """name -> (served detector, its float32 reference for the replay)."""
    return {
        "kmeans-pallas": (KMeans(impl="pallas"), KMeans(impl="jnp")),
        "kmeans-fused": (KMeans(), KMeans(impl="jnp")),
        "autoencoder": (AutoEncoder(), AutoEncoder()),
        "isoforest": (IsolationForest(n_trees=100),
                      IsolationForest(n_trees=100)),
    }


def pipeline_phase(name: str, served, reference, *, n_points: int = 10_000,
                   n_messages: int = 32, seed: int = 0,
                   timeout_s: float = 180.0) -> dict:
    """One detector through the pipeline and its replay; returns the
    numbers, raises :class:`SmokeFailure` on a miss."""
    params = _PlacementRecordingParams()
    handler = served.make_processor(params, train=True)
    durations = []

    def timed(context, data=None):
        t0 = time.perf_counter()
        out = handler(context, data=data)
        durations.append(time.perf_counter() - t0)
        return out

    res = run_pipeline(timed, n_points=n_points, n_messages=n_messages,
                       seed=seed, timeout_s=timeout_s)
    device = jax.devices()[0]
    _check(params.placements == {frozenset({device})},
           f"{name}: published model leaves live on {params.placements}, "
           f"not only on {device}")
    expect = replay(reference.make_processor(ParameterService(), train=True),
                    n_points=n_points, n_messages=n_messages, seed=seed)
    out_diff = [abs(a["n_outliers"] - b["n_outliers"])
                for a, b in zip(res.results, expect)]
    mean_err = [abs(a["mean_score"] - b["mean_score"])
                / max(abs(b["mean_score"]), 1e-12)
                for a, b in zip(res.results, expect)]
    r = {
        "messages": res.n_processed, "wall_s": res.wall_s,
        "first_call_s": durations[0],
        "steady_s": statistics.median(durations[1:] or durations),
        "max_outlier_diff": max(out_diff),
        "max_mean_score_rel_err": max(mean_err),
        "peak_bytes_in_use": _peak_bytes(),
    }
    print(f"pipeline {name} {n_messages}x{n_points}x32: "
          + " ".join(f"{k}={v}" for k, v in r.items()), flush=True)
    atol, rtol = PIPELINE_TOL[name]
    _check(r["max_outlier_diff"] <= atol and
           r["max_mean_score_rel_err"] <= rtol,
           f"{name}: served results differ from the replay by "
           f"{r['max_outlier_diff']} outliers / "
           f"{r['max_mean_score_rel_err']} relative mean score "
           f"(allowed {atol} / {rtol})")
    return r


def main() -> int:
    devices = jax.devices()
    dev = devices[0]
    print(f"chip_smoke: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: the default JAX device is not a TPU; nothing run",
              file=sys.stderr)
        return 1
    print(f"chip_smoke: compile cache {enable_compilation_cache()}",
          flush=True)
    kernel_phase()
    for name, (served, reference) in detectors().items():
        pipeline_phase(name, served, reference)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
