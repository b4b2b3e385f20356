"""Window rate, due-time latency and percentile arithmetic; work counted
from shapes and the shares of the peaks built on it."""
import json

import numpy as np
import pytest

from benchlib import harness, numbers, shares, spec
from bench_helpers import ROOT


def test_percentile_is_nearest_rank():
    v = list(range(1, 101))
    assert numbers.percentile(v, 50) == 50
    assert numbers.percentile(v, 95) == 95
    assert numbers.percentile(v, 100) == 100
    assert numbers.percentile([7.0], 95) == 7.0
    assert numbers.percentile([3, 1, 2], 50) == 2
    # a message that never completed is infinitely late
    assert numbers.percentile([1.0] * 19 + [float("inf")], 95) == 1.0
    assert numbers.percentile([1.0] * 18 + [float("inf")] * 2, 95) \
        == float("inf")
    with pytest.raises(ValueError):
        numbers.percentile([], 50)


def test_window_rate_counts_only_what_completed_inside():
    done = [0.5, 1.0, 9.99, 10.0, 10.01, None]
    assert numbers.window_rate(done, 10.0, 10.0) == pytest.approx(0.4)


def test_spread_is_the_interquartile_share_of_the_median():
    assert numbers.spread([1.0, 2.0, 3.0, 4.0, 5.0]) == pytest.approx(
        (4.5 - 1.5) / 3.0)
    assert numbers.spread([2.0] * 6) == 0.0


def test_latency_runs_from_the_due_time_and_plan_matches_stamps():
    # two devices, stamps arrive out of order; a device's k-th produced
    # message is its k-th due one
    stamps = [("m2", "produced", 10.30, 1), ("m0", "produced", 10.11, 0),
              ("m1", "produced", 10.21, 0), ("m0", "consumed", 10.12, None),
              ("m0", "processed", 10.15, None),
              ("m1", "consumed", 10.22, None),
              ("m1", "processed", 10.25, None),
              ("m2", "consumed", 10.31, None)]
    plan = [np.array([10.1, 10.2]), np.array([10.3])]
    msgs = harness.messages(stamps, plan)
    assert [m["due"] for m in msgs] == [10.1, 10.2, 10.3]
    run = harness.Run(messages=msgs)
    lat = run.latencies_ms()
    assert lat[0] == pytest.approx(50.0) and lat[1] == pytest.approx(50.0)
    assert lat[2] == float("inf")
    assert run.mean_span_ms("due", "produced") == pytest.approx(
        (10.0 + 10.0 + 0.0) / 3)
    assert run.mean_span_ms("consumed", "processed") == pytest.approx(30.0)


def _msg(partition, produced, processed):
    return {"partition": partition, "produced": produced,
            "processed": processed}


def test_unprocessed_tells_lost_from_backlog():
    msgs = [_msg(0, 1.0, 1.1), _msg(0, 2.0, None), _msg(0, 3.0, 3.1),
            _msg(0, 4.0, None), _msg(0, 5.0, None),
            _msg(1, 1.5, 1.6), _msg(1, None, None),
            _msg(2, 2.5, None)]
    # partition 0 skipped its second message and has two behind its
    # last processed one; partition 1's second never reached the broker
    assert harness.unprocessed(msgs) == (2, 3)
    assert harness.unprocessed([_msg(0, 1.0, 1.2)]) == (0, 0)


def _configs():
    bench = spec.load_benchmark(ROOT)
    return {c["name"]: spec.load_config(bench, c["name"], ROOT)
            for c in bench["configs"]}


def test_kmeans_work_from_shapes():
    cfg = _configs()["kmeans-paper"]
    flops, nbytes = cfg.work.step(cfg.data)
    n, f, k = 10_000, 32, 25
    assert flops == 2 * n * k * f + 3 * n * f + 6 * k * f + 2 * n * k
    assert nbytes == 4 * n * f + 8 * k * f + 8 * k + 8 * n
    peaks = spec.load_peaks("TPU v5 lite", ROOT)
    assert shares.bound((flops, nbytes), peaks) == "memory"
    assert shares.least_time((flops, nbytes), peaks) == pytest.approx(
        nbytes / 819e9)


def test_isoforest_work_from_shapes():
    cfg = _configs()["isoforest-paper"]
    forest_bytes = 100 * 511 * 13
    assert cfg.work.fit(cfg.data) == (4 * 256 * 8 * 100,
                                      4 * 256 * 8 * 100 + forest_bytes)
    assert cfg.work.score(cfg.data) == (2 * 10_000 * 100 * 8,
                                        4 * 10_000 * 33 + forest_bytes)
    peaks = spec.load_peaks("TPU v5 lite", ROOT)
    for w in (cfg.work.fit(cfg.data), cfg.work.score(cfg.data)):
        assert shares.bound(w, peaks) == "memory"


class _Trace:
    def __init__(self, modules):
        self.modules = modules

    def module_time(self, pattern):
        runs = [d for n, ds in self.modules.items() if pattern in n
                for d in ds]
        return len(runs), sum(runs)


def test_roofline_and_step_mfu_arithmetic():
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    work = (1e6, 4e6)          # 1 us of compute, 4 ms of memory
    run = harness.Run(trace=_Trace({"jit__step(1)": [8e-3, 8e-3],
                                    "other": [1.0]}),
                      peaks=peaks, spans=[(0.0, 0.5), (1.0, 1.5)],
                      config={}, work=type("W", (), {
                          "message": staticmethod(lambda c: work)}))
    assert shares.roofline(run, {"_step": work}) == pytest.approx(50.0)
    assert shares.roofline(run, {"_absent": work}) is None
    assert shares.step_mfu(run) == pytest.approx(100 * 1e6 / (0.5 * 1e12))
    run.trace = None
    assert shares.roofline(run, {"_step": work}) is None


def test_unknown_device_kind_is_an_error():
    with pytest.raises(spec.SpecError):
        spec.load_peaks("TPU v99", ROOT)
    table = json.loads((ROOT / "bench" / "peaks.json").read_text())
    assert table["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
