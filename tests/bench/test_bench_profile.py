"""The trace reduction, on hand-made records, on a trace the JAX profiler
writes here, and on a small trace recorded on a TPU v5e."""
import gzip
import json
import threading
from pathlib import Path

import pytest

from benchlib import profile

DATA = Path(__file__).parent / "data"


def _ev(plane, line, name, t, d):
    return {"plane": plane, "line": line, "name": name, "t": t, "d": d}


DEV = "/device:TPU:0"
HOST = "/host:CPU"


def _records():
    return [
        # device ops: [10, 30) and [20, 40) overlap, then [60, 70)
        _ev(DEV, "XLA Ops", "fusion.1", 10, 20),
        _ev(DEV, "XLA Ops", "fusion.2", 20, 20),
        _ev(DEV, "XLA Ops", "copy.3", 60, 10),
        _ev(DEV, "XLA Modules", "jit__step(7)", 10, 30),
        _ev(DEV, "XLA Modules", "jit__step(7)", 60, 10),
        _ev(DEV, "XLA Modules", "jit__other(2)", 150, 10),
        # host: the handler span and a dispatch inside it
        _ev(HOST, "python", "bench.handler", 0, 80),
        _ev(HOST, "python", "PjitFunction(_step)", 45, 10),
        _ev(HOST, "python", "bench.handler", 85, 10),
    ]


def test_union_and_clip():
    assert profile.union([(5, 9), (1, 3), (2, 4), (9, 10), (7, 7)]) == [
        (1, 4), (5, 10)]
    assert profile.clip([(0, 5), (8, 20), (30, 40)], 2, 10) == [
        (2, 5), (8, 10)]


def test_busy_idle_modules_and_breakdown_over_a_window():
    s = profile.TraceSummary(_records(), 0.0, 100.0)
    assert s.n_devices == 1
    assert s.busy_s == pytest.approx(40e-9)       # [10,40) and [60,70)
    assert s.window_s == pytest.approx(100e-9)
    assert s.idle_share == pytest.approx(0.6)
    assert s.module_time("_step") == (2, pytest.approx(40e-9))
    assert s.module_time("_other") == (0, 0)      # outside the window
    b = s.breakdown()
    assert b["device_ops"][0] == ["jit__step/fusion.1", pytest.approx(20e-9)]
    idle = dict(b["idle_gaps"])
    # gaps [0,10) and [40,60) inside the handler, the dispatch covers
    # the middle of the second; [70,100) straddles the handler's end and
    # another span
    assert idle["bench.handler"] == pytest.approx(10e-9 + 30e-9)
    assert idle["PjitFunction(_step)"] == pytest.approx(20e-9)
    assert sum(idle.values()) == pytest.approx(60e-9)


def test_idle_gap_outside_every_span_is_host_idle():
    s = profile.TraceSummary(_records(), 100.0, 200.0)
    assert s.busy_s == 0.0
    assert dict(s.breakdown()["idle_gaps"]) == {
        profile.HOST_IDLE: pytest.approx(100e-9)}


def test_no_device_plane_reads_no_idle_share():
    host_only = [e for e in _records() if e["plane"] == HOST]
    assert profile.TraceSummary(host_only, 0.0, 100.0).idle_share is None


def test_align_maps_host_clock_onto_trace_time():
    host = [1.0, 1.5, 2.0, 2.7, 2.75]
    trace = [h * 1e9 + 123.0 for h in host]
    assert profile.align(trace, host) == pytest.approx(123.0)
    # a trace of part of the run holds a consecutive run of the spans
    assert profile.align(trace[1:4], host) == pytest.approx(123.0)
    assert profile.align(trace[3:], host) == pytest.approx(123.0)
    assert profile.align(trace, host[:2]) is None
    assert profile.align([], host) is None


def test_load_events_keeps_benchmark_threads_of_a_real_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)

    def worker():
        with jax.profiler.TraceAnnotation("bench.handler"):
            f(x).block_until_ready()
    th = threading.Thread(target=worker)
    th.start()
    th.join(timeout=60)
    jax.profiler.stop_trace()
    assert not th.is_alive()
    events = profile.load_events(profile.xplane_file(str(tmp_path)))
    spans = [e for e in events if e["name"] == "bench.handler"]
    assert len(spans) == 1 and spans[0]["d"] > 0
    line = (spans[0]["plane"], spans[0]["line"])
    # every kept host event lies on a thread that ran a benchmark span
    assert {(e["plane"], e["line"]) for e in events
            if not profile.is_device_plane(e["plane"])} == {line}


def test_recorded_tpu_trace_reduces_to_its_known_numbers():
    with gzip.open(DATA / "tpu_v5e_kmeans_trace.json.gz", "rt") as f:
        rec = json.load(f)
    s = profile.TraceSummary(rec["events"], rec["lo"], rec["hi"])
    want = rec["expect"]
    assert s.n_devices == 1
    assert s.busy_s == pytest.approx(want["busy_s"], rel=1e-12)
    assert s.window_s == pytest.approx(want["window_s"], rel=1e-12)
    assert 0.0 < s.idle_share < 1.0
    count, seconds = s.module_time("_assign_update")
    assert count == want["step_runs"] and count > 0
    assert seconds == pytest.approx(want["step_s"], rel=1e-12)
    b = s.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert sum(v for _, v in b["idle_gaps"]) <= s.window_s
    # the breakdown's idle time is the whole idle time when few labels
    assert b["idle_gaps"] == want["idle_gaps"]
