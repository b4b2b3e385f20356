"""Behaviour tests for the Pilot-Edge core: broker semantics, pilot
lifecycle, runtime fault tolerance, placement, parameter service,
elasticity."""
import threading
import time

import numpy as np
import pytest

from repro.core import (AutoScaler, Broker, ComputeResource, ConsumerGroup,
                        EdgeToCloudPipeline, MetricsRegistry,
                        ParameterService, Pilot, PilotError, PilotManager,
                        PlacementEngine, ScalePolicy, SimClock, TaskFailed,
                        TaskProfile, TaskRuntime, WanShaper, remesh_restart)
from repro.core.monitoring import LatencySketch


def _drive(clock, fut, step_s=0.5, timeout_s=10.0):
    """Advance virtual time in steps until the future resolves — the test
    plays the role of the (virtual) passage of time."""
    deadline = time.monotonic() + timeout_s
    while not fut.done() and time.monotonic() < deadline:
        clock.advance(step_s)
        time.sleep(0.002)
    return fut


# ---------------------------------------------------------------------------
# broker
# ---------------------------------------------------------------------------

def test_topic_ordering_within_partition():
    b = Broker()
    t = b.create_topic("t", n_partitions=1)
    for i in range(10):
        t.produce(np.array([i]), partition=0)
    got = [t.poll(0, i).value()[0] for i in range(10)]
    assert got == list(range(10))


def test_topic_round_robin_and_keyed():
    b = Broker()
    t = b.create_topic("t", n_partitions=4)
    msgs = [t.produce(np.array([i])) for i in range(8)]
    assert sorted(m.partition for m in msgs) == [0, 0, 1, 1, 2, 2, 3, 3]
    m1 = t.produce(np.array([1]), key="device-7")
    m2 = t.produce(np.array([2]), key="device-7")
    assert m1.partition == m2.partition


def test_serialization_roundtrip_and_sizes():
    b = Broker()
    t = b.create_topic("t")
    data = np.random.default_rng(0).standard_normal((100, 32))
    m = t.produce(data)
    got = t.poll(0, 0).value()
    np.testing.assert_array_equal(got, data)
    # paper accounting: ~8 B/value + npy header
    assert abs(m.nbytes - 100 * 32 * 8) < 200


def test_consumer_group_commit_resume():
    b = Broker()
    t = b.create_topic("t", n_partitions=2)
    g = ConsumerGroup(t)
    g.join("c0")
    for i in range(6):
        t.produce(np.array([i]))
    seen = []
    for _ in range(3):
        m = g.poll("c0", timeout_s=1.0)
        seen.append(int(m.value()[0]))
        g.commit(m)
    assert g.lag() == 3
    # c0 dies; c1 takes over from committed offsets
    g.leave("c0")
    g.join("c1")
    rest = []
    for _ in range(3):
        m = g.poll("c1", timeout_s=1.0)
        rest.append(int(m.value()[0]))
        g.commit(m)
    assert sorted(seen + rest) == list(range(6))
    assert g.lag() == 0


def test_wan_shaper_bandwidth_serialization():
    sh = WanShaper(bandwidth_bps=8e6, rtt_s=0.1, sleep=False)  # 1 MB/s
    d1 = sh.delay_for(500_000, now=0.0)      # 0.5 MB -> 0.5s tx + 0.05 lat
    assert abs(d1 - 0.55) < 1e-6
    d2 = sh.delay_for(500_000, now=0.0)      # queued behind the first
    assert abs(d2 - 1.05) < 1e-6


# ---------------------------------------------------------------------------
# pilots
# ---------------------------------------------------------------------------

def test_pilot_admission_and_release():
    mgr = PilotManager()
    n = mgr.free_devices
    p = mgr.submit_pilot(ComputeResource(tier="cloud", n_devices=n))
    assert mgr.free_devices == 0
    assert p.mesh is not None and p.mesh.size == n
    with pytest.raises(PilotError):
        mgr.submit_pilot(ComputeResource(tier="cloud", n_devices=1))
    mgr.release(p)
    assert mgr.free_devices == n


def test_pilot_edge_no_devices():
    mgr = PilotManager()
    p = mgr.submit_pilot(ComputeResource(tier="edge", n_workers=3))
    assert p.mesh is None and p.capacity == 3
    mgr.release(p)


def test_pilot_resize_workers():
    mgr = PilotManager()
    p = mgr.submit_pilot(ComputeResource(tier="edge", n_workers=2))
    mgr.resize(p, n_workers=8)
    assert p.resource.n_workers == 8


def test_failed_pilot_devices_not_reused():
    mgr = PilotManager()
    n = mgr.free_devices
    p = mgr.submit_pilot(ComputeResource(tier="cloud", n_devices=n))
    mgr.mark_failed(p)
    assert p.state == "failed"
    assert mgr.free_devices == 0          # devices are gone, not recycled


# ---------------------------------------------------------------------------
# runtime: retries, heartbeats, stragglers
# ---------------------------------------------------------------------------

def _edge_pilot(workers=4):
    return PilotManager().submit_pilot(
        ComputeResource(tier="edge", n_workers=workers))


def test_runtime_basic_and_map():
    rt = TaskRuntime(_edge_pilot())
    futs = rt.map(lambda ctx, x: x * 2, range(8))
    assert [f.result(5) for f in futs] == [0, 2, 4, 6, 8, 10, 12, 14]
    rt.shutdown()


def test_runtime_retry_then_success():
    rt = TaskRuntime(_edge_pilot(), max_retries=2)
    calls = []

    def flaky(ctx):
        calls.append(ctx.attempt)
        if ctx.attempt < 2:
            raise RuntimeError("boom")
        return "ok"

    assert rt.submit(flaky).result(10) == "ok"
    assert calls == [0, 1, 2]
    assert rt.metrics.counter("runtime.retries") == 2
    rt.shutdown()


def test_runtime_retries_exhausted():
    rt = TaskRuntime(_edge_pilot(), max_retries=1)
    fut = rt.submit(lambda ctx: 1 / 0)
    with pytest.raises(TaskFailed):
        fut.result(10)
    rt.shutdown()


def test_runtime_heartbeat_timeout_recovers():
    # virtual time: the hung attempt blocks on the SimClock; advancing past
    # the heartbeat timeout triggers loss detection with zero real waiting
    clock = SimClock(auto_advance=False)
    rt = TaskRuntime(_edge_pilot(), max_retries=1,
                     heartbeat_timeout_s=0.3, monitor_interval_s=0.01,
                     clock=clock)
    state = {"hung": False}
    hung = threading.Event()

    def task(ctx):
        if ctx.attempt == 0:
            state["hung"] = True
            hung.set()
            ctx.clock.sleep(60.0)    # no heartbeat -> declared lost
            return "zombie"
        return "recovered"

    fut = rt.submit(task)
    assert hung.wait(5.0)
    assert _drive(clock, fut).result(1) == "recovered"
    assert state["hung"]
    clock.close()
    rt.shutdown(wait=False)


def test_runtime_straggler_speculation():
    clock = SimClock(auto_advance=False)
    rt = TaskRuntime(_edge_pilot(8), speculative_factor=3.0,
                     monitor_interval_s=0.01, clock=clock)
    # establish a (virtually instantaneous) median
    for f in rt.map(lambda ctx, x: x, range(6)):
        f.result(5)
    hung = threading.Event()

    def straggler(ctx):
        if ctx.attempt == 0:
            hung.set()
            ctx.clock.sleep(600.0)   # way past 3x median
            return "slow"
        return "backup"

    fut = rt.submit(straggler)
    assert hung.wait(5.0)
    assert _drive(clock, fut).result(1) == "backup"
    assert fut.speculated
    m = rt.metrics
    assert m.counter("runtime.speculative_launches") >= 1
    # first-completion-wins accounting: the backup won, and every launch
    # is accounted (wins + losses + cancelled == launches)
    assert m.counter("runtime.speculative_wins") == 1
    assert (m.counter("runtime.speculative_wins")
            + m.counter("runtime.speculative_losses")
            + m.counter("runtime.speculative_cancelled")
            == m.counter("runtime.speculative_launches"))
    clock.close()
    rt.shutdown(wait=False)


def test_runtime_speculation_cancelled_on_terminal_failure():
    """A speculated task that never completes (backup attempts exhaust the
    retries) resolves its launches as *cancelled*, keeping the accounting
    identity for the whole-body path too."""
    clock = SimClock(auto_advance=False)
    rt = TaskRuntime(_edge_pilot(8), speculative_factor=3.0,
                     max_retries=1, monitor_interval_s=0.01, clock=clock)
    for f in rt.map(lambda ctx, x: x, range(6)):
        f.result(5)
    hung = threading.Event()

    def doomed(ctx):
        if ctx.attempt == 0:
            hung.set()
            ctx.clock.sleep(600.0)   # straggles → speculation fires
            return "slow"
        raise RuntimeError("backup blows up")   # → retries exhaust

    fut = rt.submit(doomed)
    assert hung.wait(5.0)
    with pytest.raises(TaskFailed):
        _drive(clock, fut).result(1)
    m = rt.metrics
    launches = m.counter("runtime.speculative_launches")
    assert launches >= 1
    assert m.counter("runtime.speculative_wins") == 0
    assert (m.counter("runtime.speculative_losses")
            + m.counter("runtime.speculative_cancelled") == launches)
    assert m.counter("runtime.speculative_cancelled") >= 1
    clock.close()
    rt.shutdown(wait=False)


# ---------------------------------------------------------------------------
# placement
# ---------------------------------------------------------------------------

def test_placement_light_task_stays_on_edge():
    mgr = PilotManager()
    edge = mgr.submit_pilot(ComputeResource(tier="edge", n_workers=1))
    cloud = mgr.submit_pilot(ComputeResource(tier="cloud", n_workers=8))
    eng = PlacementEngine()
    light = TaskProfile(flops=1e6, input_bytes=1e6, input_tier="edge")
    heavy = TaskProfile(flops=1e12, input_bytes=1e6, input_tier="edge")
    assert eng.place(light, [edge, cloud]).pilot.tier == "edge"
    assert eng.place(heavy, [edge, cloud]).pilot.tier == "cloud"


def test_placement_preference_and_memory_veto():
    mgr = PilotManager()
    edge = mgr.submit_pilot(ComputeResource(tier="edge", n_workers=1,
                                            memory_gb=4))
    cloud = mgr.submit_pilot(ComputeResource(tier="cloud", n_workers=1,
                                             memory_gb=44))
    eng = PlacementEngine()
    pref = TaskProfile(flops=1e6, preferred_tiers=("cloud",))
    assert eng.place(pref, [edge, cloud]).pilot.tier == "cloud"
    big = TaskProfile(flops=1e6, memory_gb=16.0)
    assert eng.place(big, [edge, cloud]).pilot.tier == "cloud"


# ---------------------------------------------------------------------------
# parameter service
# ---------------------------------------------------------------------------

def test_param_service_versioning():
    ps = ParameterService()
    v1 = ps.publish("m", {"w": np.ones(3)})
    v2 = ps.publish("m", {"w": np.ones(3) * 2})
    assert (v1, v2) == (1, 2)
    ver, tree = ps.fetch("m")
    assert ver == 2 and tree["w"][0] == 2
    assert ps.fetch_if_newer("m", 2) is None
    got = ps.fetch_if_newer("m", 1)
    assert got is not None and got[0] == 2


def test_param_service_publish_is_snapshot():
    ps = ParameterService()
    w = np.ones(3)
    ps.publish("m", {"w": w})
    w[:] = 99                      # mutate after publish
    assert ps.fetch("m")[1]["w"][0] == 1


def test_param_service_subscribe():
    ps = ParameterService()
    got = []
    ps.subscribe("m", lambda v, t: got.append(v))
    ps.publish("m", {"w": np.zeros(1)})
    ps.publish("m", {"w": np.zeros(1)})
    assert got == [1, 2]


# ---------------------------------------------------------------------------
# pipeline end-to-end + dynamism
# ---------------------------------------------------------------------------

def _mini_pipeline(n_workers=2, **kw):
    mgr = PilotManager()
    edge = mgr.submit_pilot(ComputeResource(tier="edge",
                                            n_workers=n_workers))
    cloud = mgr.submit_pilot(ComputeResource(tier="cloud",
                                             n_workers=n_workers))
    rng = np.random.default_rng(0)
    return EdgeToCloudPipeline(
        pilot_cloud_processing=cloud, pilot_edge=edge,
        produce_function_handler=lambda ctx: rng.standard_normal((50, 4)),
        process_cloud_function_handler=lambda ctx, data=None:
            float(np.mean(data)),
        n_edge_devices=n_workers, **kw)


def test_pipeline_processes_all_messages():
    res = _mini_pipeline().run(n_messages=40, timeout_s=30)
    assert res.n_processed == 40
    assert len(res.results) == 40
    assert res.metrics.summary()["count"] == 40


def test_pipeline_hot_swap():
    mgr = PilotManager()
    edge = mgr.submit_pilot(ComputeResource(tier="edge", n_workers=2))
    cloud = mgr.submit_pilot(ComputeResource(tier="cloud", n_workers=2))
    rng = np.random.default_rng(0)
    n_seen = []

    def slow_fn(ctx, data=None):
        n_seen.append(1)
        time.sleep(0.005)                 # keep the stream in flight
        return float(np.mean(data))

    pipe = EdgeToCloudPipeline(
        pilot_cloud_processing=cloud, pilot_edge=edge,
        produce_function_handler=lambda ctx: rng.standard_normal((50, 4)),
        process_cloud_function_handler=slow_fn, n_edge_devices=2)
    swapped = []

    def new_fn(ctx, data=None):
        swapped.append(1)
        return -1.0

    def swap_when_halfway():
        while len(n_seen) < 10:
            time.sleep(0.002)
        pipe.replace_function("process_cloud", new_fn)

    threading.Thread(target=swap_when_halfway, daemon=True).start()
    res = pipe.run(n_messages=60, timeout_s=30)
    assert res.n_processed == 60
    assert swapped, "hot-swapped function never ran"
    assert any(r == -1.0 for r in res.results)


def test_pipeline_consumer_fault_recovers():
    fault = {"armed": True}
    lock = threading.Lock()
    rng = np.random.default_rng(0)
    mgr = PilotManager()
    edge = mgr.submit_pilot(ComputeResource(tier="edge", n_workers=2))
    cloud = mgr.submit_pilot(ComputeResource(tier="cloud", n_workers=2))

    def flaky(ctx, data=None):
        with lock:
            if fault["armed"]:
                fault["armed"] = False
                raise RuntimeError("injected")
        return 0.0

    pipe = EdgeToCloudPipeline(
        pilot_cloud_processing=cloud, pilot_edge=edge,
        produce_function_handler=lambda ctx: rng.standard_normal((10, 4)),
        process_cloud_function_handler=flaky, max_retries=2)
    res = pipe.run(n_messages=30, timeout_s=30)
    assert res.n_processed == 30           # nothing lost
    assert res.metrics.counter("runtime.task_errors") == 1
    assert res.metrics.counter("runtime.retries") == 1


def test_pipeline_run_ends_when_a_stage_has_failed():
    """A handler that always raises fails every consumer task for good;
    the run ends then, not at its timeout, and says which stage died."""
    mgr = PilotManager()
    edge = mgr.submit_pilot(ComputeResource(tier="edge", n_workers=2))
    cloud = mgr.submit_pilot(ComputeResource(tier="cloud", n_workers=2))

    def broken(ctx, data=None):
        raise RuntimeError("broken handler")

    pipe = EdgeToCloudPipeline(
        pilot_cloud_processing=cloud, pilot_edge=edge,
        produce_function_handler=lambda ctx: np.zeros((4, 2)),
        process_cloud_function_handler=broken, max_retries=1)
    t0 = time.monotonic()
    res = pipe.run(n_messages=20, timeout_s=600)
    assert time.monotonic() - t0 < 30
    assert res.n_processed == 0 and res.n_produced == 20
    assert res.metrics.counter("runtime.task_errors") == 4   # 2 tasks x 2
    assert [e["stage"] for e in res.metrics.events("run_aborted")] == \
        ["process_cloud"]
    mgr.release_all()


def test_pipeline_runs_under_manual_simclock():
    """The threaded pipeline accepts a manually driven SimClock: a driver
    thread plays time while run() executes, metrics land on virtual
    timestamps, and shutdown doesn't hang on parked virtual sleepers."""
    clock = SimClock(auto_advance=False)
    pipe = _mini_pipeline(clock=clock)
    stop = threading.Event()

    def drive():
        while not stop.is_set():
            clock.advance(0.05)
            time.sleep(0.001)

    driver = threading.Thread(target=drive, daemon=True)
    driver.start()
    t0 = time.monotonic()
    try:
        res = pipe.run(n_messages=20, timeout_s=300.0)
    finally:
        stop.set()
        driver.join(5.0)
        clock.close()
    assert res.n_processed == 20
    assert time.monotonic() - t0 < 30.0     # no real-timeout stalls
    assert res.wall_s < 300.0               # virtual wall, not real
    assert res.metrics.summary()["count"] == 20


def test_threaded_run_rejects_auto_advance_clock():
    """Auto-advance virtual time belongs to SimExecutor; the threaded
    strategy (the default) refuses it at run time."""
    pipe = _mini_pipeline(clock=SimClock())      # construction is fine now
    with pytest.raises(ValueError):
        pipe.run(n_messages=4)


def test_sim_executor_requires_pipeline_clock():
    from repro.core import SimExecutor
    pipe = _mini_pipeline(clock=SimClock())
    with pytest.raises(ValueError):
        pipe.run(n_messages=4, scheduler=SimExecutor(clock=SimClock()))
    # and a wall-clock pipeline can't adopt a DES strategy
    with pytest.raises(ValueError):
        _mini_pipeline().run(n_messages=4, scheduler=SimExecutor())


def test_pipeline_wan_accounting():
    sh = WanShaper(bandwidth_bps=80e6, rtt_s=0.15, sleep=False)
    res = _mini_pipeline(wan_shaper=sh).run(n_messages=10, timeout_s=30)
    assert res.n_processed == 10
    # every message recorded a wan delay stamp
    lat = res.metrics.latencies("produced", "broker_in")
    assert len(lat) == 10


# ---------------------------------------------------------------------------
# elasticity
# ---------------------------------------------------------------------------

def test_autoscaler_scales_up_and_down():
    mgr = PilotManager()
    pilot = mgr.submit_pilot(ComputeResource(tier="cloud", n_workers=2))
    lag = {"v": 100}
    sc = AutoScaler(mgr, pilot, lag_fn=lambda: lag["v"],
                    policy=ScalePolicy(max_workers=8, lag_high=50,
                                       lag_low=5, cooldown_s=0.0))
    assert sc.step_once() == 4
    assert sc.step_once() == 8
    assert sc.step_once() is None          # at max
    lag["v"] = 0
    assert sc.step_once() == 4
    assert pilot.resource.n_workers == 4


def test_remesh_restart():
    mgr = PilotManager()
    n = mgr.free_devices
    p = mgr.submit_pilot(ComputeResource(tier="cloud", n_devices=n))
    restored = {}

    def restore_fn(new_pilot):
        restored["mesh_size"] = new_pilot.mesh.size if new_pilot.mesh \
            else 0
        return {"step": 7}

    # device lost: restart on n-? — single-device container: reuse 0 free
    mgr.release(p)                      # free them to simulate survivors
    p2 = mgr.submit_pilot(ComputeResource(tier="cloud", n_devices=n))
    new_pilot, state = remesh_restart(mgr, p2, 0, restore_fn=restore_fn)
    assert state == {"step": 7}
    assert new_pilot.state == "active"


# ---------------------------------------------------------------------------
# broker log truncation (bounded-memory retention)
# ---------------------------------------------------------------------------

def test_truncation_reclaims_committed_prefix_keeps_absolute_offsets():
    b = Broker()
    t = b.create_topic("t", n_partitions=1, truncate_batch=4)
    g = ConsumerGroup(t)
    g.join("c0")
    for i in range(10):
        t.produce(np.array([i]))
    for _ in range(10):
        g.commit(g.poll("c0", timeout_s=1.0))
    # 10 committed in batches of 4: two chunks reclaimed, 2 retained
    assert t.truncated_msgs == 8
    assert t.log_start_offsets() == [8]
    assert t.end_offsets() == [10]          # absolute offsets unaffected
    assert [m.offset for m in t.partitions[0].log] == [8, 9]
    assert int(t.poll(0, 8).value()[0]) == 8
    with pytest.raises(KeyError):
        t.poll(0, 7)                        # below the log start: reclaimed
    # producing after truncation continues the absolute numbering
    m = t.produce(np.array([10]))
    assert m.offset == 10


def test_truncation_blocked_until_every_group_commits():
    """The group-minimum committed offset bounds reclamation: a lagging
    second group pins the log even though the first has committed all."""
    b = Broker()
    t = b.create_topic("t", n_partitions=1, truncate_batch=2)
    g1 = ConsumerGroup(t, group_id="g1")
    g2 = ConsumerGroup(t, group_id="g2")
    g1.join("a")
    g2.join("b")
    for i in range(8):
        t.produce(np.array([i]))
    for _ in range(8):
        g1.commit(g1.poll("a", timeout_s=1.0))
    assert t.truncated_msgs == 0            # g2 still at offset 0
    for _ in range(8):
        g2.commit(g2.poll("b", timeout_s=1.0))
    assert t.truncated_msgs == 8
    assert t.log_sizes() == [0]


def test_truncation_late_group_starts_at_log_start():
    """Kafka 'earliest' semantics against a truncated log: a group that
    joins after reclamation starts at the log start (not absolute 0) and
    replays exactly the retained tail."""
    b = Broker()
    t = b.create_topic("t", n_partitions=1, truncate_batch=3)
    g = ConsumerGroup(t)
    g.join("c0")
    for i in range(9):
        t.produce(np.array([i]))
    for _ in range(7):
        g.commit(g.poll("c0", timeout_s=1.0))
    assert t.log_start_offsets() == [6]
    late = ConsumerGroup(t, group_id="late")
    assert late.committed == [6]
    late.join("z")
    got = []
    for _ in range(3):
        m = late.poll("z", timeout_s=1.0)
        got.append(int(m.value()[0]))
        late.commit(m)
    assert got == [6, 7, 8]
    assert late.lag() == 0


def test_truncation_callback_reports_reclaimed_msg_ids():
    b = Broker()
    t = b.create_topic("t", n_partitions=2, truncate_batch=2)
    reclaimed = []
    t.on_truncate(lambda part, ids: reclaimed.append((part, list(ids))))
    g = ConsumerGroup(t)
    g.join("c0")
    produced = [t.produce(np.array([i])) for i in range(8)]
    for _ in range(8):
        g.commit(g.poll("c0", timeout_s=1.0))
    got_ids = {mid for _, ids in reclaimed for mid in ids}
    assert got_ids == {m.msg_id for m in produced}
    assert {p for p, _ in reclaimed} == {0, 1}


def test_truncation_disabled_and_no_group_cases():
    b = Broker()
    # retention off: logs grow, base pinned at 0
    t0 = b.create_topic("t0", n_partitions=1)
    g = ConsumerGroup(t0)
    g.join("c0")
    for i in range(6):
        t0.produce(np.array([i]))
    for _ in range(6):
        g.commit(g.poll("c0", timeout_s=1.0))
    assert t0.truncated_msgs == 0
    assert t0.log_start_offsets() == [0]
    assert t0.maybe_truncate(0) == 0
    # retention on but no consumer group yet: nothing is safe to reclaim
    t1 = b.create_topic("t1", n_partitions=1, truncate_batch=1)
    t1.produce(np.array([0]))
    assert t1.maybe_truncate(0) == 0


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_truncation_churn_preserves_at_least_once(seed):
    """Seed-driven cousin of the hypothesis property test in
    test_properties.py (which needs the CI image): random poll/commit/
    crash/rejoin churn against a truncating topic never reclaims an
    uncommitted offset and still delivers every message at least once."""
    rng = np.random.default_rng(seed)
    n_msgs = int(rng.integers(10, 40))
    n_parts = int(rng.integers(1, 4))
    batch = int(rng.integers(1, 6))
    clock = SimClock()
    b = Broker(clock=clock)
    t = b.create_topic("t", n_partitions=n_parts, truncate_batch=batch)
    g = ConsumerGroup(t)
    consumers = ["c0", "c1"]
    for c in consumers:
        g.join(c)
    for i in range(n_msgs):
        t.produce(np.array([i]))
    seen, deliveries = set(), 0
    alive = list(consumers)
    for _ in range(40 * n_msgs + 400):
        starts = t.log_start_offsets()
        ends = t.end_offsets()
        for p in range(n_parts):
            assert starts[p] <= g.committed[p], \
                "truncation reclaimed an uncommitted offset"
            assert [m.offset for m in t.partitions[p].log] \
                == list(range(starts[p], ends[p]))
        if g.lag() == 0:
            break
        if len(alive) < len(consumers) and rng.random() < 0.2:
            back = [c for c in consumers if c not in alive][0]
            alive.append(back)
            g.join(back)
        cid = alive[int(rng.integers(0, len(alive)))]
        msg, _ = g.poll_nowait(cid)
        if msg is None:
            clock.advance(0.01)
            continue
        deliveries += 1
        seen.add(int(msg.value()[0]))
        if len(alive) > 1 and rng.random() < 0.25:
            # crash before the commit: the offset must survive truncation
            # and be redelivered after the rebalance
            alive.remove(cid)
            g.leave(cid)
        else:
            g.commit(msg)
    assert g.lag() == 0
    assert deliveries >= n_msgs          # at-least-once
    assert seen == set(range(n_msgs))    # every message delivered, no gaps


# ---------------------------------------------------------------------------
# streaming metrics (bounded-memory sketches)
# ---------------------------------------------------------------------------

class _Tick:
    """Bare now() callable with settable time (the seed clock API)."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_streaming_registry_matches_exact_aggregates():
    """The same stamp stream through exact and streaming registries:
    counts/first/last/throughput/max agree exactly, percentiles agree to
    within the sketch's bucket width."""
    rng = np.random.default_rng(7)
    lats = rng.lognormal(mean=-2.0, sigma=1.0, size=2000)
    clocks = (_Tick(), _Tick())
    exact = MetricsRegistry(clocks[0])
    stream = MetricsRegistry(clocks[1], streaming=True)
    for i, lat in enumerate(lats):
        for clk, m in zip(clocks, (exact, stream)):
            clk.t = i * 0.01
            m.stamp(f"m{i}", "produced", bytes=100.0)
            clk.t = i * 0.01 + float(lat)
            m.stamp(f"m{i}", "processed", bytes=100.0)
    assert stream.pending_traces == 0          # all retired at `processed`
    assert stream.retired_traces == len(lats)
    se, ss = exact.summary(), stream.summary()
    assert se["count"] == ss["count"] == len(lats)
    np.testing.assert_allclose(ss["mean_s"], se["mean_s"], rtol=1e-9)
    assert ss["max_s"] == se["max_s"]
    for q in (0.5, 0.9, 0.95, 0.99):
        np.testing.assert_allclose(stream.percentile(q),
                                   exact.percentile(q), rtol=0.04)
    for ev in ("produced", "processed"):
        assert stream.event_count(ev) == exact.event_count(ev)
        assert stream.first_stamp(ev) == exact.first_stamp(ev)
        assert stream.last_stamp(ev) == exact.last_stamp(ev)
        assert stream.throughput(ev) == exact.throughput(ev)


def test_streaming_registry_refuses_per_message_views():
    m = MetricsRegistry(streaming=True)
    m.stamp("a", "produced")
    m.stamp("a", "processed")
    with pytest.raises(RuntimeError):
        m.latencies()


def test_latency_sketch_percentile_bounds():
    rng = np.random.default_rng(3)
    xs = rng.exponential(scale=0.1, size=5000)
    sk = LatencySketch()
    for x in xs:
        sk.add(float(x))
    assert sk.count == len(xs)
    assert sk.percentile(0.0) == float(np.min(xs))     # exact extremes
    assert sk.percentile(1.0) == float(np.max(xs))
    srt = np.sort(xs)
    for q in (0.25, 0.5, 0.75, 0.95, 0.99):
        est = sk.percentile(q)
        ref = float(srt[min(len(xs) - 1, int(q * len(xs)))])
        assert ref <= est <= ref * (1.0 + 2 * 10 ** (1 / sk.PER_DECADE))
        np.testing.assert_allclose(est, ref, rtol=0.04)
    empty = LatencySketch()
    assert empty.percentile(0.5) == 0.0


def test_streaming_fifo_window_bounds_pending_traces():
    """Traces that never reach `processed` (intermediate hops) leave
    through the max_pending FIFO window instead of accumulating."""
    m = MetricsRegistry(streaming=True, max_pending=10)
    for i in range(100):
        m.stamp(f"m{i}", "produced")
    assert m.pending_traces == 10
    assert m.retired_traces == 90
    # produced-only traces have no spans: nothing lands in the sketches
    assert m.summary() == {"count": 0}
    # ...but their event stats were still counted at the stamp
    assert m.event_count("produced") == 100


def test_pipeline_streaming_metrics_and_truncation_end_to_end():
    """The real threaded pipeline with bounded-memory both ways on:
    sketch-backed metrics and broker-log retention. Everything still
    processes, the summary comes off the sketches, and the topic log was
    actually reclaimed while the run was in flight."""
    m = MetricsRegistry(streaming=True)
    pipe = _mini_pipeline(metrics=m, truncate_logs=8)
    res = pipe.run(n_messages=40, timeout_s=30)
    assert res.n_processed == 40
    assert res.metrics.summary()["count"] == 40
    assert res.metrics.percentile(0.95) > 0.0
    assert sum(t.truncated_msgs for t in pipe._topics) > 0
    with pytest.raises(RuntimeError):
        res.metrics.latencies()
