"""Sharded DES tests: seed splitting, partitioning helpers, boundary
injection, shard-count determinism, and the conservative-window
causality property.

The property tests follow the repo's stubbed-hypothesis idiom (the
container has no ``hypothesis``): seed-parametrized
``np.random.default_rng`` loops drawing randomized configurations.
"""
import math

import jax
import numpy as np
import pytest

from repro.core import ComputeResource, PilotManager
from repro.core.broker import Broker
from repro.core.faas import ContinuumPipeline, StageSpec
from repro.core.monitoring import MetricsRegistry
from repro.core.placement import PlacementEngine
from repro.sim.clock import SimClock
from repro.sim.shard import (ShardCoordinator, build_scale_shard,
                             lookahead_s, merge_rows, run_scale_sharded,
                             shard_seed, split_blocks, tier_cut_builders)

# ---------------------------------------------------------------------------
# seed splitting
# ---------------------------------------------------------------------------


def test_shard_seed_pinned():
    # pinned SplitMix64 outputs: the per-shard streams are part of the
    # determinism contract, so the mix itself must never drift
    assert shard_seed(0, 0) == 16294208416658607535
    assert shard_seed(0, 1) == 7960286522194355700
    assert shard_seed(0, 2) == 487617019471545679
    assert shard_seed(12345, 7) == 7959005890829367068


def test_shard_seed_streams_distinct_and_64bit():
    seen = set()
    for seed in range(8):
        for sid in range(64):
            z = shard_seed(seed, sid)
            assert 0 <= z < 2 ** 64
            seen.add(z)
    assert len(seen) == 8 * 64          # no collisions across the grid


def test_shard_seed_differs_from_naive_offset():
    # the point of the split: stream (seed, sid) is not stream
    # (seed + sid, 0) of the same family
    assert shard_seed(0, 1) != shard_seed(1, 0)


# ---------------------------------------------------------------------------
# partitioning helpers
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_split_blocks_properties(seed):
    rng = np.random.default_rng(seed)
    for _ in range(50):
        n = int(rng.integers(0, 200))
        k = int(rng.integers(1, 17))
        blocks = split_blocks(n, k)
        assert len(blocks) == k
        # exact disjoint cover of range(n), in order
        flat = [i for lo, hi in blocks for i in range(lo, hi)]
        assert flat == list(range(n))
        sizes = [hi - lo for lo, hi in blocks]
        assert max(sizes) - min(sizes) <= 1


def test_split_blocks_monotone_in_n():
    # consumers >= devices globally must imply it per shard: block i of
    # the larger n always covers at least block i of the smaller n
    for k in (1, 2, 3, 5, 8):
        for devices in (3, 8, 17):
            for consumers in (devices, devices + 1, 4 * devices):
                dev = split_blocks(devices, k)
                con = split_blocks(consumers, k)
                for (dlo, dhi), (clo, chi) in zip(dev, con):
                    assert chi - clo >= dhi - dlo


def test_split_blocks_rejects_bad_k():
    with pytest.raises(ValueError):
        split_blocks(10, 0)


def test_lookahead_from_cost_model():
    cost = PlacementEngine().cost
    la = lookahead_s(cost, [("edge", "cloud")])
    # pure routed link latency of the edge->cloud WAN hop
    assert la == cost.route("edge", "cloud").transfer_s(0.0)
    assert la > 0.0
    # min over the cut set
    multi = lookahead_s(cost, [("edge", "cloud"), ("device", "edge")])
    assert multi == min(
        cost.route("edge", "cloud").transfer_s(0.0),
        cost.route("device", "edge").transfer_s(0.0))
    # no cut links -> fully independent shards -> one unbounded window
    assert lookahead_s(cost, []) == math.inf


# ---------------------------------------------------------------------------
# boundary injection
# ---------------------------------------------------------------------------


def test_inject_skips_ingress_accounting():
    clock = SimClock()
    metrics = MetricsRegistry(clock=clock)
    broker = Broker(metrics=metrics, clock=clock)
    topic = broker.create_topic("boundary", n_partitions=2)
    msg = topic.inject(b"x" * 32, msg_id="m-1", partition=1, ready_at=4.0,
                       produced_t=2.5)
    # ingress counters belong to the producing shard: injection must not
    # double-count bytes/messages on the receiving side
    assert metrics.counter("topic.boundary.bytes_in") == 0.0
    assert metrics.counter("topic.boundary.msgs_in") == 0.0
    part = topic.partitions[1]
    assert part.log[-1] is msg
    assert part.ready_at[-1] == 4.0
    # the produced stamp carries the original production time across the
    # process boundary (end-to-end latency stays exact)
    assert metrics.trace("m-1").stamps["produced"] == 2.5


def test_scale_shard_refuses_partition_coupling():
    # consumers < devices couples partitions through shared consumers:
    # the documented too-chatty-to-shard condition
    with pytest.raises(ValueError, match="too chatty"):
        run_scale_sharded(arrival="poisson", messages=10, devices=4,
                          consumers=2, rate_hz=100.0, payload_bytes=8,
                          service_s=0.0, seed=0, shards=2)
    with pytest.raises(ValueError, match="shards"):
        run_scale_sharded(arrival="poisson", messages=10, devices=4,
                          consumers=4, rate_hz=100.0, payload_bytes=8,
                          service_s=0.0, seed=0, shards=8)


def test_zero_task_stage_and_bad_partitions_raise():
    mgr = PilotManager()
    edge = mgr.submit_pilot(ComputeResource(tier="edge", n_workers=2))
    cloud = mgr.submit_pilot(ComputeResource(tier="cloud", n_workers=2))
    stages = [StageSpec("produce", lambda ctx: b"", pilot=edge, n_tasks=0),
              StageSpec("process", lambda ctx, data=None: None,
                        pilot=cloud, n_tasks=2)]
    # a zero-task source stage is legal (the tier-cut downstream shard)
    # but then n_partitions must be given explicitly and positive
    with pytest.raises(ValueError):
        ContinuumPipeline(stages=stages, clock=SimClock())
    pipe = ContinuumPipeline(stages=stages, n_partitions=3,
                             clock=SimClock())
    assert pipe.n_partitions == 3
    assert pipe.stage_tasks(0) == 0
    mgr.release_all()


# ---------------------------------------------------------------------------
# shard-count determinism (the regression the CI parity lane gates)
# ---------------------------------------------------------------------------

_DET_KEYS = ("processed", "duplicates", "truncated_msgs", "makespan_s",
             "lat_p50_s", "lat_p95_s", "wan_bytes")


def _sharded_cell(shards, mode="inline", **overrides):
    cfg = dict(arrival="poisson", messages=2000, devices=6, consumers=9,
               rate_hz=1000.0, payload_bytes=48, service_s=0.002, seed=11,
               shards=shards, mode=mode)
    cfg.update(overrides)
    return run_scale_sharded(**cfg)


def test_shard_counts_1_2_4_bit_identical():
    rows = {k: _sharded_cell(k) for k in (1, 2, 4)}
    base = rows[1]
    assert base["processed"] == 2000
    for k in (2, 4):
        for key in _DET_KEYS:
            assert rows[k][key] == base[key], (
                f"{key} drifts at {k} shards: {rows[k][key]!r} "
                f"!= {base[key]!r}")
    # aggregate accounting is self-consistent
    assert rows[4]["cpu_critical_s"] <= rows[4]["cpu_s_total"] + 1e-9
    assert rows[4]["windows"] == 1      # no cross-shard links: one window


def test_shard_mp_matches_inline():
    a = _sharded_cell(2, mode="inline")
    b = _sharded_cell(2, mode="mp")
    for key in _DET_KEYS:
        assert a[key] == b[key]


def test_des_shards_fork_without_touching_the_backend(monkeypatch):
    """A parent that holds the accelerator can fork the DES shards: pilots
    without devices and the 2-shard path never ask JAX for its devices,
    while a pilot that wants a device still gets one."""
    real_devices = jax.devices

    def no_backend(*args, **kwargs):
        raise AssertionError("jax.devices() on a path that needs no device")

    monkeypatch.setattr(jax, "devices", no_backend)
    mgr = PilotManager()
    edge = mgr.submit_pilot(ComputeResource(tier="edge", n_workers=2))
    cloud = mgr.submit_pilot(ComputeResource(tier="cloud", n_workers=2))
    assert edge.devices == () and cloud.devices == ()
    row = _sharded_cell(2, mode="mp")
    assert row["processed"] == 2000
    monkeypatch.setattr(jax, "devices", real_devices)
    mesh_pilot = mgr.submit_pilot(ComputeResource(tier="cloud", n_devices=1))
    assert len(mesh_pilot.devices) == 1
    mgr.release_all()


def test_shard_streaming_sketch_merge_identical():
    a = _sharded_cell(1, streaming=True)
    b = _sharded_cell(3, streaming=True)
    for key in _DET_KEYS:
        assert a[key] == b[key]


def test_merge_rows_exact_percentiles():
    # the merged multiset rank formula must match the single-list one
    rows = [
        {"processed": 2, "duplicates": 0, "events": 5, "truncated_msgs": 0,
         "wan_bytes": 10.0, "first_produced": 0.5, "last_processed": 3.0,
         "latencies": [0.3, 0.1]},
        {"processed": 3, "duplicates": 1, "events": 7, "truncated_msgs": 2,
         "wan_bytes": 20.0, "first_produced": 0.2, "last_processed": 4.0,
         "latencies": [0.2, 0.5, 0.4]},
    ]
    merged = merge_rows(rows, streaming=False)
    lat = sorted([0.3, 0.1, 0.2, 0.5, 0.4])
    assert merged["processed"] == 5
    assert merged["duplicates"] == 1
    assert merged["truncated_msgs"] == 2
    assert merged["wan_bytes"] == 30.0
    assert merged["makespan_s"] == pytest.approx(4.0 - 0.2)
    assert merged["lat_p50_s"] == lat[len(lat) // 2]
    assert merged["lat_p95_s"] == lat[min(len(lat) - 1,
                                          int(0.95 * len(lat)))]


# ---------------------------------------------------------------------------
# conservative-window causality (property, stubbed-hypothesis style)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
def test_tier_cut_windows_never_violate_causality(seed):
    """Randomized tier-cut runs: with window <= lookahead (the WAN's
    one-way latency), no cross-shard message is ever visible — let alone
    consumed — before its ``ready_at``, and every message still arrives.
    """
    rng = np.random.default_rng(seed)
    devices = int(rng.integers(2, 6))
    consumers = int(rng.integers(devices, 2 * devices + 1))
    messages = int(rng.integers(100, 400))
    rate_hz = float(rng.uniform(50.0, 400.0))
    payload = int(rng.integers(16, 256))
    rtt_s = float(rng.uniform(0.02, 0.2))
    lookahead = rtt_s / 2.0             # WanShaper: one-way = rtt/2
    window = lookahead * float(rng.uniform(0.3, 1.0))
    cfg = dict(messages=messages, devices=devices, consumers=consumers,
               rate_hz=rate_hz, payload_bytes=payload, seed=seed,
               bandwidth_bps=80e6, rtt_s=rtt_s,
               timeout_s=messages / rate_hz + 60.0)
    coord = ShardCoordinator(tier_cut_builders(cfg), window_s=window,
                             mode="inline")
    rows = coord.run()
    edge, cloud = coord.runners
    # the protocol actually windowed (not one degenerate barrier) and
    # every message crossed the boundary and got processed
    assert coord.windows > 1
    assert len(cloud.injected) == messages
    assert rows[1]["processed"] == messages
    # ingress bytes are counted exactly once, by the producing shard
    assert rows[0]["wan_bytes"] == float(messages * payload)
    assert rows[1]["wan_bytes"] == 0.0
    m = cloud.metrics
    for msg_id, (t_inject, ready_at) in cloud.injected.items():
        # conservative delivery: injected at a barrier at or before the
        # message's visibility time ...
        assert t_inject <= ready_at + 1e-12
        tr = m.trace(msg_id)
        assert tr is not None
        # ... and never consumed before it
        for event in ("broker_out", "consumed", "processed"):
            t = tr.stamps.get(event)
            if t is not None:
                assert t >= ready_at - 1e-12, (
                    f"{event} at {t} before ready_at {ready_at}")
    # end-to-end latency can never beat the WAN's one-way latency
    lat = m.latencies("produced", "processed")
    assert len(lat) == messages
    assert min(lat) >= lookahead - 1e-12


@pytest.mark.parametrize("seed", [0, 1])
def test_tier_cut_deterministic_across_reruns(seed):
    cfg = dict(messages=150, devices=3, consumers=4, rate_hz=150.0,
               payload_bytes=32, seed=seed, bandwidth_bps=50e6,
               rtt_s=0.08, timeout_s=60.0)

    def run_once():
        coord = ShardCoordinator(tier_cut_builders(cfg), window_s=0.03,
                                 mode="inline")
        rows = coord.run()
        return merge_rows(rows, streaming=False)

    a, b = run_once(), run_once()
    for key in _DET_KEYS:
        assert a[key] == b[key]


def test_build_scale_shard_message_totals():
    # each shard draws the *global* arrival cumsum and takes its own
    # device block's interleave slices — so per-shard message targets
    # are the block slice lengths and sum exactly to the global total
    cfg = dict(shards=3, arrival="poisson", messages=500, devices=4,
               consumers=4, rate_hz=500.0, payload_bytes=8, service_s=0.0,
               seed=7, streaming=False, truncate_logs=None, trace=None)
    totals = []
    for sid in range(3):
        runner = build_scale_shard(dict(cfg, shard_id=sid))
        totals.append(runner.handle.state.n_messages)
        runner.handle.finish()
    blocks = split_blocks(cfg["devices"], 3)
    expect = [sum(len(range(g, cfg["messages"], cfg["devices"]))
                  for g in range(lo, hi)) for lo, hi in blocks]
    assert totals == expect
    assert sum(totals) == cfg["messages"]
