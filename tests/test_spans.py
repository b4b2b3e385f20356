"""The span recorder of ``MetricsRegistry``: off by default at no cost,
and, when on, one record per step of the served path (serialize, poll,
deserialize, handler, commit; the handler's device step, pull and
publish) with its parent, message and bytes."""
import collections
import threading

import jax
import numpy as np
import pytest

from repro.core import (ComputeResource, EdgeToCloudPipeline,
                        MetricsRegistry, ParameterService, PilotManager,
                        SimClock, SimExecutor, WanShaper)
from repro.core import monitoring
from repro.ml import AutoEncoder, IsolationForest, KMeans

N_POINTS, N_FEATURES = 200, 8


class _CountedAnnotation(jax.profiler.TraceAnnotation):
    made = 0

    def __init__(self, name, **kw):
        type(self).made += 1
        super().__init__(name, **kw)


@pytest.fixture
def annotations(monkeypatch):
    """Counts the ``TraceAnnotation`` objects constructed in the test."""
    _CountedAnnotation.made = 0
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _CountedAnnotation)
    return _CountedAnnotation


def _by_id(records):
    return {r.id: r for r in records}


def test_spans_off_by_default_record_nothing_and_build_no_annotation(
        annotations):
    reg = MetricsRegistry()
    with reg.span("a", msg_id="m", nbytes=3) as sp:
        sp.msg_id = "other"
        sp.nbytes = 4
        with monitoring.span("b", nbytes=1):
            pass
    assert reg.span("c") is reg.span("d") is monitoring.span("e")
    assert reg.spans() == []
    assert annotations.made == 0
    assert monitoring._open.stack == []


def test_spans_on_record_nesting_message_and_bytes(annotations):
    reg = MetricsRegistry()
    reg.record_spans()
    with monitoring.span("outside"):           # no open span: not recorded
        pass
    with reg.span("top", msg_id="m1"):
        with monitoring.span("mid", nbytes=7):
            with reg.span("inner", msg_id="m2") as sp:
                sp.nbytes = 9
    with reg.span("later"):
        pass
    names = [r.name for r in reg.spans()]
    assert names == ["inner", "mid", "top", "later"]
    rec = {r.name: r for r in reg.spans()}
    assert rec["top"].parent is None and rec["later"].parent is None
    assert rec["mid"].parent == rec["top"].id
    assert rec["inner"].parent == rec["mid"].id
    assert rec["mid"].msg_id == "m1"            # inherited
    assert rec["inner"].msg_id == "m2"          # given
    assert rec["later"].msg_id is None
    assert (rec["mid"].nbytes, rec["inner"].nbytes,
            rec["top"].nbytes) == (7, 9, None)
    assert rec["top"].start <= rec["mid"].start <= rec["inner"].start
    assert rec["inner"].end <= rec["mid"].end <= rec["top"].end
    assert {r.thread for r in reg.spans()} == {threading.get_ident()}
    assert annotations.made == 4


def test_spans_are_per_thread_and_unwind_on_error():
    reg = MetricsRegistry()
    reg.record_spans()
    seen = []

    def other():
        with reg.span("there"):
            pass
        seen.append(monitoring._open.stack)

    with reg.span("here", msg_id="m"):
        t = threading.Thread(target=other)
        t.start()
        t.join(10.0)
        with pytest.raises(ValueError):
            with monitoring.span("fails"):
                raise ValueError("boom")
    assert not t.is_alive() and seen == [[]]
    rec = {r.name: r for r in reg.spans()}
    assert rec["there"].parent is None           # another thread's top
    assert rec["there"].thread != rec["here"].thread
    assert rec["fails"].parent == rec["here"].id
    assert monitoring._open.stack == []


def test_param_service_publish_span_carries_the_published_bytes():
    reg = MetricsRegistry()
    reg.record_spans()
    tree = {"w": np.zeros((3, 4), np.float32), "b": np.zeros(5, np.int64)}
    ParameterService(metrics=reg).publish("m", tree)
    plain = ParameterService()                  # no registry of its own
    plain.publish("m", tree)                    # outside any span
    with reg.span("pilot.handler", msg_id="x"):
        plain.publish("m", tree)
    pubs = [r for r in reg.spans() if r.name == "pilot.publish"]
    assert [p.nbytes for p in pubs] == [88, 88]
    assert pubs[0].parent is None and pubs[1].msg_id == "x"


def _pipeline(handler, produce, metrics, **kw):
    mgr = PilotManager(devices=(), clock=kw.get("clock"))
    edge = mgr.submit_pilot(ComputeResource(tier="edge", n_workers=2))
    cloud = mgr.submit_pilot(ComputeResource(tier="cloud", n_workers=1))
    return EdgeToCloudPipeline(
        pilot_cloud_processing=cloud, pilot_edge=edge,
        produce_function_handler=produce,
        process_cloud_function_handler=handler,
        n_edge_devices=2, cloud_consumers=1, metrics=metrics, **kw)


def _points():
    rng = np.random.default_rng(0)
    return lambda ctx: rng.standard_normal((N_POINTS, N_FEATURES))


def _tree_bytes(tree):
    return sum(x.nbytes for x in jax.tree.leaves(tree))


DETECTORS = {
    "autoencoder": lambda: AutoEncoder(n_features=N_FEATURES, epochs=1),
    "kmeans": lambda: KMeans(n_clusters=4, n_features=N_FEATURES),
    "isoforest": lambda: IsolationForest(n_trees=4, psi=32),
}


@pytest.mark.parametrize("name", sorted(DETECTORS))
def test_threaded_pipeline_spans_every_step_of_each_message(name):
    make = DETECTORS[name]
    metrics = MetricsRegistry()
    params = ParameterService(metrics=metrics)
    handler = make().make_processor(params)
    pipe = _pipeline(handler, _points(), metrics,
                     parameter_service=params)
    metrics.record_spans()
    res = pipe.run(n_messages=6, timeout_s=120.0)
    assert res.n_processed == 6
    recs = metrics.spans()
    by_id = _by_id(recs)
    per_msg = collections.defaultdict(collections.Counter)
    for r in recs:
        if r.msg_id is not None:
            per_msg[r.msg_id][r.name] += 1
    assert len(per_msg) == 6
    model_bytes = _tree_bytes(params.fetch(params.names()[0])[1])
    for msg_id, counts in per_msg.items():
        assert counts == {"pilot.serialize": 1, "pilot.poll": 1,
                          "pilot.deserialize": 1, "pilot.handler": 1,
                          "pilot.commit": 1, "pilot.step": 1,
                          "pilot.pull": 1, "pilot.publish": 1}, msg_id
    handlers = {r.id: r for r in recs if r.name == "pilot.handler"}
    consumer = {r.thread for r in handlers.values()}
    assert len(consumer) == 1
    for r in recs:
        if r.name in ("pilot.step", "pilot.pull", "pilot.publish"):
            assert r.parent in handlers
            assert r.msg_id == handlers[r.parent].msg_id
        elif r.name != "pilot.serialize":
            assert r.parent is None and r.thread in consumer, r
        if r.parent is not None:
            p = by_id[r.parent]
            assert p.start <= r.start <= r.end <= p.end
    serialize = [r for r in recs if r.name == "pilot.serialize"]
    assert not consumer & {r.thread for r in serialize}
    assert all(r.nbytes > N_POINTS * N_FEATURES * 8 for r in serialize)
    # bytes across the host/device boundary, from shapes and dtypes
    moved = collections.Counter()
    for r in recs:
        if r.name in ("pilot.step", "pilot.pull", "pilot.publish"):
            moved[r.msg_id] += r.nbytes
    want = N_POINTS * N_FEATURES * 4 + N_POINTS * 4 + model_bytes
    assert set(moved.values()) == {want}


def test_kmeans_transfer_bytes_at_the_paper_size():
    """10,000 x 32 float32 points in, 10,000 float32 scores out, 25 x 32
    centroids and 25 counts published: 1,323,300 bytes a message."""
    metrics = MetricsRegistry()
    metrics.record_spans()
    params = ParameterService(metrics=metrics)
    handler = KMeans(n_clusters=25, n_features=32).make_processor(params)
    pts = np.random.default_rng(0).standard_normal((10_000, 32))
    with metrics.span("pilot.handler", msg_id="m"):
        handler(None, data=pts)
    got = {r.name: r.nbytes for r in metrics.spans()}
    assert got == {"pilot.step": 1_280_000, "pilot.pull": 40_000,
                   "pilot.publish": 3_300, "pilot.handler": None}


def _des_fingerprint(record: bool):
    clock = SimClock()
    metrics = MetricsRegistry(clock=clock)
    if record:
        metrics.record_spans()
    pipe = _pipeline(lambda ctx, data=None: float(np.sum(data)),
                     lambda ctx: np.arange(64, dtype=np.float64), metrics,
                     clock=clock,
                     wan_shaper=WanShaper(bandwidth_bps=8e6, rtt_s=0.1))
    svc = lambda stage, ctx, data: 0.02 if stage == "produce" else 0.05
    res = pipe.run(n_messages=12, timeout_s=600.0,
                   scheduler=SimExecutor(clock=clock, service_model=svc))
    return (res.n_processed, res.wall_s,
            tuple(sorted(metrics.latencies("produced", "processed")))), \
        metrics.spans()


def test_sim_executor_spans_nest_and_leave_virtual_time_unchanged():
    off, none = _des_fingerprint(record=False)
    on, recs = _des_fingerprint(record=True)
    assert none == [] and on == off
    assert monitoring._open.stack == []
    by_id = _by_id(recs)
    names = collections.Counter(r.name for r in recs)
    # the DES polls through its own event loop, not a blocking Poll
    assert names == {"pilot.serialize": 12, "pilot.deserialize": 12,
                     "pilot.handler": 12, "pilot.commit": 12}
    for r in recs:
        assert r.start <= r.end
        if r.parent is not None:
            p = by_id[r.parent]
            assert p.thread == r.thread
            assert p.start <= r.start <= r.end <= p.end


def test_stamps_keep_only_the_metadata_that_is_read():
    metrics = MetricsRegistry()
    metrics.record_spans()
    pipe = _pipeline(lambda ctx, data=None: 0.0,
                     lambda ctx: np.zeros(4), metrics,
                     wan_shaper=WanShaper(bandwidth_bps=1e9, rtt_s=0.002))
    assert pipe.run(n_messages=4, timeout_s=60.0).n_processed == 4
    ids = {r.msg_id for r in metrics.spans() if r.name == "pilot.serialize"}
    assert len(ids) == 4
    for msg_id in ids:
        tr = metrics.trace(msg_id)
        assert set(tr.stamps) == {"produced", "broker_in", "broker_out",
                                  "consumed", "processed"}
        assert set(tr.meta) == {"bytes", "partition"}
    topic = pipe._topic.name
    assert metrics.counter(f"topic.{topic}.wan_delay_s") > 0
