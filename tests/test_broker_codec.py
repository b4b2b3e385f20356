"""The broker's message codec: an array's ``.npy`` bytes written directly
are the bytes ``np.save`` writes, every other payload encodes as before,
and ``Topic.produce`` counts the messages the direct path encoded."""
import io
import itertools
import pickle

import numpy as np
import pytest

from repro.core import (Broker, ComputeResource, ContinuumPipeline,
                        MetricsRegistry, PilotManager, StageSpec)
from repro.core.broker import _deserialize, _encode, _serialize

DTYPES = {
    "float64": np.dtype(np.float64),
    "float32": np.dtype(np.float32),
    "int8": np.dtype(np.int8),
    "bool": np.dtype(np.bool_),
    "complex128": np.dtype(np.complex128),
    "big-endian-f8": np.dtype(">f8"),
    "structured": np.dtype([("id", "<i4"), ("x", ">f8"), ("v", "<f4", (2,))]),
}


def _values(dtype, n):
    if dtype.names:
        out = np.zeros(n, dtype)
        for name in dtype.names:
            out[name] = (np.arange(n) % 7 - 3).reshape(
                (n,) + (1,) * len(dtype[name].shape))
        return out
    return (np.arange(n) % 7 - 3).astype(dtype)


# name -> (array from a dtype, whether the direct path encodes it)
LAYOUTS = {
    "0-d": (lambda d: _values(d, 1).reshape(()), True),
    "zero-size": (lambda d: _values(d, 0).reshape(0, 3), True),
    "1-D": (lambda d: _values(d, 5), True),
    "3-D C": (lambda d: _values(d, 24).reshape(2, 3, 4), True),
    "3-D F": (lambda d: np.asfortranarray(_values(d, 24).reshape(2, 3, 4)),
              True),
    "strided": (lambda d: _values(d, 48).reshape(2, 6, 4)[:, ::2], False),
}


def _np_save(a):
    buf = io.BytesIO()
    np.save(buf, a, allow_pickle=False)
    return buf.getvalue()


@pytest.mark.parametrize("dtype,layout",
                         list(itertools.product(DTYPES, LAYOUTS)))
def test_array_encodes_as_np_save_and_round_trips(dtype, layout):
    make, direct = LAYOUTS[layout]
    a = make(DTYPES[dtype])
    if layout == "3-D F":
        assert a.flags.f_contiguous and not a.flags.c_contiguous
    raw, was_direct = _encode(a)
    assert was_direct == direct
    assert raw == _serialize(a) == _np_save(a)
    got = _deserialize(raw)
    assert got.dtype == a.dtype and got.shape == a.shape
    assert np.array_equal(got, a)


def test_object_array_still_raises_as_np_save_does():
    a = np.array([{"k": 1}, None], dtype=object)
    with pytest.raises(ValueError, match="Object arrays cannot be saved"):
        _serialize(a)


def test_bytes_and_dict_payloads_encode_as_before():
    assert _encode(b"\x01raw") == (b"\x01raw", False)
    d = {"mean": 1.5, "n": 3}
    raw, direct = _encode(d)
    assert raw == pickle.dumps(d) and not direct
    assert _deserialize(raw) == d
    assert _deserialize(_serialize(b"\x01raw")) == b"\x01raw"


def test_produce_counts_arrays_encoded_directly():
    metrics = MetricsRegistry()
    t = Broker(metrics=metrics).create_topic("t")
    t.produce(np.zeros((10, 32)))
    assert metrics.counter("topic.t.arrays_encoded_direct") == 1
    t.produce({"mean": 0.0})
    assert metrics.counter("topic.t.arrays_encoded_direct") == 1
    assert metrics.counter("topic.t.msgs_in") == 2


class _RecordingBroker(Broker):
    def __init__(self, **kw):
        super().__init__(**kw)
        self.created = []

    def create_topic(self, name, **kw):
        self.created.append(super().create_topic(name, **kw))
        return self.created[-1]


def test_every_source_message_is_encoded_directly_and_no_forwarded_dict():
    """Sources hand out messages of a C-contiguous float64 pool, as the
    benchmark's sources do; the middle hop forwards a dict."""
    metrics = MetricsRegistry()
    broker = _RecordingBroker(metrics=metrics)
    pool = np.ascontiguousarray(
        np.random.default_rng(0).standard_normal((12, 50, 4)))
    handed = itertools.count()
    mgr = PilotManager(devices=())

    def pilot():
        return mgr.submit_pilot(ComputeResource(tier="cloud", n_workers=2))

    pipe = ContinuumPipeline(stages=[
        StageSpec("sense", lambda ctx: pool[next(handed) % len(pool)],
                  pilot=pilot()),
        StageSpec("summarise",
                  lambda ctx, data=None: {"mean": float(data.mean())},
                  pilot=pilot()),
        StageSpec("process_cloud", lambda ctx, data=None: data["mean"],
                  pilot=pilot()),
    ], metrics=metrics, broker=broker)
    res = pipe.run(n_messages=12, timeout_s=30)
    assert res.n_processed == 12
    first, hop = (t.name for t in broker.created)
    assert metrics.counter(f"topic.{first}.msgs_in") == 12
    assert metrics.counter(f"topic.{first}.arrays_encoded_direct") == 12
    assert metrics.counter(f"topic.{hop}.msgs_in") == 12
    assert metrics.counter(f"topic.{hop}.arrays_encoded_direct") == 0
