"""What decides ``correct``: a whole run of each cell on the CPU at a small
size, sound and with the timed path broken underneath, and each
configuration's control against its reference."""
import json

import numpy as np
import pytest

from benchlib import harness, spec
from benchlib.pool import make_pool
from bench_helpers import CPU_DEVICE

CELLS = {"kmeans-paper": "kmeans-paper.poisson",
         "isoforest-paper": "isoforest-paper.saturate"}


def _run(root, config, seed=2 ** 33 + 1):
    return harness.run_workload(CELLS[config], seed, 2.0, False,
                                t_process=0.0, root=root,
                                device=CPU_DEVICE)


def _failing(result):
    return {k for k, c in result["checks"].items()
            if c["value"] > c["limit"]}


@pytest.mark.parametrize("config", sorted(CELLS))
def test_sound_run_is_correct(small_tree, config):
    r = _run(small_tree, config)
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "checks"


def test_backlog_left_at_the_close_is_not_lost(small_tree):
    mix_path = (small_tree / "bench" / "traffic"
                / "isoforest-paper.saturate.json")
    mix = json.loads(mix_path.read_text())
    mix["rate_hz"] = 1500.0          # far past what the CPU can serve
    mix_path.write_text(json.dumps(mix))
    r = _run(small_tree, "isoforest-paper")
    # fewer processed in the window than were due: a backlog was left
    assert r["metrics"]["msgs_per_s"]["value"] * 2.0 < r["attempted"]
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0


# -- faults planted in the program ------------------------------------------


def _state_unchanged(monkeypatch):
    from repro.ml import IsolationForest, KMeans
    step = KMeans.assign_update
    monkeypatch.setattr(KMeans, "assign_update", lambda self, state, pts: (
        state, *step(self, state, pts)[1:]))
    fit, first = IsolationForest.fit, {}
    monkeypatch.setattr(IsolationForest, "fit", lambda self, pts: first.setdefault(
        "state", fit(self, pts)))


def _half_batch(monkeypatch):
    from repro.ml import IsolationForest, KMeans
    step, score = KMeans.assign_update, IsolationForest.outlier_scores
    monkeypatch.setattr(KMeans, "assign_update", lambda self, state, pts:
                        step(self, state, pts[: len(pts) // 2]))
    monkeypatch.setattr(IsolationForest, "outlier_scores",
                        lambda self, state, pts:
                        score(self, state, pts[: len(pts) // 2]))


def _answer_altered(monkeypatch):
    from repro.ml import IsolationForest, KMeans
    for cls in (KMeans, IsolationForest):
        make = cls.make_processor

        def altered(self, *a, _make=make, **kw):
            handler, calls = _make(self, *a, **kw), []

            def h(context, data=None):
                out = dict(handler(context, data=data))
                calls.append(1)
                if len(calls) == 3:
                    out["mean_score"] *= 1.0 + 1e-3
                return out
            return h
        monkeypatch.setattr(cls, "make_processor", altered)


def _message_lost(monkeypatch):
    from repro.core.broker import Topic
    produce, n = Topic.produce, []

    def lossy(self, payload, **kw):
        n.append(1)
        if len(n) == 12:                  # inside the window
            return None
        return produce(self, payload, **kw)
    monkeypatch.setattr(Topic, "produce", lossy)


def _published_model_frozen(monkeypatch):
    # the handler keeps scoring with its own model, but every publish
    # after the first stores the first model again
    import jax
    from repro.core import ParameterService
    publish, first = ParameterService.publish, {}

    def frozen(self, name, tree):
        if name not in first:
            first[name] = jax.tree.map(np.array, tree)
        return publish(self, name, first[name])
    monkeypatch.setattr(ParameterService, "publish", frozen)


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "answer_altered": _answer_altered, "message_lost": _message_lost,
          "published_model_frozen": _published_model_frozen}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("config", sorted(CELLS))
def test_broken_timed_path_is_not_correct(small_tree, monkeypatch, config,
                                          fault):
    FAULTS[fault](monkeypatch)
    r = _run(small_tree, config)
    assert r["correct"] is False, r["checks"]
    assert _failing(r)
    if fault == "published_model_frozen":
        assert "model_gap" in _failing(r)


# -- the control: the reference one precision down --------------------------


def _window(cfg, seed=5):
    """A window whose "program" is the reference itself, run on from its
    own models over a seeded call order."""
    data = cfg.data
    pool = make_pool(seed, **data["pool"])
    order = [int(i) for i in
             np.random.default_rng(seed).integers(0, len(pool), 40)]
    free = cfg.reference.replay(data, 77, pool, order, seed, None)
    return harness.Window(cfg=cfg, seed=seed, mseed=77, pool=pool,
                          order=order,
                          served=[free["answers"].get(i)
                                  for i in range(len(order))],
                          published=free["published"])


@pytest.mark.parametrize("config", sorted(CELLS))
def test_control_fails_a_limit(small_tree, config):
    cfg = spec.load_config(spec.load_benchmark(small_tree), config,
                           small_tree)
    w = _window(cfg)
    low = harness.compare(w, control=True)
    assert any(v > cfg.data["limits"][k] for k, v in low.items()), low
    # the reference against itself reads nought
    assert all(v == 0 for v in harness.compare(w).values())


def test_kmeans_replay_starts_each_call_from_the_published_model(
        small_tree):
    cfg = spec.load_config(spec.load_benchmark(small_tree), "kmeans-paper",
                           small_tree)
    w = _window(cfg)
    # a published model nudged at one call shows at that call and the
    # next, which starts from it, by about the nudge, and at no call
    # after: nothing is carried on
    w.published = [dict(p) for p in w.published]
    w.published[20]["centroids"] = w.published[20]["centroids"] * (1 + 1e-3)
    got = harness.compare(w)
    assert got["model_gap"] == pytest.approx(1e-3, rel=1e-2)
    assert got["score_gap"] < 1e-3
