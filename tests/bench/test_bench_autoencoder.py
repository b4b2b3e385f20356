"""The auto-encoder's configuration and its rate cell: the names resolve,
the cell reports what the contract asks, and a whole run is ``correct``
on the CPU at a small size, and not with the timed path broken
underneath."""
import json

import pytest

from benchlib import harness, spec
from bench_helpers import CPU_DEVICE, ROOT

AE = "autoencoder-paper"
AE_CELL = "autoencoder-paper.saturate"
# a CPU-sized auto-encoder run: 256-point messages, 2 epochs of 8 batches;
# widths, batch size and the rest as published
AE_SMALL = {"pool": {"n_messages": 8, "n_points": 256},
            "model": {"epochs": 2}, "warmup_messages": 2, "check_sample": 2}
AE_RATE_HZ = 8.0


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark(ROOT)


@pytest.fixture
def ae_tree(small_tree):
    path = small_tree / "bench" / "configs" / AE / "config.json"
    data = json.loads(path.read_text())
    for k, v in AE_SMALL.items():
        if isinstance(v, dict):
            data[k].update(v)
        else:
            data[k] = v
    path.write_text(json.dumps(data))
    mix = small_tree / "bench" / "traffic" / f"{AE_CELL}.json"
    mix.write_text(json.dumps(dict(json.loads(mix.read_text()),
                                   rate_hz=AE_RATE_HZ)))
    return small_tree


def test_the_cell_resolves_and_reports_what_it_moves(bench):
    cfg = spec.load_config(bench, AE, ROOT)
    for stem in ("system", "reference", "work"):
        assert cfg.module(stem) is not None
    assert cfg.work.adam_steps(cfg.data) == 100 * 282
    assert spec.workload(bench, AE_CELL)["chips"] == 1
    untraced = {m["name"] for m in spec.metrics_for(bench, AE_CELL, False)}
    assert untraced == {"msgs_per_s", "setup_s"}
    traced = {m["name"] for m in spec.metrics_for(bench, AE_CELL, True)}
    assert traced == {"handler_ms.rate", "step_mfu.rate",
                      "device_idle_share.rate", "ae_train_roofline",
                      "ae_step_us"}


def test_ae_work_from_shapes(bench):
    cfg = spec.load_config(bench, AE, ROOT)
    flops, nbytes = cfg.work.train(cfg.data)
    weights, params = 11_264, 11_552
    assert flops == (6 * weights * 9_000 * 100 + 12 * params * 28_200
                     + 2 * weights * 1_000)
    assert nbytes == 4 * 9_000 * 32 * 100 + 4 * 10_000 * 32 + 2 * (
        12 * params + 4)
    assert cfg.work.score(cfg.data) == (2 * weights * 10_000,
                                        4 * 10_000 * 33)
    peaks = spec.load_peaks("TPU v5 lite", ROOT)
    from benchlib import shares
    assert shares.bound(cfg.work.train(cfg.data), peaks) == "compute"


def _run(root, cell, seed=2 ** 33 + 5, traced=False):
    return harness.run_workload(cell, seed, 2.0, traced, t_process=0.0,
                                root=root, device=CPU_DEVICE)


def test_sound_run_is_correct(ae_tree):
    r = _run(ae_tree, AE_CELL)
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"msgs_per_s", "setup_s"}


def test_ae_step_us_reads_the_trained_modules(ae_tree):
    class Trace:
        def module_time(self, pattern):
            return (2, 0.5) if pattern == "_ae_train" else (0, 0.0)
    cfg = spec.load_config(spec.load_benchmark(ae_tree), AE, ae_tree)
    run = harness.Run(trace=Trace(), config=cfg.data, work=cfg.work)
    reader = spec.load_reader("ae_step_us", ae_tree)
    assert reader(run) == pytest.approx(1e6 * 0.25 / (2 * 8))
    run.trace = None
    assert reader(run) is None


def _one_epoch_fewer(monkeypatch):
    from repro.ml import AutoEncoder
    schedule = AutoEncoder.schedule
    monkeypatch.setattr(AutoEncoder, "schedule", lambda self, n: schedule(
        self, n)._replace(epochs=self.epochs - 1))


def _state_unchanged(monkeypatch):
    from repro.ml import autoencoder
    train = autoencoder._ae_train
    monkeypatch.setattr(autoencoder, "_ae_train",
                        lambda state, *a, **kw: (train(state, *a, **kw),
                                                 state)[1])


def _bf16_products(monkeypatch):
    import jax
    from repro.ml import autoencoder
    forward = autoencoder._forward

    def rounded(t):
        return jax.lax.reduce_precision(t, exponent_bits=8, mantissa_bits=7)
    monkeypatch.setattr(autoencoder, "_forward", lambda params, x, *a, **kw:
                        forward(jax.tree.map(rounded, params), rounded(x),
                                *a, **kw))


FAULTS = {"one_epoch_fewer": _one_epoch_fewer,
          "state_unchanged": _state_unchanged,
          "bf16_products": _bf16_products}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(ae_tree, monkeypatch, fault):
    from repro.ml import autoencoder
    autoencoder._ae_score.clear_cache()       # traced before the fault
    autoencoder._ae_train.clear_cache()
    FAULTS[fault](monkeypatch)
    try:
        r = _run(ae_tree, AE_CELL)
    finally:
        monkeypatch.undo()
        autoencoder._ae_score.clear_cache()
        autoencoder._ae_train.clear_cache()
    assert r["correct"] is False, r["checks"]


def test_control_fails_a_limit(ae_tree):
    """The reference run on from its own states takes the program's place;
    the reference with bf16 operands then fails a limit, and the
    reference against itself reads nought."""
    import numpy as np
    from benchlib.pool import make_pool
    cfg = spec.load_config(spec.load_benchmark(ae_tree), AE, ae_tree)
    pool = make_pool(5, **cfg.data["pool"])
    order = [int(i) for i in np.random.default_rng(5).integers(0, 8, 6)]
    free = cfg.reference.replay(cfg.data, 77, pool, order, 5, None)
    w = harness.Window(cfg=cfg, seed=5, mseed=77, pool=pool, order=order,
                       served=[free["answers"][c] for c in range(6)],
                       published=free["published"])
    low = harness.compare(w, control=True)
    assert any(v > cfg.data["limits"][k] for k, v in low.items()), low
    assert all(v == 0 for v in harness.compare(w).values())
