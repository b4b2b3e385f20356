"""Discovery of configurations, mixes and metrics by name; the shape of
``BENCHMARK.json``; the command off a TPU."""
import json
import os
import re
import subprocess
import sys

import pytest

from benchlib import harness, spec
from bench_helpers import CPU_DEVICE, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark(ROOT)


def test_every_name_resolves_to_its_files(bench):
    for c in bench["configs"]:
        cfg = spec.load_config(bench, c["name"], ROOT)
        for stem in ("system", "reference", "work"):
            assert cfg.module(stem) is not None
        assert set(c["reduced"]) <= set(cfg.data) | set(
            cfg.data["fleet"])
    for w in bench["workloads"]:
        mix = spec.load_traffic(w["traffic"], ROOT)
        assert set(mix) == {"why", "rate_hz"} and mix["rate_hz"] > 0
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(spec.load_reader(m["name"], ROOT))


def test_names_units_and_moves_keep_to_the_contract(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", []):
            assert w in cells
            moved = [e for e in bench["end_to_end"]
                     if e["name"] == m["moves"]][0]
            assert w in moved.get("workloads", cells)
    for m in bench["end_to_end"]:
        assert 0.0 < m["bound"] <= 0.25
    for w in bench["workloads"]:
        reported = spec.metrics_for(bench, w["name"], traced=False)
        assert "setup_s" in {m["name"] for m in reported}
        assert len(reported) >= 2
        assert spec.metrics_for(bench, w["name"], traced=True)
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}


def test_metrics_for_filters_by_workloads(bench):
    got = {m["name"] for m in spec.metrics_for(
        bench, "isoforest-paper.saturate", traced=False)}
    assert got == {"msgs_per_s", "setup_s"}
    got = {m["name"] for m in spec.metrics_for(
        bench, "kmeans-paper.poisson", traced=True)}
    assert "kmeans_step_roofline" in got
    assert "isoforest_step_roofline" not in got


def test_unknown_names_are_errors(bench):
    with pytest.raises(spec.SpecError):
        spec.workload(bench, "no-such-cell")
    with pytest.raises(spec.SpecError):
        spec.load_config(bench, "no-such-config", ROOT)
    with pytest.raises(spec.SpecError):
        spec.load_traffic("no-such-mix", ROOT)
    with pytest.raises(spec.SpecError):
        spec.load_reader("no_such_metric", ROOT)


def test_a_cell_added_as_new_files_alone_runs(small_tree):
    """A new mix, a new per-layer metric and a new workload entry: no
    file that exists is edited, and the harness runs the new cell."""
    (small_tree / "bench" / "traffic" / "kmeans-paper.slow.json"
     ).write_text(json.dumps({"why": "a slower fleet", "rate_hz": 32.0}))
    (small_tree / "bench" / "metrics" / "queue_wait_p95_ms.py").write_text(
        "from benchlib.numbers import percentile\n\n\n"
        "def read(run):\n"
        "    return percentile([1e3 * (m['consumed'] - m['produced'])\n"
        "                       for m in run.messages], 95)\n")
    path = small_tree / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    bench["workloads"].append({
        "name": "kmeans-paper.slow", "config": "kmeans-paper",
        "traffic": "kmeans-paper.slow", "chips": 1,
        "why": "a fleet at a lower rate"})
    for m in bench["end_to_end"]:
        if m["name"] == "latency_p50_ms":
            m["workloads"].append("kmeans-paper.slow")
    bench["per_layer"].append({
        "name": "queue_wait_p95_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "broker",
        "moves": "latency_p50_ms", "workloads": ["kmeans-paper.slow"]})
    path.write_text(json.dumps(bench))

    untraced = harness.run_workload(
        "kmeans-paper.slow", 11, 2.0, False, t_process=0.0,
        root=small_tree, device=CPU_DEVICE)
    assert untraced["correct"] is True
    assert set(untraced["metrics"]) == {"latency_p50_ms", "setup_s"}
    assert untraced["attempted"] == 64
    traced = harness.run_workload(
        "kmeans-paper.slow", 12, 2.0, True, t_process=0.0,
        root=small_tree, device=CPU_DEVICE)
    assert set(traced["metrics"]) == {"queue_wait_p95_ms"}
    assert list(traced)[-1] == "checks"


@pytest.mark.parametrize("only_bench", [False, True],
                         ids=["checkout", "bench-files-only"])
def test_command_off_tpu_exits_nonzero_with_no_result(tmp_path, only_bench):
    cwd = ROOT
    if only_bench:
        import shutil
        shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
        for p in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
            shutil.copytree(ROOT / p, tmp_path / p,
                            ignore=shutil.ignore_patterns("__pycache__"))
        cwd = tmp_path
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "kmeans-paper.poisson", "--seed", str(2 ** 33), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "TPU" in proc.stderr
