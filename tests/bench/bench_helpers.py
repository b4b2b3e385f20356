"""Helpers of the benchmark's CPU tests.

The tests steer the harness's internals directly: the command itself has
no CPU switch.  ``small_tree`` copies ``BENCHMARK.json`` and ``bench/``
into a scratch root and shrinks each configuration and mix to a size a
CPU test can hold; the harness then finds them by name as it finds the
real ones.
"""
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

# a CPU-sized stand-in for each configuration: smaller messages and
# forests, a lower rate; every file and name stays as it is
SMALL = {
    "kmeans-paper": {"pool": {"n_messages": 8, "n_points": 1000},
                     "warmup_messages": 4},
    "isoforest-paper": {"pool": {"n_messages": 8, "n_points": 1000},
                        "model": {"n_trees": 8, "psi": 64},
                        "warmup_messages": 2, "check_sample": 8},
}
SMALL_RATE_HZ = {"kmeans-paper.poisson": 40.0,
                 "isoforest-paper.saturate": 12.0}


def _merge(dst: dict, src: dict) -> None:
    for k, v in src.items():
        if isinstance(v, dict):
            _merge(dst.setdefault(k, {}), v)
        else:
            dst[k] = v


def make_small_tree(dst: Path) -> Path:
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name, change in SMALL.items():
        path = dst / "bench" / "configs" / name / "config.json"
        data = json.loads(path.read_text())
        _merge(data, change)
        path.write_text(json.dumps(data))
    for name, rate in SMALL_RATE_HZ.items():
        path = dst / "bench" / "traffic" / f"{name}.json"
        mix = json.loads(path.read_text())
        mix["rate_hz"] = rate
        path.write_text(json.dumps(mix))
    # the arithmetic of the shares runs against the v5e's peaks
    peaks_path = dst / "bench" / "peaks.json"
    peaks = json.loads(peaks_path.read_text())
    peaks["cpu"] = peaks["TPU v5 lite"]
    peaks_path.write_text(json.dumps(peaks))
    return dst


CPU_DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
