"""``BENCHMARK.json`` and the files it names, found by name.

A cell names a configuration and a traffic mix; a metric names its
reader.  Each lives in a file of its own:

* ``bench/configs/<config>/config.json``: the deployment (the file that
  ``BENCHMARK.json`` gives for the configuration), with ``system.py``
  (builds the program's pipeline), ``reference.py`` (the plain reference,
  its control and the comparison) and ``work.py`` (operations and bytes
  per message, from shapes) beside it;
* ``bench/traffic/<mix>.json``: the arrival process and its absolute rate;
* ``bench/metrics/<metric>.py``: ``read(run)``, the number or ``None``.

So a later change adds a cell, a configuration or a metric by adding
files and entries, and never by editing a file that is here.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[2]


class SpecError(ValueError):
    """A name that ``BENCHMARK.json`` or the files under ``bench/`` do not
    resolve."""


def load_benchmark(root: Path = ROOT) -> dict:
    path = Path(root) / "BENCHMARK.json"
    with open(path) as f:
        return json.load(f)


def workload(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"no workload {name!r} in BENCHMARK.json; known: "
                    f"{[w['name'] for w in spec['workloads']]}")


def metrics_for(spec: dict, name: str, traced: bool) -> List[dict]:
    """The metrics a run of workload ``name`` reports: the end-to-end
    ones untraced, the per-layer ones traced.  A metric without a
    ``workloads`` list applies to every cell."""
    group = spec["per_layer"] if traced else spec["end_to_end"]
    return [m for m in group
            if "workloads" not in m or name in m["workloads"]]


def _module(path: Path, name: str) -> ModuleType:
    if not path.is_file():
        raise SpecError(f"missing {path}")
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


class Config:
    """One configuration: its data and the modules beside its file."""

    def __init__(self, name: str, path: Path):
        self.name = name
        self.path = path
        self.dir = path.parent
        with open(path) as f:
            self.data: dict = json.load(f)
        self._mods: Dict[str, ModuleType] = {}

    def module(self, stem: str) -> ModuleType:
        if stem not in self._mods:
            self._mods[stem] = _module(
                self.dir / f"{stem}.py",
                f"bench_config_{self.name.replace('-', '_')}_{stem}")
        return self._mods[stem]

    @property
    def system(self) -> ModuleType:
        return self.module("system")

    @property
    def reference(self) -> ModuleType:
        return self.module("reference")

    @property
    def work(self) -> ModuleType:
        return self.module("work")


def load_config(spec: dict, name: str, root: Path = ROOT) -> Config:
    for c in spec["configs"]:
        if c["name"] == name:
            return Config(name, Path(root) / c["file"])
    raise SpecError(f"no configuration {name!r} in BENCHMARK.json")


def load_traffic(name: str, root: Path = ROOT) -> dict:
    path = Path(root) / "bench" / "traffic" / f"{name}.json"
    if not path.is_file():
        raise SpecError(f"missing traffic mix {path}")
    with open(path) as f:
        return json.load(f)


def load_reader(name: str, root: Path = ROOT) -> Callable:
    path = Path(root) / "bench" / "metrics" / f"{name}.py"
    mod = _module(path, "bench_metric_" + name.replace(".", "_"))
    return mod.read


def load_peaks(kind: str, root: Path = ROOT) -> dict:
    """The published peaks of a device kind; an unknown kind is an
    error, never a default."""
    with open(Path(root) / "bench" / "peaks.json") as f:
        table = json.load(f)
    if kind not in table:
        raise SpecError(f"no peaks for device kind {kind!r} in "
                        f"bench/peaks.json; known: {sorted(table)}")
    return table[kind]


def read_metrics(entries: List[dict], run, root: Path = ROOT
                 ) -> Dict[str, dict]:
    """``{name: {"value", "unit"}}`` for every entry whose reader found
    something to read; a reader that returns ``None`` leaves its metric
    out."""
    out: Dict[str, dict] = {}
    for m in entries:
        value: Optional[float] = load_reader(m["name"], root)(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
