"""A profiler trace reduced to the numbers the metrics read.

``load_events`` reads the ``.xplane.pb`` file that ``jax.profiler``
writes and keeps, as plain records ``{"plane", "line", "name", "t",
"d"}`` (start and duration in nanoseconds from the start of the
profile):

* every event on a device plane (``/device:...``);
* every host event on a thread that ran a benchmark span (``bench.*``,
  the ``TraceAnnotation`` the handler wrapper opens).

``TraceSummary`` reduces those records over one window:

* device busy time: the union of the op intervals on each device's
  ``XLA Ops`` line, averaged over the devices;
* the idle gaps between them, each named by the innermost host event
  that covered its middle on a benchmark thread (``host idle`` when none
  did);
* the device ops that took most time, and per-module counts and times
  from the ``XLA Modules`` line, which the roofline readers match by the
  program's jit names.

The records are plain data, so the reduction is checked on a small
recorded trace without a chip.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_IDLE = "host idle"


def is_device_plane(plane: str) -> bool:
    return plane.startswith("/device:")


def xplane_file(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {found}")
    return found[0]


def load_events(path: str) -> List[dict]:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out: List[dict] = []
    for plane in data.planes:
        device = is_device_plane(plane.name)
        for line in plane.lines:
            evs = [{"plane": plane.name, "line": line.name, "name": e.name,
                    "t": float(e.start_ns), "d": float(e.duration_ns)}
                   for e in line.events]
            if device or any(e["name"].startswith(SPAN_PREFIX)
                             for e in evs):
                out.extend(evs)
    return out


def op_label(op: dict, starts: List[float], modules: List[dict]) -> str:
    """``module/op``: the module the op ran in (its name without the
    program hash; ``modules`` sorted by their ``starts``) and the op's HLO
    name (the text before ``=``)."""
    name = op["name"].split(" = ", 1)[0]
    i = bisect.bisect_right(starts, op["t"]) - 1
    if i >= 0 and op["t"] < modules[i]["t"] + modules[i]["d"]:
        return modules[i]["name"].split("(", 1)[0] + "/" + name
    return name


def union(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals: Iterable[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def align(trace_starts_ns: Sequence[float],
          host_starts_s: Sequence[float]) -> Optional[float]:
    """Offset (ns) that maps a host clock reading ``h`` seconds to trace
    time ``h * 1e9 + offset``, from the benchmark spans seen on both
    sides.  The trace covers part of the run, so it holds a consecutive
    run of the host's spans: the run whose differences from the trace's
    spread least.  ``None`` when the trace holds no span, or more than
    the host recorded."""
    trace = np.sort(np.asarray(trace_starts_ns, np.float64))
    host = np.sort(np.asarray(host_starts_s, np.float64)) * 1e9
    m, n = len(trace), len(host)
    if m == 0 or m > n:
        return None
    best = min(range(n - m + 1),
               key=lambda k: np.ptp(trace - host[k:k + m]))
    return float(np.median(trace - host[best:best + m]))


class TraceSummary:
    """The reduction of one trace over the window ``[lo, hi]`` (trace
    nanoseconds)."""

    def __init__(self, events: List[dict], lo: float, hi: float):
        self.lo, self.hi = lo, hi
        self.window_s = (hi - lo) / 1e9
        mods: Dict[str, List[dict]] = defaultdict(list)
        ops: Dict[str, List[dict]] = defaultdict(list)
        host: List[dict] = []
        for e in events:
            if is_device_plane(e["plane"]):
                if e["line"] == MODULES_LINE:
                    mods[e["plane"]].append(e)
                elif e["line"] == OPS_LINE:
                    ops[e["plane"]].append(e)
            elif e["d"] > 0:
                host.append(e)
        self.modules: Dict[str, List[float]] = defaultdict(list)
        for evs in mods.values():
            evs.sort(key=lambda e: e["t"])
            for e in evs:
                if lo <= e["t"] < hi:
                    self.modules[e["name"]].append(e["d"] / 1e9)
        self.op_time: Dict[str, float] = defaultdict(float)
        busy = {}
        for plane, evs in ops.items():
            starts = [m["t"] for m in mods[plane]]
            for e in evs:
                if lo <= e["t"] < hi:
                    self.op_time[op_label(e, starts, mods[plane])] += \
                        e["d"] / 1e9
            busy[plane] = clip(union((e["t"], e["t"] + e["d"]) for e in evs),
                               lo, hi)
        self.n_devices = len(ops)
        self.busy_s = (sum(sum(e - s for s, e in b) for b in busy.values())
                       / 1e9 / self.n_devices) if busy else 0.0
        self.gaps = self._gaps(busy)
        self._host = host

    @staticmethod
    def _gaps_of(busy: List[Tuple[float, float]], lo: float, hi: float
                 ) -> List[Tuple[float, float]]:
        out, t = [], lo
        for s, e in busy:
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if hi > t:
            out.append((t, hi))
        return out

    def _gaps(self, busy) -> List[Tuple[str, float, float]]:
        return [(plane, s, e) for plane, b in sorted(busy.items())
                for s, e in self._gaps_of(b, self.lo, self.hi)]

    @property
    def idle_share(self) -> Optional[float]:
        """``None`` when the trace holds no device."""
        if not self.n_devices:
            return None
        return 1.0 - self.busy_s / self.window_s

    def module_time(self, pattern: str) -> Tuple[int, float]:
        """``(count, seconds)`` of the device module runs whose name holds
        ``pattern``, started inside the window."""
        runs = [d for name, ds in self.modules.items() if pattern in name
                for d in ds]
        return len(runs), sum(runs)

    def _host_lines(self) -> List[Tuple[List[float], List[dict],
                                        List[float], List[dict]]]:
        if not hasattr(self, "_lines"):
            by_line: Dict[Tuple[str, str], List[dict]] = defaultdict(list)
            for e in self._host:
                by_line[(e["plane"], e["line"])].append(e)
            self._lines = []
            for evs in by_line.values():
                evs.sort(key=lambda e: e["t"])
                spans = [e for e in evs if e["name"].startswith(SPAN_PREFIX)]
                self._lines.append(([e["t"] for e in evs], evs,
                                    [e["t"] for e in spans], spans))
        return self._lines

    @staticmethod
    def _covering(starts, evs, t: float, reach: int) -> Optional[dict]:
        i = bisect.bisect_right(starts, t) - 1
        for j in range(i, max(i - reach, -1), -1):
            if t < evs[j]["t"] + evs[j]["d"]:
                return evs[j]
        return None

    def gap_label(self, t: float, reach: int = 256) -> str:
        """The innermost host event covering ``t`` on a benchmark thread.
        On one thread spans nest, so the covering event that started last
        is the innermost; the search walks back at most ``reach`` events
        and then falls back to the benchmark span around ``t``."""
        best = None
        for starts, evs, span_starts, spans in self._host_lines():
            found = (self._covering(starts, evs, t, reach)
                     or self._covering(span_starts, spans, t, 1))
            if found is not None and (best is None or found["d"] < best["d"]):
                best = found
        return best["name"] if best is not None else HOST_IDLE

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took most time, and idle time by what the
        host was doing, each as ``[[name, seconds], ...]``."""
        ops = sorted(self.op_time.items(), key=lambda kv: -kv[1])[:top]
        idle: Dict[str, float] = defaultdict(float)
        for _, s, e in self.gaps:
            idle[self.gap_label((s + e) / 2.0)] += (e - s) / 1e9
        gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, v] for n, v in ops],
                "idle_gaps": [[n, v] for n, v in gaps]}
