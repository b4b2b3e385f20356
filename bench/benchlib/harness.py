"""One run of one cell: set-up, the measured window, the check.

Set-up builds the cell's configuration (``bench/configs/<config>/
system.py``) around a seeded message pool, and warms every shape up
with a short closed-loop run of the same pipeline.  The window is one
open-loop ``pipe.run(arrival_plan=...)`` of the program's own pipeline
under its default ``ThreadedExecutor``.  The benchmark supplies only the
arrival plan, the pooled producer, a handler wrapper (pool index, order,
output and a ``bench.handler`` span of every call) and the recording
registry and parameter service of ``benchlib.fleet``.

A message is due at ``T + plan`` where ``T`` is read just before
``pipe.run``; its latency runs from there to its ``processed`` stamp.
The first is due ``LEAD_S`` after ``T``.  The run ends once every due
message is processed, or ``DRAIN_S`` after the window closes: a cell
below the knee has drained by then, and one above it leaves its backlog
unprocessed.  A due message is lost when it never reached the broker, or
when a later message of its partition was processed; an unprocessed one
behind every processed message of its partition is backlog, not lost.

Once the window has closed and the device's peak memory has been read,
the configuration's reference replays the handler calls in the order
the wrapper recorded, each from the model the program published before
it, and the answers and published models are compared, each number
against its limit in ``config.json``; so is the broker's exactly-once
effect.
"""
from __future__ import annotations

import collections
import itertools
import sys
import tempfile
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from benchlib import profile, spec, traffic
from benchlib.pool import fingerprints, make_pool

# the span the wrapper opens around every handler call
HANDLER_SPAN = profile.SPAN_PREFIX + "handler"
# seconds from the start of the window's run to its open, and from its
# close to the end of the run
LEAD_S = 0.25
DRAIN_S = 1.0
# a traced run traces from this share of the window on, for at most
# TRACE_S seconds
TRACE_FROM = 0.25
TRACE_S = 8.0


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_devices(chips: int) -> dict:
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < chips:
        raise NoAccelerator(
            f"the cell needs {chips} TPU chip(s); JAX reports "
            f"{len(devices)} {dev.platform} device(s) ({dev.device_kind})")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def enable_cache() -> Optional[str]:
    """The program's persistent compile cache, keeping every program so
    that a second run of a cell compiles nothing."""
    from repro.compile_cache import enable_compilation_cache
    where = enable_compilation_cache()
    if where is not None:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return where


def model_seed(seed: int) -> int:
    """A 31-bit seed for the model's own generators, drawn from the
    run's seed (which may exceed 32 bits)."""
    return int(np.random.SeedSequence([seed, 7]).generate_state(1)[0] >> 1)


class Probe:
    """The pooled producer and the handler wrapper."""

    def __init__(self, pool: np.ndarray):
        self.pool = pool
        self.index = fingerprints(pool)
        self._next = itertools.count()
        self.handed: List[int] = []
        # (pool index, host start, host end, output) per handler call
        self.calls: List[tuple] = []

    def produce(self, context):
        i = next(self._next) % len(self.pool)     # count(): atomic
        self.handed.append(i)
        return self.pool[i]

    def wrap(self, handler: Callable) -> Callable:
        index, calls = self.index, self.calls

        def bench_handler(context, data=None):
            i = index[np.asarray(data)[0].tobytes()]
            t0 = time.monotonic()
            with jax.profiler.TraceAnnotation(HANDLER_SPAN):
                out = handler(context, data=data)
            calls.append((i, t0, time.monotonic(), out))
            return out

        return bench_handler


class Tracer(threading.Thread):
    """Traces the device from ``start`` to ``stop`` (host clock) on a
    thread of its own, so that a traced run covers a few seconds inside
    the window; ``on`` and ``off`` are when the trace was on."""

    def __init__(self, log_dir: str, start: float, stop: float):
        super().__init__(name="bench-tracer", daemon=True)
        self.log_dir, self.start_at, self.stop_at = log_dir, start, stop
        self.on = self.off = None
        self.start()

    def run(self):
        time.sleep(max(self.start_at - time.monotonic(), 0.0))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self.on = time.monotonic()
        time.sleep(max(self.stop_at - self.on, 0.0))
        self.off = time.monotonic()
        jax.profiler.stop_trace()


class Compiles:
    """Counts compilations and cache reads while armed."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event in self.EVENTS:
            self.count += 1


class Run:
    """What the metric readers read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def latencies_ms(self) -> List[float]:
        return [(m["processed"] - m["due"]) * 1e3
                if m["processed"] is not None else float("inf")
                for m in self.messages]

    def mean_span_ms(self, start: str, end: str) -> Optional[float]:
        vals = [m[end] - m[start] for m in self.messages
                if m[start] is not None and m[end] is not None]
        return 1e3 * sum(vals) / len(vals) if vals else None


def messages(stamps, plan_abs: List[np.ndarray]) -> List[dict]:
    """One record per due message, matched to the plan: a device's
    messages are produced in plan order on its own partition."""
    by_id: Dict[str, dict] = collections.defaultdict(dict)
    for msg_id, event, t, partition in stamps:
        rec = by_id[msg_id]
        rec[event] = t
        if partition is not None:
            rec["partition"] = partition
    per_part: Dict[int, List[dict]] = collections.defaultdict(list)
    for rec in by_id.values():
        if "produced" in rec:
            per_part[rec["partition"]].append(rec)
    out = []
    for d, due in enumerate(plan_abs):
        recs = sorted(per_part.get(d, []), key=lambda r: r["produced"])
        for k, t_due in enumerate(due):
            r = recs[k] if k < len(recs) else {}
            out.append({"due": float(t_due), "partition": d,
                        "produced": r.get("produced"),
                        "consumed": r.get("consumed"),
                        "processed": r.get("processed")})
    return out


def unprocessed(msgs: List[dict]) -> Tuple[int, int]:
    """``(lost, backlog)`` among the due messages: a partition is
    consumed in order, so an unprocessed message is backlog when no later
    message of its partition was processed, and lost when one was or
    when it never reached the broker."""
    lost = backlog = 0
    by_part: Dict[int, List[dict]] = collections.defaultdict(list)
    for m in msgs:
        by_part[m["partition"]].append(m)
    for recs in by_part.values():
        passed = False
        for m in reversed(recs):                  # from the newest
            if m["processed"] is not None:
                passed = True
            elif passed or m["produced"] is None:
                lost += 1
            else:
                backlog += 1
    return lost, backlog


def _peak_bytes() -> Optional[int]:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


class Window:
    """What one measured window left behind: the due messages, the calls
    in the order the handler saw them, every published model, the
    exactly-once counts and, traced, the reduced trace."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def run_window(name: str, seed: int, seconds: float, traced: bool, *,
               root=spec.ROOT, log=sys.stderr) -> Window:
    """Set-up and the window of one run of workload ``name``."""
    bench = spec.load_benchmark(root)
    cell = spec.workload(bench, name)
    cfg = spec.load_config(bench, cell["config"], root)
    mix = spec.load_traffic(cell["traffic"], root)
    data = cfg.data
    compiles = Compiles()

    # -- set-up ----------------------------------------------------------
    t_pool = time.monotonic()
    pool = make_pool(seed, **data["pool"])
    probe = Probe(pool)
    mseed = model_seed(seed)
    system = cfg.system.build(data, mseed, probe)
    try:
        t_warm = time.monotonic()
        warm = system.pipe.run(n_messages=data["warmup_messages"],
                               timeout_s=900.0)
        print(f"bench: set-up: pool and pipeline {t_warm - t_pool:.3f} s, "
              f"warm-up {time.monotonic() - t_warm:.3f} s", file=log,
              flush=True)
        if warm.n_processed != data["warmup_messages"]:
            raise RuntimeError(
                f"warm-up processed {warm.n_processed} of "
                f"{data['warmup_messages']}: "
                f"{system.metrics.events('task_error')[:3]}")
        n_warm = len(probe.calls)
        errors_before = system.metrics.counter("runtime.task_errors")
        dups_before = system.metrics.counter("pipeline.duplicates_dropped")
        system.metrics.log.clear()
        n_dev = data["fleet"]["edge_devices"]
        plan = traffic.arrival_plan(mix["rate_hz"], seconds, seed, n_dev,
                                    LEAD_S)
        w_open, w_close = LEAD_S, LEAD_S + seconds
        handed_before = len(probe.handed)

        trace_dir = tempfile.TemporaryDirectory() if traced else None

        # -- the window ---------------------------------------------------
        compiles.armed = True
        t0 = time.monotonic()
        if traced:
            t_on = t0 + w_open + TRACE_FROM * seconds
            tracer = Tracer(trace_dir.name, t_on,
                            t_on + min(TRACE_S, (1 - TRACE_FROM) * seconds))
        try:
            res = system.pipe.run(arrival_plan=plan,
                                  timeout_s=w_close + DRAIN_S)
        finally:
            if traced:
                tracer.join()
        compiles.armed = False
        peak = _peak_bytes()

        msgs = messages(list(system.metrics.log), [t0 + p for p in plan])
        calls = probe.calls[n_warm:]
        task_errors = (system.metrics.counter("runtime.task_errors")
                       - errors_before)
        dups = (system.metrics.counter("pipeline.duplicates_dropped")
                - dups_before)
        lost, backlog = unprocessed(msgs)
        handed = collections.Counter(probe.handed[handed_before:])
        called = collections.Counter(c[0] for c in calls)
        checks = {
            "lost": (lost, 0),
            # one handler call per processed message
            "calls_vs_processed": (abs(len(calls) - sum(
                1 for m in msgs if m["processed"] is not None)), 0),
            # a call on a message that no source handed out
            "pool_mismatch": (sum((called - handed).values()), 0),
            "task_errors": (int(task_errors), 0),
            "aborted": (len(system.metrics.events("run_aborted")), 0),
            "unpublished": (len(probe.calls)
                            - len(system.params.history), 0),
            "off_device_leaves": (system.params.off_device_leaves, 0),
        }
        print(f"bench: {len(msgs)} due, {res.n_processed} processed, "
              f"{backlog} left as backlog, {int(dups)} duplicates dropped, "
              f"{compiles.count} compiles or cache reads in the window",
              file=log, flush=True)
        published = system.params.history
    finally:
        system.release()
    spans = [(c[1], c[2]) for c in calls]
    summary = None
    if traced:
        summary = _reduce_trace(trace_dir, spans, tracer.on, tracer.off, log)
        trace_dir.cleanup()
    return Window(bench=bench, cfg=cfg, seed=seed, mseed=mseed, pool=pool,
                  order=[c[0] for c in probe.calls],
                  served=[c[3] for c in probe.calls], published=published,
                  messages=msgs, spans=spans, checks=checks, peak=peak,
                  seconds=seconds, open=t0 + w_open, close=t0 + w_close,
                  trace=summary)


def compare(w: Window, control: bool = False) -> dict:
    """The configuration's numbers for this window: the served answers
    and published models against the reference replayed over the same
    calls, each call from the model published before it.  With
    ``control``, the reference one precision down takes the program's
    place."""
    ref_mod, data = w.cfg.reference, w.cfg.data
    args = (data, w.mseed, w.pool, w.order, w.seed, w.published)
    ref = ref_mod.replay(*args)
    if not control:
        return ref_mod.compare(data, w.served, w.published, ref)
    low = ref_mod.replay(*args, control=True)
    served = [low["answers"].get(i) for i in range(len(w.order))]
    return ref_mod.compare(data, served, low["published"], ref)


def run_workload(name: str, seed: int, seconds: float, traced: bool, *,
                 t_process: float, root=spec.ROOT, device: dict,
                 log=sys.stderr) -> dict:
    """One run of workload ``name``; returns the result line's object."""
    w = run_window(name, seed, seconds, traced, root=root, log=log)
    # the check, once the window has closed and the peak is read
    checks = dict(w.checks)
    limits = w.cfg.data["limits"]
    for k, v in compare(w).items():
        checks[k] = (v, limits[k])
    correct = all(v <= lim for v, lim in checks.values())

    run = Run(config=w.cfg.data, work=w.cfg.work, seconds=seconds,
              setup_s=w.open - t_process, open=w.open, close=w.close,
              messages=w.messages, spans=w.spans, trace=w.trace,
              peaks=spec.load_peaks(device["kind"], root))
    entries = spec.metrics_for(w.bench, name, traced)
    result = {
        "correct": bool(correct),
        "attempted": len(w.messages),
        "failed": checks["lost"][0],
        "metrics": spec.read_metrics(entries, run, root),
        "device": dict(device, memory_peak_bytes=w.peak),
    }
    if w.trace is not None:
        result["device"].update(busy_s=w.trace.busy_s,
                                window_s=w.trace.window_s)
        result["breakdown"] = w.trace.breakdown()
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def _reduce_trace(trace_dir, spans, lo_s: float, hi_s: float, log
                  ) -> Optional[profile.TraceSummary]:
    events = profile.load_events(profile.xplane_file(trace_dir.name))
    starts = [e["t"] for e in events if e["name"] == HANDLER_SPAN]
    offset = profile.align(starts, [s for s, _ in spans])
    if offset is None:
        print(f"bench: {len(starts)} handler spans in the trace against "
              f"{len(spans)} calls; the trace is not read", file=log)
        return None
    return profile.TraceSummary(events, lo_s * 1e9 + offset,
                                hi_s * 1e9 + offset)


def print_checks(checks: dict, out=sys.stderr) -> None:
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=out, flush=True)
