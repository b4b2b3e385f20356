"""Shares of the chip's peaks, from work counted from shapes.

A configuration's ``work.py`` gives, per program, ``(flops, bytes)`` that
the algorithm needs for one message.  Its least time on the chip is the
larger of ``flops / bf16 peak`` and ``bytes / HBM bandwidth``.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple


def least_time(work: Tuple[float, float], peaks: dict) -> float:
    flops, nbytes = work
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def bound(work: Tuple[float, float], peaks: dict) -> str:
    """Which side bounds the least time: ``compute`` or ``memory``."""
    flops, nbytes = work
    return ("compute" if flops / peaks["bf16_flops_per_s"]
            >= nbytes / peaks["hbm_bytes_per_s"] else "memory")


def roofline(run, programs: Dict[str, Tuple[float, float]]
             ) -> Optional[float]:
    """Percent: the least time of every traced run of the named device
    modules over the device time they took.  ``None`` without a trace or
    when none of them ran."""
    if run.trace is None:
        return None
    least = spent = 0.0
    for pattern, work in programs.items():
        count, seconds = run.trace.module_time(pattern)
        least += count * least_time(work, run.peaks)
        spent += seconds
    return 100.0 * least / spent if spent > 0 else None


def step_mfu(run) -> Optional[float]:
    """Percent: the algorithmic FLOPs of one message over the mean
    handler span times the bf16 peak."""
    if not run.spans:
        return None
    mean_s = sum(e - s for s, e in run.spans) / len(run.spans)
    flops = run.work.message(run.config)[0]
    return 100.0 * flops / (mean_s * run.peaks["bf16_flops_per_s"])
