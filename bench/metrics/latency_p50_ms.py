"""latency_p50_ms: due time to `processed` stamp, median over every
message due in the window (host clock)."""
from benchlib.numbers import percentile


def read(run):
    return percentile(run.latencies_ms(), 50)
