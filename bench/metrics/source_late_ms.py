"""source_late_ms: mean of `produced` stamp minus due time: how late the
edge sources ran, serialization included (program stamps)."""


def read(run):
    return run.mean_span_ms("due", "produced")
