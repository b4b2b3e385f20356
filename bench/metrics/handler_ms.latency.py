"""handler_ms.latency: mean `consumed` to `processed` stamps in the latency
cells: deserialization, the handler and the stage loop (program stamps)."""


def read(run):
    return run.mean_span_ms("consumed", "processed")
