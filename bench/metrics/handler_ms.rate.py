"""handler_ms.rate: mean `consumed` to `processed` stamps in the rate
cells: deserialization, the handler and the stage loop (program stamps)."""


def read(run):
    return run.mean_span_ms("consumed", "processed")
