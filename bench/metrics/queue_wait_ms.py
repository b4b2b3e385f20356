"""queue_wait_ms: mean `produced` to `consumed` stamps: the time a message
waits in the broker for the cloud consumer (program stamps)."""


def read(run):
    return run.mean_span_ms("produced", "consumed")
