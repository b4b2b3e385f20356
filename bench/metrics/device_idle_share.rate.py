"""device_idle_share.rate: 1 - device busy time / traced window, in
percent (device trace), in the rate cells."""


def read(run):
    share = None if run.trace is None else run.trace.idle_share
    return None if share is None else 100.0 * share
