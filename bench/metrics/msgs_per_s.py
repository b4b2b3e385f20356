"""msgs_per_s: messages processed inside the window over its length
(host clock)."""
from benchlib.numbers import window_rate


def read(run):
    return window_rate([m["processed"] for m in run.messages], run.close,
                       run.seconds)
