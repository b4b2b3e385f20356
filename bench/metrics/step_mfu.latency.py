"""step_mfu.latency: the configuration's algorithmic FLOPs per message
over the mean `bench.handler` span times the bf16 peak, in percent (host
clock spans), in the latency cells."""
from benchlib.shares import step_mfu


def read(run):
    return step_mfu(run)
