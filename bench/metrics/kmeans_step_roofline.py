"""kmeans_step_roofline: the k-means assign+update program's share of its
roofline, in percent (device trace).  Each run of a device module named
after the program's step (`_assign_update`) does one message's step,
whose least time is the larger of its FLOPs over the bf16 peak and its
bytes over the HBM bandwidth (`work.step`, from shapes).  Silent when no
such module runs."""
from benchlib.shares import roofline


def read(run):
    return roofline(run, {"_assign_update": run.work.step(run.config)})
