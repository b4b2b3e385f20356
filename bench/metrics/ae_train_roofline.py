"""ae_train_roofline: the auto-encoder's fit and score programs' share of
their roofline, in percent (device trace).  Device modules named after the
program's `_ae_train` and `_ae_score` each do one message's fit and
scoring, whose least times come from `work.train` and `work.score` (from
shapes).  Silent when neither runs."""
from benchlib.shares import roofline


def read(run):
    return roofline(run, {"_ae_train": run.work.train(run.config),
                          "_ae_score": run.work.score(run.config)})
