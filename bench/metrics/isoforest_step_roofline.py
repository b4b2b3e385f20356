"""isoforest_step_roofline: the isolation forest's fit and score programs'
share of their roofline, in percent (device trace).  Device modules named
after the program's `_fit` and `_score` each do one message's forest
build and scoring, whose least times come from `work.fit` and
`work.score` (from shapes).  Silent when neither runs."""
from benchlib.shares import roofline


def read(run):
    return roofline(run, {"_fit": run.work.fit(run.config),
                          "_score": run.work.score(run.config)})
