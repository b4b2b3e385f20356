"""ae_step_us: device time of one sequential Adam step of the
auto-encoder's fit, in microseconds (device trace): the time of the
`_ae_train` device modules over their count and over `work.adam_steps`,
the steps of one message's fit.  Silent without a trace or where no
`_ae_train` module ran."""


def read(run):
    if run.trace is None:
        return None
    count, seconds = run.trace.module_time("_ae_train")
    if count == 0:
        return None
    return 1e6 * seconds / count / run.work.adam_steps(run.config)
