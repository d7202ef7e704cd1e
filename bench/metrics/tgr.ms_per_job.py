"""T_GR kernel device time per training job, mean over the chips: the
``custom-call`` instructions named ``level_histograms``."""


def read(rec):
    tr, jobs = rec.trace, len(rec.driver.jobs)
    if tr is None or not jobs:
        return None
    s = tr.op_seconds(lambda o: o.kernel and o.name.startswith("level_histograms"))
    return 1e3 * s / tr.n_devices / jobs if s > 0 else None
