"""T_NS split-scan kernel (``kernels/split_scan``) device time per
training job, mean over the chips. In a training job the only Pallas
kernels are T_GR (named ``level_histograms``) and T_NS, so T_NS is every
other ``custom-call``; the kernel carries no name of its own yet."""


def read(rec):
    tr, jobs = rec.trace, len(rec.driver.jobs)
    if tr is None or not jobs:
        return None
    s = tr.op_seconds(lambda o: o.kernel and not o.name.startswith("level_histograms"))
    return 1e3 * s / tr.n_devices / jobs if s > 0 else None
