"""T_GR kernel (``kernels/gain_ratio``) share of its roofline: the
histogram work the jobs need (``work.tgr``) at the least time, over the
kernel's device time summed over the chips. The kernel is read from the
trace as the ``custom-call`` instructions named ``level_histograms``
(the jitted wrapper's name; the kernel itself carries no name yet)."""
from metrics import work


def is_tgr(op):
    return op.kernel and op.name.startswith("level_histograms")


def read(rec):
    tr, jobs = rec.trace, len(rec.driver.jobs)
    if tr is None or not jobs:
        return None
    kernel_s = tr.op_seconds(is_tgr)
    if kernel_s <= 0:
        return None
    least, _ = work.least_seconds(*work.tgr(rec.shapes), rec.peaks)
    return 100.0 * jobs * least / kernel_s
