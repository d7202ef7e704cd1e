"""Mesh plane (``core/distributed``) collective time per job, mean over
the chips: all-reduce, all-gather, reduce-scatter, all-to-all and
collective-permute instructions of every program in the window."""


def read(rec):
    tr, jobs = rec.trace, len(rec.driver.jobs)
    if tr is None or not jobs:
        return None
    s = tr.op_seconds(lambda o: o.collective)
    return 1e3 * s / tr.n_devices / jobs if s > 0 else None
