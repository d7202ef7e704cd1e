"""Growth engine (``core/engine``) device time per job outside the two
kernels: the route, write and expand XLA ops of the jitted grow program
(module ``jit__grow_forest_impl``)."""


def read(rec):
    tr, jobs = rec.trace, len(rec.driver.jobs)
    if tr is None or not jobs:
        return None
    s = tr.op_seconds(lambda o: "grow_forest_impl" in o.module and not o.kernel)
    return 1e3 * s / tr.n_devices / jobs if s > 0 else None
