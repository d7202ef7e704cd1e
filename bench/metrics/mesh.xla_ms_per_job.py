"""Mesh trainer (``core/distributed``) device time per job outside the
kernels and the collectives, mean over the chips: route, write, the
tree walk of OOB weighting and dimension reduction's gain ratios. The
trainer's program is the module that runs both the T_GR kernel
(``custom-call`` named ``level_histograms``) and a collective; the mesh
counterpart of ``engine.xla_ms_per_job``, whose module the mesh does
not run."""


def read(rec):
    tr, jobs = rec.trace, len(rec.driver.jobs)
    if tr is None or not jobs:
        return None
    tgr = {o.module for o in tr.ops if o.kernel and o.name.startswith("level_histograms")}
    mods = tgr & {o.module for o in tr.ops if o.collective}
    s = tr.op_seconds(lambda o: o.module in mods and not o.kernel and not o.collective)
    return 1e3 * s / tr.n_devices / jobs if s > 0 else None
