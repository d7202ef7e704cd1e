"""Whole-job share of the chips' peak: the work a training job needs
(``work.train_job``: binning, DSI, T_GR, T_NS, routing, OOB) at the
roofline's least time, over the window's wall time times the chips."""
from metrics import work


def read(rec):
    jobs = len(rec.driver.jobs)
    if not jobs or rec.window_s <= 0:
        return None
    least, _ = work.least_seconds(*work.train_job(rec.shapes), rec.peaks)
    return 100.0 * jobs * least / (rec.window_s * rec.chips)
