"""Whole serving path's share of the chip's peak: the work the served
rows need (``work.serve``: binning, traversal and vote, one forest read
per forward pass) at the least time, over the window."""
from metrics import work


def read(rec):
    served = rec.driver.served()
    if not served or rec.window_s <= 0:
        return None
    rows = sum(r.rows for r in served)
    passes = len(rec.driver.groups())
    least, _ = work.least_seconds(*work.serve(rows, passes, rec.shapes), rec.peaks)
    return 100.0 * least / (rec.window_s * rec.chips)
