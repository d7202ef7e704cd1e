"""Device idle share over the traced window of a training cell, mean over
the cell's chips: 1 - busy union / window."""


def read(rec):
    tr = rec.trace
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - sum(tr.busy_s) / len(tr.busy_s) / tr.window_s)
