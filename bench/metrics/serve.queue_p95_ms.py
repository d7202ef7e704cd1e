"""95th percentile of the time a request waits in ``PRFService``'s queue:
from when it was due to the start of the drain that served it (the
benchmark's own spans)."""
import numpy as np


def read(rec):
    served = rec.driver.served()
    if not served:
        return None
    return float(np.percentile([(r.start - r.due) * 1e3 for r in served], 95))
