"""95th percentile of how late the load generator sent a request against
its schedule, so that a starved generator is not read as a fast server."""
import numpy as np


def read(rec):
    sent = [r for r in rec.driver.requests if r.sent is not None]
    if not sent:
        return None
    return float(np.percentile([(r.sent - r.due) * 1e3 for r in sent], 95))
