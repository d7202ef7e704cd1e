"""Share of padded rows in ``PRFService``'s forward passes: each drain's
rows are cut into passes of at most ``max_batch`` and each pass padded
to its power-of-two bucket."""
from harness.drivers import bucket_size


def read(rec):
    t = rec.traffic
    rows = padded = 0
    for total in rec.driver.groups():
        for i in range(0, total, t["max_batch"]):
            n = min(t["max_batch"], total - i)
            b = bucket_size(n, t["min_bucket"], t["max_batch"])
            rows, padded = rows + b, padded + b - n
    return 100.0 * padded / rows if rows else None
