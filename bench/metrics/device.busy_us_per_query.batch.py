"""device.busy_us_per_query.batch: union of device-op intervals in the
traced window over the query rows answered in it (us per query)."""


def read(rec):
    t = rec["trace"]
    q = rec["window"].queries
    if t is None or not t.devices or q == 0:
        return None
    busy = t.busy_s()
    return busy / q * 1e6 if busy > 0 else None
