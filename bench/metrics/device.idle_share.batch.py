"""device.idle_share.batch: 1 - device busy / traced window (%)."""


def read(rec):
    t = rec["trace"]
    if t is None or not t.devices or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s() / t.window_s)
