"""beam.us_per_iteration.batch: device time of the beam loop's
``while`` op (which holds its body's ops) in the traced window over the
loop iterations the program counted in it (sum of
``quiver_nav_loop_iterations``), in us.  The search program holds the
only ``while`` op of the cell (a look at a v5e trace)."""

# the loop's op name in the device trace: ``while.<n>``
LOOP = r"^while(\.|$)"


def read(rec):
    t = rec["trace"]
    it = rec["nav"].get("loop_iterations")
    if t is None or not t.devices or not it or it["sum"] <= 0:
        return None
    secs = sum(t.op_seconds(LOOP).values())
    if secs <= 0:
        return None
    return secs / it["sum"] * 1e6
