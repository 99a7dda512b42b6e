"""bq_distance_roofline.batch: the least time the chip needs to move the
distance kernel's useful bytes, over the kernel's summed device time in
the traced window (%).

Useful bytes per distance evaluation: one 2-bit signature (d/4 bytes)
read and one int32 distance written; evaluations are the program's
``quiver_nav_evals`` count.  Bytes bound it: v5e publishes no peak for
the kernel's integer popcount work.  The kernel's padding (8 query rows
by 128 base rows per tile for one query's ~40 neighbours) is not
useful work, so it shows here as a low share.
"""

# the kernel's op name in the device trace (a look at a v5e trace)
KERNEL = r"bq_distance"


def read(rec):
    t = rec["trace"]
    ev = rec["nav"].get("evals")
    if t is None or not t.devices or not ev or ev["count"] == 0:
        return None
    secs = sum(t.op_seconds(KERNEL).values())
    if secs <= 0:
        return None
    useful = ev["sum"] * (rec["dim"] // 4 + 4)
    return 100.0 * useful / rec["peaks"]["hbm_bytes_per_s"] / secs
