"""beam.lockstep_share.batch: the useful share of the row-iterations
the beam loop pays for in the window (%): the real rows' hops (sum of
``quiver_nav_hops``) over those hops plus the row-iterations the rows
spend done, waiting for their chunk's slowest row (sum of
``quiver_nav_wait_iterations``).  100 when every row of a chunk needs
as many hops as its slowest."""


def read(rec):
    hops = rec["nav"].get("hops")
    wait = rec["nav"].get("wait_iterations")
    if not hops or not wait or hops["sum"] <= 0:
        return None
    return 100.0 * hops["sum"] / (hops["sum"] + wait["sum"])
