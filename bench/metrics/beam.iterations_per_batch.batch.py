"""beam.iterations_per_batch.batch: the beam loop's iterations per
launched chunk in the window: sum over count of the program's
``quiver_nav_loop_iterations`` histogram, one observation per chunk of
its largest per-row hops (the vmapped while_loop runs until its slowest
row).  The traced run arms an ObsHub, so the program records it."""


def read(rec):
    it = rec["nav"].get("loop_iterations")
    if not it or it["count"] == 0:
        return None
    return it["sum"] / it["count"]
