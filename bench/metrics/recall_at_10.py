"""recall_at_10: served top-10 ids against the exact float64 top-10,
over every query row the window answered (fraction)."""


def read(rec):
    return rec["recall"]
