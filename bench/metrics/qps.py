"""qps: query rows answered in the window over the window's seconds
(first due request to last answer), host clock."""


def read(rec):
    w = rec["window"]
    if w.seconds <= 0:
        return None
    return w.queries / w.seconds
