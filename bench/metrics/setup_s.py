"""setup_s: process start to the first due request, host clock (s)."""


def read(rec):
    return rec["setup_s"]
