"""build_s: QuIVerIndex.build up to block_until_ready, host clock (s)."""


def read(rec):
    return rec["build_s"]
