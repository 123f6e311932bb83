"""Backend compiles during the whole measured window (count; should be 0)."""


def read(r):
    return r.compiles
