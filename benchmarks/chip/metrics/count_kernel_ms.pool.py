"""Device time of the ``count_batch`` Pallas kernel per step (ms)."""


def read(r):
    if r.units == 0:
        return None
    return 1e3 * r.kernel_s() / r.units
