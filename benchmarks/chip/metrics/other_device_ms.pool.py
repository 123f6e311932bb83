"""Device busy time outside the ``count_batch`` kernel per step (ms): the
batched index scatter, the slot wipes, the tail gathers and scan tables of
both row families."""


def read(r):
    if r.units == 0 or not r.trace.device_ops:
        return None
    return 1e3 * (r.trace.busy_s() - r.kernel_s()) / r.units
