"""Device busy time outside the ``count_batch`` kernel per recording (ms):
the table gather, the type-index build or update, the scan tables, the
pruning."""


def read(r):
    if r.units == 0 or not r.trace.device_ops:
        return None
    return 1e3 * (r.trace.busy_s() - r.kernel_s()) / r.units
