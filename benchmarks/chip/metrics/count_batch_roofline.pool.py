"""The ``count_batch`` kernel's share of its HBM roofline in the pool (%):
the least HBM time of the bytes the steps' counts needed
(``pool_bytes.step_bytes``: new rows over the whole history, carried rows
over the chunk's reach) at the chip's published HBM bandwidth, over the
kernel's device time. HBM bound only: no vector-unit peak of the chip is
published."""


def read(r):
    if r.units == 0 or r.unit_bytes <= 0:
        return None
    return 100.0 * r.unit_bytes / r.peak("hbm_bytes_per_s") / r.kernel_s()
