"""Device time per recording of the operations under the named scope
``scan_table`` (ms): the fused kernel's window scan tables, counts of the
prev rows' tile boundaries below each next tile's window bounds (a
compare and a sum per side), in the plan cache's programs, outside the
``count_batch`` kernel."""
from benchmarks.chip import spans


def read(r):
    return spans.scope_ms(r, "scan_table")
