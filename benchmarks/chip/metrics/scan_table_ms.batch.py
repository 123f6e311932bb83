"""Device time per recording of the operations under the named scope
``scan_table`` (ms): the fused kernel's window scan tables, the binary
searches over the ``[B, N, cap]`` rows, outside the ``count_batch``
kernel."""
from benchmarks.chip import spans


def read(r):
    return spans.scope_ms(r, "scan_table")
