"""Device time per recording of the operations under the named scope
``table_gather`` (ms): the ``table[symbols]`` gathers that build each
level's ``[B, N, cap]`` rows, outside the ``count_batch`` kernel."""
from benchmarks.chip import spans


def read(r):
    return spans.scope_ms(r, "table_gather")
