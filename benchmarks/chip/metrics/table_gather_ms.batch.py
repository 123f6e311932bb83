"""Device time per recording of the operations under the named scope
``table_gather`` (ms): the ``table[symbols]`` gathers that build each
level's ``[B, N, cap]`` rows, in the plan cache's programs (not the eager
index build's operations of the same name), outside the ``count_batch``
kernel."""
from benchmarks.chip import spans


def read(r):
    return spans.scope_ms(r, "table_gather")
