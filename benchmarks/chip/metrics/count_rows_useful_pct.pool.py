"""Share of the rows the pool dispatched that are real candidates (%): 100
x the ``warm`` plus ``cold`` over the ``padded`` stats of the
``repro.pool.count`` spans, summed over the traced window. The rest pad
each family's chunk to its batch class in every slot of the pool."""
from benchmarks.chip import pool_spans


def read(r):
    padded = pool_spans.stat_sum(r, "repro.pool.count", "padded")
    if padded is None:
        return None
    real = (pool_spans.stat_sum(r, "repro.pool.count", "warm")
            + pool_spans.stat_sum(r, "repro.pool.count", "cold"))
    return 100.0 * real / padded
