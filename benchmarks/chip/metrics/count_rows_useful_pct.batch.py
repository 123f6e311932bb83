"""Share of the candidate rows counted that are real candidates (%): 100 x
the ``rows`` over the ``padded`` stats of the program's
``repro.mine.count`` spans, summed over the traced window. The rest are
the padding that rounds each level's batch up to its plan bucket."""
from benchmarks.chip import spans


def read(r):
    if r.units == 0 or not spans.program_has_spans():
        return None
    padded = spans.span_stat_sum(r, "repro.mine.count", "padded")
    return 100.0 * spans.span_stat_sum(r, "repro.mine.count", "rows") / padded
