"""Host time per recording in the program's ``repro.mine.count`` spans
(ms): every level's count launch (adapter glue, plan lookup, executable
launch) and keep mask."""
from benchmarks.chip import spans


def read(r):
    return spans.span_ms(r, "repro.mine.count")
