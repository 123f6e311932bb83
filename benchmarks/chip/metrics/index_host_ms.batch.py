"""Host time per recording in the program's ``repro.mine.index`` span
(ms): the type-index build and its padding to the capacity class."""
from benchmarks.chip import spans


def read(r):
    return spans.span_ms(r, "repro.mine.index")
