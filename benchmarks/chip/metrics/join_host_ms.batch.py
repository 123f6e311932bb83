"""Host time per recording in the program's ``repro.mine.join`` spans
(ms): every level's candidate join, padding, upload and window fills."""
from benchmarks.chip import spans


def read(r):
    return spans.span_ms(r, "repro.mine.join")
