"""Device-to-host fetches per recording: the program's ``repro.mine.sync``
spans over the recordings traced (1 + the levels counted)."""
from benchmarks.chip import spans


def read(r):
    if r.units == 0 or not spans.program_has_spans():
        return None
    return len(spans.span_events(r, "repro.mine.sync")) / r.units
