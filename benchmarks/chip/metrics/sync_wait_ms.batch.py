"""Host time per recording in the program's ``repro.mine.sync`` spans
(ms): the device-to-host fetches, the level-1 counts' and each level's
``device_get``, waiting for the device included."""
from benchmarks.chip import spans


def read(r):
    return spans.span_ms(r, "repro.mine.sync")
