"""Host time per step in the session pool's ``repro.pool.sync`` spans
(ms): every level's one ``device_get``, waiting for the device
included."""
from benchmarks.chip import pool_spans


def read(r):
    return pool_spans.span_ms(r, "repro.pool.sync")
