"""Host time per step in the session pool's ``repro.pool.join`` spans
(ms): every session's candidate join, every level."""
from benchmarks.chip import pool_spans


def read(r):
    return pool_spans.span_ms(r, "repro.pool.join")
