"""Host time per step in the session pool's ``repro.pool.count`` spans
(ms): every level's warm/cold split, row and carry assembly and
the dispatches of both row families."""
from benchmarks.chip import pool_spans


def read(r):
    return pool_spans.span_ms(r, "repro.pool.count")
