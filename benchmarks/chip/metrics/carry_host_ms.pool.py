"""Host time per step in the session pool's ``repro.pool.carry`` spans
(ms): every level's counts and greedy chain states written back."""
from benchmarks.chip import pool_spans


def read(r):
    return pool_spans.span_ms(r, "repro.pool.carry")
