"""Host time per step in the session pool's ``repro.pool.absorb`` spans
(ms): coalescing the chunks, per-type bincounts, cap growth, the
chunk matrix, the batched index scatter's dispatch, the per-session
buffers and suffix sizing."""
from benchmarks.chip import pool_spans


def read(r):
    return pool_spans.span_ms(r, "repro.pool.absorb")
