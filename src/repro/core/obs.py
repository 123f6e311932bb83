"""The miner's host spans in the JAX profiler's trace.

``span(name, **stats)`` is a ``jax.profiler.TraceAnnotation`` named
``repro.<name>`` whose keyword arguments become the trace event's stats.
It records nothing unless a profiler trace is active
(``jax.profiler.trace``); an untraced call pays one annotation enter and
exit per span. The batch miners emit ``repro.mine`` around each call and,
inside it, ``repro.mine.index``, ``repro.mine.join``, ``repro.mine.count``
and ``repro.mine.sync`` (see ``core/mining.py``); the session pool emits
``repro.pool.flush`` around each flush and, inside it, ``repro.pool.absorb``
and per level ``repro.pool.join``, ``.count``, ``.sync`` and ``.carry``
(see ``core/serving.py``). ``SPANS`` lists every span name the program
emits. The count path's device work carries the named scopes
``table_gather`` and ``scan_table``.
"""
from __future__ import annotations

import jax

SPAN_PREFIX = "repro."
SPANS = tuple(SPAN_PREFIX + name for name in (
    "mine", "mine.index", "mine.join", "mine.count", "mine.sync",
    "pool.flush", "pool.absorb", "pool.join", "pool.count", "pool.sync",
    "pool.carry"))


def span(name: str, **stats) -> jax.profiler.TraceAnnotation:
    """A host span ``repro.<name>`` carrying ``stats`` (ints or strings)."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **stats)
