"""What the readers of the session pool's spans share.

The pool (``core/serving.py``) marks each flush with ``repro.pool.flush``
and, inside it, the leaves ``repro.pool.absorb`` and, per level,
``repro.pool.join``, ``.count`` (stats ``level``, ``warm``, ``cold``,
``padded``), ``.sync`` and ``.carry``. The program lists the span names it
emits in ``repro.core.obs.SPANS``. A program that does not declare a span
(one older than the pool's spans) reads as nothing; a program that
declares it, and a trace that holds units of work but not the span, fails
the run (``spans.span_events``).
"""
from __future__ import annotations

from typing import Optional

from benchmarks.chip import spans


def declared(name: str) -> bool:
    """Whether the program under test declares the span ``name``."""
    if not spans.program_has_spans():
        return False
    from repro.core import obs
    return name in getattr(obs, "SPANS", ())


def span_ms(r, name: str) -> Optional[float]:
    """Milliseconds inside the span ``name`` per step, where declared."""
    return spans.span_ms(r, name) if declared(name) else None


def stat_sum(r, name: str, stat: str) -> Optional[float]:
    """The sum of a declared span's stat over the traced window."""
    if r.units == 0 or not declared(name):
        return None
    return spans.span_stat_sum(r, name, stat)
