"""The bytes the count kernel needs for one session's step in the pool.

After a chunk, a session's candidates at level ``N >= 2`` are the join of
its frequent episodes at ``N - 1`` (``reference.join``, in the program's
order). A candidate that was not among the session's candidates of that
level at the previous step is new: counting it needs its types' events
over the whole history. A candidate counted at the previous step is
carried: its greedy state at the previous step's end stands, and the
occurrences that can end in the new chunk start no earlier than the
chunk's first event less ``(N - 1) * t_high``, so it needs only its types'
events from there. Each candidate also writes one 4-byte count
(``roofline.count_bytes``). Defined from the answers and the streams, not
from what the program happens to gather.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from benchmarks.chip import reference, roofline


def candidates(result: Optional[dict], level: int, max_candidates: int):
    """The candidates of ``level`` that a per-level ``result`` counted."""
    if result is None or level - 1 not in result:
        return []
    frontier = [tuple(int(x) for x in row)
                for row in result[level - 1].symbols]
    return reference.join(frontier, level, max_candidates)


def step_bytes(result: dict, previous: Optional[dict], types, times,
               chunk_start: float, *, t_high: float, n_types: int,
               max_candidates: int) -> int:
    """Bytes for the step that produced ``result`` from ``previous`` (the
    session's result at the step before; None for its first step), over
    its stream so far (``types``, ``times``) whose newest chunk starts at
    ``chunk_start``."""
    types = np.asarray(types)
    times = np.asarray(times, np.float64)
    whole = np.bincount(types, minlength=n_types)
    total = 0
    for level in sorted(result):
        if level < 2:
            continue
        rows = candidates(result, level, max_candidates)
        before = set(candidates(previous, level, max_candidates))
        tail = np.bincount(types[times >= chunk_start - (level - 1) * t_high],
                           minlength=n_types)
        total += roofline.count_bytes([r for r in rows if r not in before],
                                      whole)
        total += roofline.count_bytes([r for r in rows if r in before], tail)
    return total
