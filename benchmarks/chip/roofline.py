"""The work the count kernel needs, and the chip's peaks.

A level's count of candidate episodes needs, for each candidate row, the
float32 times of the events of its N types at their real per-type counts,
and one 4-byte count out. That is the least HBM traffic any implementation
of the per-candidate formulation moves; the padded table width and the
bytes of the lowered program are not counted. No vector-unit peak of the
v5e is published, so the roofline share uses the HBM bound alone.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Iterable, Sequence

import numpy as np

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
TIME_BYTES = 4          # one float32 timestamp
COUNT_BYTES = 4         # one int32 count out


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of ``device_kind``; an unknown device raises."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {PEAKS_FILE.name} (known: {sorted(table)})")
    return table[device_kind]


def count_bytes(rows: Iterable[Sequence[int]], type_counts) -> int:
    """Bytes the count of ``rows`` needs over a stream whose per-type event
    counts are ``type_counts``."""
    type_counts = np.asarray(type_counts, np.int64)
    rows = np.asarray(list(rows), np.int64)
    if rows.size == 0:
        return 0
    return int(TIME_BYTES * type_counts[rows].sum()
               + COUNT_BYTES * rows.shape[0])
