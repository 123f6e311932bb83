"""Plain reference miner: frequent serial episodes, level by level.

Independent of the program under test (numpy only). Semantics, as stated
in arXiv:0905.2203 §II and the configuration files:

* level 1: an episode is one event type; its count is the number of events
  of that type;
* level N >= 2: candidates are the Apriori join of the frequent (N-1)-node
  episodes (level 2: every ordered pair of frequent types, first-type
  major), cut to ``max_candidates`` in join order;
* the count of a serial episode ``a_0 -> ... -> a_{N-1}`` with one window
  ``(lo, hi]`` per gap is its number of non-overlapped occurrences, as the
  paper's state machine counts them: scanning events in time order, the
  first complete occurrence ends as early as possible, the next one starts
  strictly after it ends, and so on. An occurrence is events of the
  episode's types at strictly increasing times whose consecutive gaps ``d``
  satisfy ``lo < d <= hi``, decided exactly on the float32 timestamps
  (``d`` is computed in float64, where the difference of two float32
  values is exact).

The count is computed as: for every event that can end a chain of the
first ``j+1`` symbols, the latest start over such chains (a range maximum
over the previous symbol's events inside the window), then the greedy
choice of chains by end time whose start is after the last chosen end.
That greedy choice is the state machine's count: after a completion at
time ``c`` the machine restarts on the events after ``c``.

``edges="float32"`` decides each window by a float32 edge rounded to
nearest, ``s >= fl32(t - hi)``, instead of exactly: the float32 arithmetic
one step below the stated float64 window bounds, used as the control that
the comparison must reject.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

Level = Tuple[np.ndarray, np.ndarray, int]   # (rows [F, N], counts [F], n_candidates)
ROW_BLOCK = 2048                              # episodes per greedy block


def per_type_times(types, times, n_types: int) -> List[np.ndarray]:
    """Sorted float64 times of each event type (float32 values, exact)."""
    types = np.asarray(types)
    times = np.asarray(times, np.float32).astype(np.float64)
    order = np.argsort(types, kind="stable")
    edges = np.searchsorted(types[order], np.arange(n_types + 1))
    return [np.sort(times[order[edges[t]:edges[t + 1]]], kind="stable")
            for t in range(n_types)]


def join(frequent: Sequence[tuple], level: int,
         max_candidates: int) -> List[tuple]:
    """The Apriori candidates of ``level`` from the frequent rows of
    ``level - 1`` (in discovery order)."""
    if level == 2:
        firsts = sorted({row[0] for row in frequent})
        return [(a, b) for a in firsts for b in firsts][:max_candidates]
    by_prefix: Dict[tuple, List[tuple]] = {}
    for row in frequent:
        by_prefix.setdefault(row[:-1], []).append(row)
    out: List[tuple] = []
    for alpha in frequent:
        for beta in by_prefix.get(alpha[1:], []):
            out.append(alpha + (beta[-1],))
            if len(out) >= max_candidates:
                return out
    return out


def _within(later, earlier, bound: float, edges: str) -> np.ndarray:
    """``later - earlier <= bound``: exactly (``edges="exact"``: the gap of
    two float32 values is exact in float64), or against the float32 edge
    ``fl32(later - bound)`` (``edges="float32"``, the control)."""
    if edges == "exact":
        return later - earlier <= bound
    edge = later.astype(np.float32) - np.float32(bound)
    return earlier.astype(np.float32) >= edge


def _first_within(prev: np.ndarray, cur: np.ndarray, bound: float,
                  edges: str) -> np.ndarray:
    """For each ``cur[k]``, the first index ``i`` of sorted ``prev`` with
    ``cur[k] - prev[i] <= bound`` (``len(prev)`` if none)."""
    n = prev.size
    idx = np.searchsorted(prev, cur - bound, side="left")
    if n == 0:
        return idx
    while True:   # step down while the previous event is also within
        j = np.maximum(idx - 1, 0)
        down = (idx > 0) & _within(cur, prev[j], bound, edges)
        if not down.any():
            break
        idx = idx - down
    while True:   # step up while this event is not within
        j = np.minimum(idx, n - 1)
        up = (idx < n) & ~_within(cur, prev[j], bound, edges)
        if not up.any():
            break
        idx = idx + up
    return idx


def _range_max(vals: np.ndarray, lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
    """``max(vals[lo[k]:hi[k]])`` for every k, ``-inf`` where empty."""
    out = np.full(lo.shape, -np.inf)
    length = hi - lo
    ok = length > 0
    if not ok.any():
        return out
    table = [vals]
    width = 1
    while 2 * width <= vals.size:
        prev = table[-1]
        table.append(np.maximum(prev[:-width], prev[width:]))
        width *= 2
    span = np.frexp(length[ok].astype(np.float64))[1] - 1   # floor(log2)
    l_ok, h_ok = lo[ok], hi[ok]
    res = np.empty(l_ok.shape)
    for p in np.unique(span):
        m = span == p
        row = table[p]
        res[m] = np.maximum(row[l_ok[m]], row[h_ok[m] - (1 << p)])
    out[ok] = res
    return out


class _Chains:
    """Latest chain start per end event, memoised by episode prefix.

    Window bounds come from one sorted array of all events: for an end
    event at ``t``, the events of type ``a`` with ``t - s > hi`` are those
    of type ``a`` among the events before the first one within ``hi``,
    which a running per-type count gives for every ``a`` at once."""

    def __init__(self, types, times, n_types: int, lo: float, hi: float,
                 edges: str):
        self.by_type = per_type_times(types, times, n_types)
        order = np.argsort(np.asarray(times), kind="stable")
        self.all_times = np.asarray(times, np.float32)[order].astype(
            np.float64)
        onehot = np.zeros((n_types, order.size + 1), np.int32)
        onehot[np.asarray(types)[order], np.arange(1, order.size + 1)] = 1
        self.running = np.cumsum(onehot, axis=1, dtype=np.int32)
        width = max([t.size for t in self.by_type] + [1])
        self.padded = np.full((n_types, width), -np.inf)
        for t, row in enumerate(self.by_type):
            self.padded[t, :row.size] = row
        self.lo, self.hi, self.edges = lo, hi, edges
        self._starts: Dict[tuple, np.ndarray] = {}
        self._bounds: Dict[int, Tuple[np.ndarray, ...]] = {}

    def bounds(self, b: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """For each event of type ``b`` and each type ``a`` (rows): the
        index range of type-``a`` events inside its window, and the latest
        type-``a`` time in it (``-inf`` where empty): the starts of the
        2-node chains ``(a, b)``."""
        got = self._bounds.get(b)
        if got is None:
            cur = self.by_type[b]
            far = _first_within(self.all_times, cur, self.hi, self.edges)
            near = _first_within(self.all_times, cur, self.lo, self.edges)
            first, last = self.running[:, far], self.running[:, near]
            rows = np.arange(self.padded.shape[0])[:, None]
            pair = np.where(last > first,
                            self.padded[rows, np.maximum(last - 1, 0)],
                            -np.inf)
            got = self._bounds[b] = (first, last, pair)
        return got

    def starts(self, prefix: tuple) -> np.ndarray:
        """Latest start of a chain of ``prefix`` ending at each event of
        type ``prefix[-1]`` (``-inf`` where no chain ends there)."""
        if len(prefix) == 1:
            return self.by_type[prefix[0]]
        if len(prefix) == 2:
            return self.bounds(prefix[1])[2][prefix[0]]
        got = self._starts.get(prefix)
        if got is None:
            first, last, _ = self.bounds(prefix[-1])
            a = prefix[-2]
            got = _range_max(self.starts(prefix[:-1]), first[a], last[a])
            self._starts[prefix] = got
        return got


def _greedy(starts: List[np.ndarray], ends: List[np.ndarray]) -> np.ndarray:
    """Non-overlapped greedy count of each row's chains (sorted by end):
    take a chain when its start is after the last taken end."""
    counts = np.zeros(len(starts), np.int64)
    for b0 in range(0, len(starts), ROW_BLOCK):
        block = range(b0, min(b0 + ROW_BLOCK, len(starts)))
        keep = [np.isfinite(starts[b]) for b in block]
        width = max([int(k.sum()) for k in keep] + [0])
        s = np.full((len(block), width), -np.inf)
        e = np.zeros((len(block), width))
        for r, (b, k) in enumerate(zip(block, keep)):
            s[r, :k.sum()] = starts[b][k]
            e[r, :k.sum()] = ends[b][k]
        last = np.full(len(block), -np.inf)
        taken = np.zeros(len(block), np.int64)
        for col in range(width):
            take = s[:, col] > last
            last = np.where(take, e[:, col], last)
            taken += take
        counts[b0:b0 + len(block)] = taken
    return counts


def count(types, times, n_types: int, rows: Sequence[tuple], lo: float,
          hi: float, edges: str = "exact") -> np.ndarray:
    """Non-overlapped counts of the serial episodes ``rows`` (N >= 2)."""
    chains = _Chains(types, times, n_types, lo, hi, edges)
    return _greedy([chains.starts(tuple(r)) for r in rows],
                   [chains.by_type[r[-1]] for r in rows])


def mine(types, times, *, n_types: int, t_low: float, t_high: float,
         threshold: int, level_thresholds: Dict[int, int], max_level: int,
         max_candidates: int, edges: str = "exact") -> Dict[int, Level]:
    """Frequent episodes of every level: ``{level: (rows, counts,
    n_candidates)}``. A level whose frontier is empty is absent."""
    chains = _Chains(types, times, n_types, t_low, t_high, edges)
    sizes = np.array([t.size for t in chains.by_type])
    freq1 = np.nonzero(sizes >= threshold)[0]
    out: Dict[int, Level] = {
        1: (freq1[:, None].astype(np.int32), sizes[freq1], n_types)}
    frontier = [(int(t),) for t in freq1]
    for level in range(2, max_level + 1):
        if not frontier:
            break
        cands = join(frontier, level, max_candidates)
        if not cands:
            break
        cnt = _greedy([chains.starts(c) for c in cands],
                      [chains.by_type[c[-1]] for c in cands])
        thr = level_thresholds.get(level, threshold)
        keep = cnt >= thr
        frontier = [c for c, k in zip(cands, keep) if k]
        out[level] = (np.array(frontier, np.int32).reshape(-1, level),
                      cnt[keep], len(cands))
    return out


def mismatches(got: Dict[int, Level], want: Dict[int, Level]) -> int:
    """How many entries two per-level results disagree on: episodes
    frequent in one and not the other, common episodes whose counts differ,
    and levels whose candidate counts differ. A level with no frequent
    episode and no candidate equals an absent one."""
    empty = (np.zeros((0, 1), np.int32), np.zeros((0,), np.int64), 0)
    bad = 0
    for level in sorted(set(got) | set(want)):
        g_rows, g_cnt, g_cand = got.get(level, empty)
        w_rows, w_cnt, w_cand = want.get(level, empty)
        g = {tuple(int(x) for x in r): int(c) for r, c in zip(g_rows, g_cnt)}
        w = {tuple(int(x) for x in r): int(c) for r, c in zip(w_rows, w_cnt)}
        bad += len(g.keys() ^ w.keys())
        bad += sum(g[k] != w[k] for k in g.keys() & w.keys())
        bad += int(g_cand != w_cand)
    return bad
