"""Sequential FSM episode counters — the paper's CPU baseline (§III-A, Fig 3).

Two implementations:

* :func:`count_fsm_numpy` — exact list-based oracle, a direct transcription of
  the algorithm in [Patnaik et al. 2008] as described in the paper. Used as
  the ground-truth reference for every other counter in this repo.

* :func:`count_fsm_scan` — a jittable ``lax.scan`` port with per-symbol ring
  buffers of static size K (sufficient when no more than K events of a symbol
  fall inside one constraint window). This is the "direct port" whose limited
  parallelism motivates the paper's algorithm transformation; it also powers
  the MapConcat baseline's per-segment state machines.

Tie convention (documented in DESIGN.md): "non-overlapped" is strict — the
next occurrence must *start strictly after* the previous occurrence's end
(paper Algorithm 1 uses ``prev_e < s_i``). The FSM therefore only seeds new
first-symbol events with ``t > last_completion_time``.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .episodes import Episode, in_window

NEG = -jnp.inf


def count_fsm_numpy(types, times, episode: Episode, return_occurrences: bool = False):
    """Exact serial FSM count of non-overlapped occurrences (oracle).

    For each symbol j, ``lists[j]`` holds times of symbol-j events that extend
    some partial occurrence. On completing the last symbol the count is
    incremented and the whole data structure cleared (paper Fig 3).
    """
    types = np.asarray(types)
    times = np.asarray(times, np.float64)
    n = episode.n
    sym = episode.symbols
    lo, hi = episode.t_low, episode.t_high
    lists = [[] for _ in range(n)]
    count = 0
    prev_completion = -np.inf
    occs = []
    for e, t in zip(types, times):
        completed = False
        # highest position first so an event cannot chain off itself
        for j in range(n - 1, -1, -1):
            if e != sym[j]:
                continue
            if j == 0 and n == 1:
                if t > prev_completion:
                    count += 1
                    prev_completion = t
                    if return_occurrences:
                        occs.append(t)
                continue
            if j == 0:
                if t > prev_completion:
                    lists[0].append(t)
                continue
            ok = any(lo[j - 1] < t - s <= hi[j - 1] for s in lists[j - 1])
            if not ok:
                continue
            if j == n - 1:
                count += 1
                prev_completion = t
                if return_occurrences:
                    occs.append(t)
                lists = [[] for _ in range(n)]
                completed = True
                break
            lists[j].append(t)
        if completed:
            continue
    if return_occurrences:
        return count, occs
    return count


def count_all_occurrences_numpy(types, times, episode: Episode):
    """Exact *superset* enumeration: every (start, end) pair such that some
    valid occurrence starts at ``start`` and ends at ``end``. Exponential in
    principle; per distinct end we keep only the latest start (the dominance
    argument in core/tracking.py). Oracle for the tracking step."""
    types = np.asarray(types)
    times = np.asarray(times, np.float64)
    n = episode.n
    sym, lo, hi = episode.symbols, episode.t_low, episode.t_high
    per_sym = [times[types == s] for s in sym]
    # level 0: latest start of a chain ending at this symbol-0 event = itself
    cur_times = per_sym[0]
    cur_start = per_sym[0].copy()
    for i in range(n - 1):
        nxt = per_sym[i + 1]
        nstart = np.full(nxt.shape, -np.inf)
        for j, t in enumerate(nxt):
            m = (cur_times >= t - hi[i]) & (cur_times < t - lo[i])
            if m.any():
                nstart[j] = cur_start[m].max()
        keep = nstart > -np.inf
        cur_times, cur_start = nxt[keep], nstart[keep]
    return cur_start, cur_times  # (starts, ends), sorted by end


def greedy_numpy(starts, ends) -> int:
    """Paper Algorithm 1 on a host: intervals sorted by end time."""
    count = 0
    prev_e = -np.inf
    for s, e in zip(starts, ends):
        if prev_e < s:
            prev_e = e
            count += 1
    return count


# ---------------------------------------------------------------------------
# Jittable ring-buffer FSM (direct port; limited parallelism by construction)
# ---------------------------------------------------------------------------


def count_fsm_scan(
    types: jax.Array,
    times: jax.Array,
    episode: Episode,
    ring: int = 8,
    t_start: float = -jnp.inf,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``lax.scan`` FSM. Events with time == +inf are padding and ignored.

    Args:
      ring: static per-symbol buffer size; correct iff no more than ``ring``
        same-symbol events are simultaneously "live" inside one constraint
        window (tests size data accordingly; the numpy oracle has no limit).
      t_start: only occurrences *starting strictly after* this time are
        counted (used by MapConcat segment stitching).

    Returns: (count i32, first_end f32, last_end f32) — first/last completed
      occurrence end times (+/-inf when count == 0), the (a, b) bookkeeping of
      paper Fig 4.
    """
    n = episode.n
    sym, lo, hi = episode.as_arrays()
    types = jnp.asarray(types, jnp.int32)
    times = jnp.asarray(times, jnp.float32)

    bufs0 = jnp.full((n, ring), NEG, jnp.float32)   # times per symbol (ring)
    heads0 = jnp.zeros((n,), jnp.int32)
    carry0 = (bufs0, heads0, jnp.float32(t_start), jnp.int32(0),
              jnp.float32(jnp.inf), jnp.float32(NEG))

    def step(carry, ev):
        bufs, heads, prev_e, count, first_end, last_end = carry
        e, t = ev
        valid = jnp.isfinite(t)

        # completion check (position n-1)
        if n == 1:
            completes = valid & (e == sym[0]) & (t > prev_e)
        else:
            win_ok = (bufs[n - 2] > NEG) & in_window(
                t, bufs[n - 2], lo[n - 2], hi[n - 2])
            completes = valid & (e == sym[n - 1]) & jnp.any(win_ok)

        # non-completing updates for positions 0..n-2 (masked out on completion)
        new_bufs, new_heads = bufs, heads
        for j in range(n - 1):
            if j == 0:
                add = valid & (e == sym[0]) & (t > prev_e)
            else:
                ok = (bufs[j - 1] > NEG) & in_window(
                    t, bufs[j - 1], lo[j - 1], hi[j - 1])
                add = valid & (e == sym[j]) & jnp.any(ok)
            add = add & ~completes
            new_bufs = jnp.where(
                add,
                new_bufs.at[j, new_heads[j]].set(t),
                new_bufs,
            )
            new_heads = jnp.where(
                add, new_heads.at[j].set((new_heads[j] + 1) % ring), new_heads)

        # on completion: clear everything, bump count
        new_bufs = jnp.where(completes, jnp.full_like(bufs, NEG), new_bufs)
        new_heads = jnp.where(completes, jnp.zeros_like(heads), new_heads)
        prev_e = jnp.where(completes, t, prev_e)
        count = count + completes.astype(jnp.int32)
        first_end = jnp.where(completes & (count == 1), t, first_end)
        last_end = jnp.where(completes, t, last_end)
        return (new_bufs, new_heads, prev_e, count, first_end, last_end), None

    (_, _, _, count, first_end, last_end), _ = lax.scan(step, carry0, (types, times))
    return count, first_end, last_end
