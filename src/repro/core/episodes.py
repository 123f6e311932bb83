"""Serial episodes with inter-event time constraints (paper §II-C, Def. 2).

An N-node serial episode ``A -(l1,h1]-> B -(l2,h2]-> C ...`` pairs N event
types with N-1 half-open inter-event windows: a valid occurrence satisfies
``l_i < t_{i+1} - t_i <= h_i`` for every consecutive pair.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# Exact window arithmetic on f32 times
# ---------------------------------------------------------------------------
# A window test ``lo < t - s <= hi`` must not depend on how ``t - hi``
# rounds: at stream times of ~100 s one f32 ulp is ~8e-6, so a rounded
# threshold admits or drops pairs that sit within an ulp of the window edge,
# and counts drift from the exact FSM oracle. Every engine tests windows
# through the two thresholds below, which are the f32 values on the right
# side of the exact real result (an error-free TwoSum, then one ulp step).


def _round_error(a, b, total):
    """``a + b - total`` exactly, for ``total = fl(a + b)`` (TwoSum)."""
    b_part = total - a
    return (a - (total - b_part)) + (b - b_part)


def _ulp_step(x, up: bool):
    """The f32 next to finite, nonzero ``x`` toward +inf (``up``) or -inf."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    step = jnp.where(x > 0, 1, -1) if up else jnp.where(x > 0, -1, 1)
    return jax.lax.bitcast_convert_type(bits + step, jnp.float32)


def sub_ceil(t, c):
    """Least f32 ``>=`` the exact ``t - c``: ``s >= sub_ceil(t, c)`` iff
    ``t - s <= c`` and ``s < sub_ceil(t, c)`` iff ``t - s > c``, exactly,
    for f32 ``s``. Infinite ``t`` passes through."""
    t = jnp.asarray(t, jnp.float32)
    c = jnp.asarray(c, jnp.float32)
    x = t - c
    err = _round_error(t, -c, x)
    return jnp.where((err > 0) & (x != 0), _ulp_step(x, True), x)


def add_floor(s, c):
    """Greatest f32 ``<=`` the exact ``s + c``: ``t <= add_floor(s, c)`` iff
    ``t - s <= c`` and ``t > add_floor(s, c)`` iff ``t - s > c``, exactly,
    for f32 ``t``."""
    s = jnp.asarray(s, jnp.float32)
    c = jnp.asarray(c, jnp.float32)
    x = s + c
    err = _round_error(s, c, x)
    return jnp.where((err < 0) & (x != 0), _ulp_step(x, False), x)


def in_window(t, s, lo, hi):
    """``lo < t - s <= hi`` exactly (f32 operands, broadcasting)."""
    return (s >= sub_ceil(t, hi)) & (s < sub_ceil(t, lo))


def window_f32(x) -> np.ndarray:
    """Float32 window bounds for float64 ones: the largest f32 ``<= x``.

    For every f32 gap ``d``, ``d <= window_f32(hi)`` iff ``d <= hi`` and
    ``d > window_f32(lo)`` iff ``d > lo`` — the engines' f32 windows decide
    exactly as the float64 bounds an episode was given (round to nearest
    would move a bound like ``1 - 2**-53`` up onto the gap ``1.0``)."""
    x64 = np.asarray(x, np.float64)
    x32 = x64.astype(np.float32)
    down = np.nextafter(x32, np.float32(-np.inf), dtype=np.float32)
    return np.where(x32.astype(np.float64) > x64, down, x32).astype(np.float32)


@dataclasses.dataclass(frozen=True)
class Episode:
    symbols: Tuple[int, ...]           # event-type ids, length N >= 1
    t_low: Tuple[float, ...] = ()      # length N-1, each >= 0
    t_high: Tuple[float, ...] = ()     # length N-1, each > t_low

    def __post_init__(self):
        object.__setattr__(self, "symbols", tuple(int(s) for s in self.symbols))
        object.__setattr__(self, "t_low", tuple(float(x) for x in self.t_low))
        object.__setattr__(self, "t_high", tuple(float(x) for x in self.t_high))
        n = len(self.symbols)
        if n < 1:
            raise ValueError("episode needs >= 1 symbol")
        if len(self.t_low) != n - 1 or len(self.t_high) != n - 1:
            raise ValueError("need N-1 inter-event constraints")
        for lo, hi in zip(self.t_low, self.t_high):
            if lo < 0:
                raise ValueError("t_low must be >= 0 (windows are (low, high])")
            if hi <= lo:
                raise ValueError("t_high must exceed t_low")

    @property
    def n(self) -> int:
        return len(self.symbols)

    @property
    def max_span(self) -> float:
        """Upper bound on (end - start) of any occurrence; halo/segment bound."""
        return float(sum(self.t_high))

    def subepisode(self, start: int, stop: int) -> "Episode":
        return Episode(
            self.symbols[start:stop],
            self.t_low[start : stop - 1],
            self.t_high[start : stop - 1],
        )

    def as_arrays(self):
        return (
            jnp.asarray(self.symbols, jnp.int32),
            jnp.asarray(window_f32(self.t_low)),
            jnp.asarray(window_f32(self.t_high)),
        )

    def __str__(self):
        parts = [str(self.symbols[0])]
        for s, lo, hi in zip(self.symbols[1:], self.t_low, self.t_high):
            parts.append(f"-({lo:g},{hi:g}]->{s}")
        return "".join(parts)


def serial(symbols: Sequence[int], low: float, high: float) -> Episode:
    """Episode with one shared (low, high] window for every gap."""
    n = len(symbols)
    return Episode(tuple(symbols), (low,) * (n - 1), (high,) * (n - 1))


def episodes_from_rows(
    rows, t_low: float, t_high: float
) -> "list[Episode]":
    """Inverse of :func:`episode_batch` for uniform windows.

    ``rows`` is i32[B, N] symbol rows (the miner's array form); every gap
    gets the shared (t_low, t_high] window. N == 1 rows get no windows.
    """
    rows = np.asarray(rows, np.int64)
    if rows.ndim != 2:
        raise ValueError("rows must be [B, N]")
    n = rows.shape[1]
    lo, hi = (t_low,) * (n - 1), (t_high,) * (n - 1)
    return [Episode(tuple(int(s) for s in row), lo, hi) for row in rows]


def episode_batch(episodes: Sequence[Episode]):
    """Pack same-length episodes into dense arrays for vmap counting.

    Returns (symbols [B,N] i32, t_low [B,N-1] f32, t_high [B,N-1] f32).
    """
    ns = {e.n for e in episodes}
    if len(ns) != 1:
        raise ValueError("episode_batch requires equal-length episodes")
    sym = np.asarray([e.symbols for e in episodes], np.int32)
    lo = window_f32([e.t_low for e in episodes]).reshape(len(episodes), -1)
    hi = window_f32([e.t_high for e in episodes]).reshape(len(episodes), -1)
    return jnp.asarray(sym), jnp.asarray(lo), jnp.asarray(hi)
