"""Boundary-convention pin: the half-open ``(lo, hi]`` window and the strict
greedy tie, at EXACT boundary timestamps, across every engine and both
schedulers (DESIGN.md §3).

The streaming miner stitches tail occurrences onto cached greedy state, the
sharded miner stitches across shard boundaries — both are exact only if
every engine agrees on what happens when ``t_next - t_prev`` lands exactly
on ``hi`` (inside) or exactly on ``lo`` (outside), and when an occurrence
starts exactly at the previous occurrence's end (not taken: ``s > prev_e``
is strict). These tests pin those conventions with hand-computable streams
on an exactly-representable 0.25 grid, checked against the FSM oracle, so
a future engine (or refactor) that drifts fails loudly here instead of
silently disagreeing at a stitch boundary.
"""
import numpy as np
import pytest

from repro.core import (Episode, EventStream, count_fsm_numpy,
                        count_nonoverlapped, serial)

ENGINES = ("dense", "dense_pallas", "dense_pallas_fused", "count_scan_write",
           "atomic_sort", "flags")
SCHEDULERS = (False, True)   # greedy_scan, greedy_parallel


def _count(stream, ep, engine, parallel):
    res = count_nonoverlapped(
        stream, ep, engine=engine, parallel_schedule=parallel,
        cap_occ=4 * max(1, stream.n_events), max_window=64)
    assert not bool(res.overflow)
    return int(res.count)


def _check_all(stream, ep, expected):
    oracle = count_fsm_numpy(stream.types, stream.times, ep)
    assert oracle == expected, f"oracle disagrees: {oracle} != {expected}"
    for engine in ENGINES:
        for parallel in SCHEDULERS:
            got = _count(stream, ep, engine, parallel)
            assert got == expected, (
                f"{engine}/{'parallel' if parallel else 'scan'}: "
                f"{got} != {expected} for {ep}")


@pytest.mark.parametrize("gap,expected", [
    (1.0, 1),    # t_next - t_prev == hi exactly: INSIDE the half-open window
    (0.25, 0),   # == lo exactly: OUTSIDE (strict lower bound)
    (0.5, 1),    # interior sanity
    (1.25, 0),   # past hi
    (0.0, 0),    # simultaneous events: 0 <= lo is outside for any lo >= 0
])
def test_exact_boundary_gap_two_symbols(gap, expected):
    stream = EventStream(np.array([0, 1], np.int32),
                         np.array([1.0, 1.0 + gap], np.float32), 2)
    _check_all(stream, serial([0, 1], 0.25, 1.0), expected)


def test_exact_boundary_gap_zero_low():
    """lo == 0: a zero gap (duplicate timestamp) is still strictly outside."""
    stream = EventStream(np.array([0, 1], np.int32),
                         np.array([2.0, 2.0], np.float32), 2)
    _check_all(stream, serial([0, 1], 0.0, 1.0), 0)
    stream2 = EventStream(np.array([0, 1], np.int32),
                          np.array([2.0, 3.0], np.float32), 2)
    _check_all(stream2, serial([0, 1], 0.0, 1.0), 1)


def test_exact_boundaries_per_gap_windows():
    """A 3-symbol episode with per-gap windows, each gap at its own exact
    boundary: first at hi_1 (inside), second at lo_2 (outside) and just
    above (inside)."""
    ep = Episode((0, 1, 2), (0.25, 0.5), (1.0, 2.0))
    # gap1 == hi1 == 1.0 (in), gap2 == lo2 == 0.5 (out) -> no occurrence
    s_out = EventStream(np.array([0, 1, 2], np.int32),
                        np.array([0.0, 1.0, 1.5], np.float32), 3)
    _check_all(s_out, ep, 0)
    # gap2 == 0.75 (in) -> one occurrence
    s_in = EventStream(np.array([0, 1, 2], np.int32),
                       np.array([0.0, 1.0, 1.75], np.float32), 3)
    _check_all(s_in, ep, 1)
    # gap2 == hi2 == 2.0 exactly (in)
    s_hi = EventStream(np.array([0, 1, 2], np.int32),
                       np.array([0.0, 1.0, 3.0], np.float32), 3)
    _check_all(s_hi, ep, 1)


def test_greedy_tie_start_equals_prev_end():
    """Two chained occurrences sharing one boundary timestamp: the second
    STARTS exactly at the first's END, so the strict scheduler takes one."""
    # A@0 B@1 (occurrence [0,1]) then A@1 B@2 (occurrence [1,2]):
    # 1 is not > 1, so the second cannot follow the first -> count 1
    stream = EventStream(np.array([0, 1, 0, 1], np.int32),
                         np.array([0.0, 1.0, 1.0, 2.0], np.float32), 2)
    _check_all(stream, serial([0, 1], 0.25, 1.0), 1)
    # pushing the second pair 0.25 later separates them -> count 2
    stream2 = EventStream(np.array([0, 1, 0, 1], np.int32),
                          np.array([0.0, 1.0, 1.25, 2.25], np.float32), 2)
    _check_all(stream2, serial([0, 1], 0.25, 1.0), 2)


@pytest.mark.parametrize("seed", range(6))
def test_boundary_grid_differential(seed):
    """Streams whose every gap is drawn from {0, lo, mid, hi, hi+step} on an
    exact 0.25 grid — every inter-event distance in the stream sits on or
    next to a window boundary — differentially against the FSM oracle."""
    rng = np.random.default_rng(seed)
    lo, hi = 0.25, 1.0
    n, n_types = 24, 3
    gaps = rng.choice(np.array([0.0, lo, 0.5, hi, hi + 0.25], np.float32), n)
    times = np.cumsum(gaps).astype(np.float32)
    types = rng.integers(0, n_types, n).astype(np.int32)
    stream = EventStream(types, times, n_types)
    episodes = [serial([0, 1], lo, hi), serial([1, 0, 2], lo, hi),
                serial([0, 0], lo, hi), serial([2, 1, 0], 0.0, hi)]
    for ep in episodes:
        expected = count_fsm_numpy(types, times, ep)
        for engine in ENGINES:
            for parallel in SCHEDULERS:
                got = _count(stream, ep, engine, parallel)
                assert got == expected, (seed, str(ep), engine, parallel)


# ---------------------------------------------------------------------------
# Window edges off the f32 grid: exact, not rounded
# ---------------------------------------------------------------------------


def _straddling_gap(edge):
    """(s, t, within): f32 times near 100 s whose exact gap ``t - s`` is on
    one side of ``edge`` (``within``: ``t - s <= edge``) while the
    f32-rounded threshold ``t - edge`` lands on the other side of ``s`` —
    a pair a rounded window test misclassifies."""
    s = np.float32(100.0)
    edge32 = np.float32(edge)
    t = np.float32(s + edge32)
    for _ in range(64):
        within = float(t) - float(s) <= float(edge32)
        if within != bool(s >= np.float32(t - edge32)):
            return s, t, within
        t = np.nextafter(t, np.float32(np.inf), dtype=np.float32)
    raise AssertionError("no straddling pair found")


@pytest.mark.parametrize("side", ["hi", "lo"])
def test_window_edges_exact_off_the_f32_grid(side):
    s, t, within = _straddling_gap(0.01)
    stream = EventStream(np.array([0, 1], np.int32),
                         np.array([s, t], np.float32), 2)
    if side == "hi":      # window (0, 0.01]: inside iff within
        ep, expected = serial([0, 1], 0.0, 0.01), int(within)
    else:                 # window (0.01, 1]: inside iff not within
        ep, expected = serial([0, 1], 0.01, 1.0), int(not within)
    _check_all(stream, ep, expected)


def test_float64_window_bound_just_below_a_gap():
    """hi = 1 - 2**-53 rounds to 1.0 as float32 to nearest; the gap 1.0 is
    outside the given window, so every engine must count 0 like the FSM."""
    stream = EventStream(np.array([1, 0], np.int32),
                         np.array([0.0, 1.0], np.float32), 2)
    _check_all(stream, serial([1, 0], 0.0, float(np.nextafter(1.0, 0.0))), 0)


def test_sub_ceil_add_floor_match_float64():
    from repro.core.episodes import add_floor, sub_ceil
    rng = np.random.default_rng(0)
    a = np.concatenate([rng.uniform(-300, 300, 4000),
                        rng.uniform(0, 0.03, 1000)]).astype(np.float32)
    c = np.concatenate([rng.uniform(0, 5, 4000),
                        np.full(1000, 0.01)]).astype(np.float32)
    got_ceil = np.asarray(sub_ceil(a, c))
    got_floor = np.asarray(add_floor(a, c))
    exact_sub = a.astype(np.float64) - c.astype(np.float64)
    exact_add = a.astype(np.float64) + c.astype(np.float64)
    # least f32 >= exact / greatest f32 <= exact
    assert np.all(got_ceil.astype(np.float64) >= exact_sub)
    below = np.nextafter(got_ceil, np.float32(-np.inf), dtype=np.float32)
    assert np.all(below.astype(np.float64) < exact_sub)
    assert np.all(got_floor.astype(np.float64) <= exact_add)
    above = np.nextafter(got_floor, np.float32(np.inf), dtype=np.float32)
    assert np.all(above.astype(np.float64) > exact_add)
    assert np.isinf(np.asarray(sub_ceil(np.float32(np.inf), 0.5)))
