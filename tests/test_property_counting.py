"""Hypothesis property tests for the counting system's invariants."""
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="property tests need hypothesis (pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

import strategies as sts
from repro.core import (count_fsm_numpy, count_nonoverlapped, serial)
from repro.core.events import EventStream


@pytest.mark.parametrize("engine", ["dense", "dense_pallas", "dense_pallas_fused"])
@settings(max_examples=40, deadline=None)
@given(s=sts.streams(), ep=sts.episodes())
def test_dense_matches_fsm_oracle(engine, s, ep):
    ep = sts.clamp_episode(ep, s.n_types)
    want = count_fsm_numpy(s.types, s.times, ep)
    # dense_pallas runs the Pallas kernel in interpret mode on CPU
    got = count_nonoverlapped(s, ep, engine=engine)
    assert int(got.count) == want


@settings(max_examples=25, deadline=None)
@given(s=sts.streams(), ep=sts.per_gap_episodes())
def test_per_gap_windows_match_fsm_oracle(s, ep):
    """Heterogeneous (per-gap) constraint windows: dense vs the FSM oracle."""
    ep = sts.clamp_episode(ep, s.n_types)
    want = count_fsm_numpy(s.types, s.times, ep)
    got = count_nonoverlapped(s, ep, engine="dense")
    assert int(got.count) == want


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), n=st.integers(2, 4))
def test_candidate_join_matches_reference(seed, n):
    """Array-based suffix/prefix join == the list-based reference join."""
    from repro.core import MinerConfig
    from repro.core.episodes import Episode
    from repro.core.mining import generate_candidates, generate_candidates_arrays
    rng = np.random.default_rng(seed)
    rows = np.unique(rng.integers(0, 5, size=(12, n)), axis=0).astype(np.int32)
    rng.shuffle(rows)
    cfg = MinerConfig(t_low=0.1, t_high=2.0, threshold=1, max_candidates=4096)
    frequent = [Episode(tuple(int(x) for x in r),
                        (cfg.t_low,) * (n - 1), (cfg.t_high,) * (n - 1))
                for r in rows]
    want = generate_candidates(frequent, n + 1, cfg)
    got = generate_candidates_arrays(rows, n + 1, cfg)
    assert [e.symbols for e in want] == [tuple(int(x) for x in r) for r in got]


@settings(max_examples=25, deadline=None)
@given(sts.streams(), sts.episodes())
def test_count_bounded_by_min_symbol_count(s, ep):
    """Non-overlapped count <= events of the rarest symbol in the episode."""
    ep = serial([x % s.n_types for x in ep.symbols], 0.0, 2.0)
    counts = np.bincount(np.asarray(s.types), minlength=s.n_types)
    bound = min(counts[list(ep.symbols)])
    got = int(count_nonoverlapped(s, ep, engine="dense").count)
    assert got <= bound


@settings(max_examples=25, deadline=None)
@given(sts.streams(), sts.episodes(),
       st.one_of(st.floats(0.1, 10.0),
                 st.sampled_from([0.125, 0.25, 0.5, 2.0, 4.0, 8.0])))
def test_time_scale_invariance(s, ep, scale):
    """Scaling all times and windows by the same factor preserves counts
    exactly when the scaling is exact in float32 (a power of two). Any
    other factor rounds the scaled times and windows, which moves pairs
    across window edges — duplicate timestamps move together, so no fixed
    tolerance holds even for the FSM oracle; there the scaled count must
    equal the oracle's count of the scaled stream (windows given as the
    float32 values the engines hold)."""
    ep = serial([x % s.n_types for x in ep.symbols], 0.25, 2.25)
    base = int(count_nonoverlapped(s, ep, engine="dense").count)
    s2 = EventStream(s.types, (np.asarray(s.times) * scale).astype(np.float32),
                     s.n_types)
    ep2 = serial(list(ep.symbols), float(np.float32(0.25 * scale)),
                 float(np.float32(2.25 * scale)))
    got = int(count_nonoverlapped(s2, ep2, engine="dense").count)
    if float(np.log2(scale)).is_integer():
        assert got == base
    else:
        assert got == count_fsm_numpy(np.asarray(s2.types),
                                      np.asarray(s2.times), ep2)


@settings(max_examples=25, deadline=None)
@given(sts.streams())
def test_anti_monotonicity(s):
    """count(alpha) >= count(alpha extended by one symbol)."""
    ep2 = serial([0, 1], 0.0, 2.0)
    ep3 = serial([0, 1, 0], 0.0, 2.0)
    c2 = int(count_nonoverlapped(s, ep2, engine="dense").count)
    c3 = int(count_nonoverlapped(s, ep3, engine="dense").count)
    assert c2 >= c3


@settings(max_examples=20, deadline=None)
@given(sts.streams(), sts.episodes())
def test_engines_consistent(s, ep):
    ep = serial([x % s.n_types for x in ep.symbols], 0.25, 2.0)
    dense = count_nonoverlapped(s, ep, engine="dense")
    csw = count_nonoverlapped(s, ep, engine="count_scan_write",
                              cap_occ=32 * max(s.n_events, 4), max_window=128)
    if not bool(csw.overflow):
        assert int(dense.count) == int(csw.count)
