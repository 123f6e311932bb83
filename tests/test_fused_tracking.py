"""Fused batched tracking: kernel vs oracle, engine parity, bench gate.

The fused engine's contract (ISSUE 2): count-identical to the numpy FSM
oracle and to the per-level ``dense_pallas`` engine — including
``n_superset`` and the ``overflow`` flag — with all-padding batch rows and
``window_tiles`` truncation flagged, never silent.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.analysis.staticcheck.jaxpr_checks import iter_primitives
from repro.core import count_fsm_numpy, count_nonoverlapped, serial
from repro.core.counting import count_batch_indexed
from repro.core.episodes import episode_batch, sub_ceil
from repro.core.events import EventStream, type_index
from repro.kernels import ops, ref

CAP = 128   # fixed capacity so hypothesis examples share compilations


def _batch_times(rng, b, n, cap, empty_rows=()):
    times = np.full((b, n, cap), np.inf, np.float32)
    for i in range(b):
        for s in range(n):
            if (i, s) in empty_rows:
                continue
            n_real = int(rng.integers(0, cap + 1))
            times[i, s, :n_real] = np.sort(
                rng.uniform(0, 100, n_real)).astype(np.float32)
    return times


# ---------------------------------------------------------------------------
# Kernel level: ops.track_batch vs the quadratic per-level oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cap", [128, 257, 300, 512])   # odd/prime: pad path
@pytest.mark.parametrize("blocks", [(64, 64), (128, 128), (128, 256)])
def test_track_batch_matches_ref(cap, blocks):
    rng = np.random.default_rng(cap)
    b, n = 3, 3
    times = _batch_times(rng, b, n, cap, empty_rows={(1, 1)})
    t_low = rng.uniform(0, 1, (b, n - 1)).astype(np.float32)
    t_high = (t_low + rng.uniform(0.5, 4, (b, n - 1))).astype(np.float32)
    bn, bp = blocks
    starts, nsup, trunc = ops.track_batch(
        jnp.asarray(times), jnp.asarray(t_low), jnp.asarray(t_high),
        block_next=bn, block_prev=bp, interpret=True)
    want, _ = jax.vmap(ref.track_episode_ref)(
        jnp.asarray(times), jnp.asarray(t_low), jnp.asarray(t_high))
    np.testing.assert_array_equal(np.asarray(want), np.asarray(starts))
    assert not np.any(np.asarray(trunc))


def test_track_batch_single_symbol():
    """N=1 episodes: every first-symbol event is an occurrence."""
    rng = np.random.default_rng(0)
    times = _batch_times(rng, 2, 1, 64, empty_rows={(1, 0)})
    starts, nsup, trunc = ops.track_batch(
        jnp.asarray(times), jnp.zeros((2, 0), jnp.float32),
        jnp.zeros((2, 0), jnp.float32), interpret=True)
    finite = np.isfinite(times[:, 0])
    np.testing.assert_array_equal(
        np.asarray(nsup), finite.sum(axis=1).astype(np.int32))
    np.testing.assert_array_equal(
        np.isfinite(np.asarray(starts)) & (np.asarray(starts) > -np.inf),
        finite)


# ---------------------------------------------------------------------------
# Window-tile bounds (vectorized host-side exactness caps)
# ---------------------------------------------------------------------------


def _required_window_tiles_loop(t_prev, t_next, t_high, bn, bp):
    """The pre-vectorization per-tile Python loop, kept as the oracle."""
    cap = t_prev.shape[0]
    nt = cap // bn
    tiles = 1
    for i in range(nt):
        blk = t_next[i * bn:(i + 1) * bn]
        finite = blk[np.isfinite(blk)]
        if finite.size == 0:
            continue
        lo_i = np.searchsorted(t_prev, finite.min() - t_high, side="left")
        hi_i = np.searchsorted(t_prev, finite.max(), side="left")
        tiles = max(tiles, int(hi_i - lo_i) // bp + 2)
    return min(tiles, cap // bp)


@pytest.mark.parametrize("frac", [0.0, 0.3, 1.0])
@pytest.mark.parametrize("blocks", [(64, 64), (128, 64), (128, 128)])
def test_required_window_tiles_matches_loop_oracle(frac, blocks):
    rng = np.random.default_rng(7)
    cap = 512
    bn, bp = blocks
    for t_high in (0.5, 2.0, 50.0):
        t_prev = np.full(cap, np.inf, np.float32)
        t_next = np.full(cap, np.inf, np.float32)
        n_real = int(cap * frac)
        t_prev[:n_real] = np.sort(rng.uniform(0, 100, n_real)).astype(np.float32)
        t_next[:n_real] = np.sort(rng.uniform(0, 100, n_real)).astype(np.float32)
        got = ops.required_window_tiles(t_prev, t_next, t_high, bn, bp)
        want = _required_window_tiles_loop(t_prev, t_next, t_high, bn, bp)
        assert got == want


def test_required_window_tiles_batch_covers_each_level():
    rng = np.random.default_rng(3)
    b, n, cap = 4, 4, 256
    times = _batch_times(rng, b, n, cap, empty_rows={(2, 1)})
    t_high = rng.uniform(0.5, 5, (b, n - 1)).astype(np.float32)
    bn = bp = 64
    got = ops.required_window_tiles_batch(times, t_high, bn, bp)
    per_level = max(
        ops.required_window_tiles(times[i, s], times[i, s + 1],
                                  float(t_high[i, s]), bn, bp)
        for i in range(b) for s in range(n - 1))
    assert got == per_level
    # the bound keeps the fused kernel exact when used as the cap
    starts_cap, _, trunc = ops.track_batch(
        jnp.asarray(times), jnp.zeros((b, n - 1), jnp.float32),
        jnp.asarray(t_high), block_next=bn, block_prev=bp,
        window_tiles=got, interpret=True)
    starts_full, _, _ = ops.track_batch(
        jnp.asarray(times), jnp.zeros((b, n - 1), jnp.float32),
        jnp.asarray(t_high), block_next=bn, block_prev=bp,
        window_tiles=0, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(starts_full), np.asarray(starts_cap))


# ---------------------------------------------------------------------------
# Window scan table: tile-boundary counts vs the binary-search formulation
# ---------------------------------------------------------------------------


def _scan_table_oracle(times, t_high, bn, bp, window_tiles):
    """The scan table as binary searches: each next tile's event span
    ``[lo_idx, hi_idx)`` by ``searchsorted`` over the prev row, then
    ``lo_idx // bp`` and ``ceil(hi_idx / bp)``."""
    b, n, cap = times.shape
    nt, prev_tiles = cap // bn, cap // bp
    tiles = times[..., : nt * bn].reshape(b, n, nt, bn)
    finite = np.isfinite(tiles)
    has = finite[:, 1:, :, 0]
    q_lo = np.asarray(sub_ceil(tiles[:, 1:, :, 0], t_high[..., None]))
    q_hi = np.where(finite, tiles, -np.inf).max(axis=-1)[:, 1:]
    lo_idx = np.zeros((b, n - 1, nt), np.int64)
    hi_idx = np.zeros((b, n - 1, nt), np.int64)
    for i in range(b):
        for s in range(n - 1):
            lo_idx[i, s] = np.searchsorted(times[i, s], q_lo[i, s], "left")
            hi_idx[i, s] = np.searchsorted(times[i, s], q_hi[i, s], "left")
    start = lo_idx // bp
    num = np.where(has, np.maximum(-(-hi_idx // bp) - start, 0), 0)
    truncated = np.zeros(b, bool)
    if 0 < window_tiles < prev_tiles:
        span = np.clip(hi_idx - lo_idx, 0, cap)
        truncated = (span // bp + 2 > window_tiles).any(axis=(1, 2))
        num = np.minimum(num, window_tiles)
    return np.clip(start, 0, max(prev_tiles - 1, 0)), num, truncated


def _scan_table_case(kind, cap, rng):
    b, n = 4, 3
    if kind == "ties":
        # integer times over a short range: runs of duplicates, and tile
        # boundaries equal to the integer window edges of other rows
        times = np.full((b, n, cap), np.inf, np.float32)
        for i in range(b):
            for s in range(n):
                k = int(rng.integers(cap // 2, cap + 1))
                times[i, s, :k] = np.sort(rng.integers(0, cap // 4, k))
        return times, rng.integers(1, 4, (b, n - 1)).astype(np.float32)
    empty = {(1, 1)}
    if kind == "all_inf":
        empty |= {(0, 0), (0, 1), (0, 2), (2, 0), (3, 2)}
    times = _batch_times(rng, b, n, cap, empty_rows=empty)
    if kind == "t_high_0":
        return times, np.zeros((b, n - 1), np.float32)
    if kind == "t_high_inf":   # padding tiles get NaN edges (inf - inf)
        return times, np.full((b, n - 1), np.inf, np.float32)
    return times, rng.choice([0.5, 10.0, 50.0], (b, n - 1)).astype(np.float32)


@pytest.mark.parametrize("window_tiles", [0, 1, 3])
@pytest.mark.parametrize("kind", ["random", "ties", "all_inf", "t_high_0",
                                  "t_high_inf"])
@pytest.mark.parametrize("blocks", [(8, 8), (16, 8), (8, 16), (4, 12),
                                    (128, 128)])
def test_window_scan_table_matches_binary_search(blocks, kind, window_tiles):
    bn, bp = blocks
    cap = 4 * np.lcm(bn, bp)
    rng = np.random.default_rng(bn * 1000 + bp + window_tiles)
    times, t_high = _scan_table_case(kind, cap, rng)
    got = ops.window_scan_table(jnp.asarray(times), jnp.asarray(t_high),
                                bn, bp, window_tiles)
    want = _scan_table_oracle(times, t_high, bn, bp, window_tiles)
    for g, w in zip(got, want):
        assert g.dtype == (jnp.bool_ if w.dtype == bool else jnp.int32)
        np.testing.assert_array_equal(np.asarray(g), w)
    if window_tiles == 1 and kind == "random":
        assert want[2].any()     # the capped path still flags truncation


def test_window_scan_table_default_path_has_no_search():
    """At the chip's shape the default table is compares and sums: no
    loop (``jnp.searchsorted`` traces to a ``scan``, lowered to an HLO
    ``while``) and no ``gather`` anywhere in its jaxpr."""
    def prims(window_tiles):
        closed = jax.make_jaxpr(
            lambda t, h: ops.window_scan_table(t, h, 128, 128, window_tiles))(
            jax.ShapeDtypeStruct((8, 2, 8192), jnp.float32),
            jax.ShapeDtypeStruct((8, 1), jnp.float32))
        return {name for name, _ in iter_primitives(closed.jaxpr)}
    searches = {"while", "scan", "gather"}
    assert not prims(0) & searches
    # the capped path keeps its binary search for the truncation flag
    assert {"scan", "gather"} <= prims(4)


# ---------------------------------------------------------------------------
# Engine level: fused vs FSM oracle vs per-level dense_pallas (property)
# ---------------------------------------------------------------------------


def _indexed_batch(stream, episodes):
    table, counts = type_index(
        stream.types, stream.times, stream.n_types, CAP)
    # the library's float32 windows (episode_batch), which decide every
    # float32 gap exactly as the oracle's float64 bounds do
    sym, lo, hi = episode_batch(episodes)
    return table, counts, sym, lo, hi


def _run_both(stream, episodes, **kw):
    table, counts, sym, lo, hi = _indexed_batch(stream, episodes)
    fused = count_batch_indexed(table, counts, sym, lo, hi,
                                engine="dense_pallas_fused", **kw)
    level = count_batch_indexed(table, counts, sym, lo, hi,
                                engine="dense_pallas", **kw)
    return [np.asarray(x) for x in fused], [np.asarray(x) for x in level]


def _random_case(seed, n_types=4, batch=4):
    """One seeded (stream, equal-length episode batch) parity case."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 120))
    times = np.cumsum(rng.integers(0, 6, n).astype(np.float32) * 0.25)
    types = rng.integers(0, n_types, n).astype(np.int32)
    stream = EventStream(types, times.astype(np.float32), n_types)
    ep_len = int(rng.integers(2, 5))
    lo = float(rng.uniform(0, 1))
    hi = lo + float(rng.uniform(0.3, 4))
    episodes = [serial(rng.integers(0, n_types, ep_len).tolist(), lo, hi)
                for _ in range(batch)]
    return stream, episodes


def _check_fused_parity(case):
    """Fused == FSM oracle == per-level dense_pallas on counts + n_superset."""
    stream, episodes = case
    (cf, nf, of), (cl, nl, ol) = _run_both(stream, episodes)
    assert not of.any() and not ol.any()
    np.testing.assert_array_equal(cf, cl)
    np.testing.assert_array_equal(nf, nl)
    for e, got in zip(episodes, cf):
        assert int(got) == count_fsm_numpy(stream.types, stream.times, e)


def _check_truncation_parity(case, wt):
    """Truncation caps: the two Pallas engines flag the same episodes, and
    unflagged episodes keep exact counts."""
    stream, episodes = case
    (cf, nf, of), (cl, nl, ol) = _run_both(
        stream, episodes, window_tiles=wt, block_next=32, block_prev=32)
    np.testing.assert_array_equal(of, ol)
    for e, got, flagged in zip(episodes, cf, of):
        if not flagged:
            assert int(got) == count_fsm_numpy(stream.types, stream.times, e)


@pytest.mark.parametrize("seed", range(12))
def test_fused_engine_matches_fsm_oracle_and_dense_pallas(seed):
    _check_fused_parity(_random_case(seed))


@pytest.mark.parametrize("batch", [9, 20])
def test_fused_engine_interpret_chunked_batches(batch):
    """Batches above the interpret-mode chunk size (8) take the lax.map
    path, including ragged tails padded with all-inf rows."""
    _check_fused_parity(_random_case(42, batch=batch))


@pytest.mark.parametrize("seed,wt", [(0, 1), (1, 2), (2, 4), (3, 1), (4, 3)])
def test_fused_overflow_flag_matches_dense_pallas(seed, wt):
    _check_truncation_parity(_random_case(seed + 100), wt)


try:  # hypothesis widens the seeded parity checks when available
    from hypothesis import given, settings, strategies as st
    import strategies as sts  # the shared generators (tests/strategies.py)
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:

    @settings(max_examples=25, deadline=None)
    @given(case=sts.stream_and_batch())
    def test_fused_parity_property(case):
        _check_fused_parity(case)

    @settings(max_examples=10, deadline=None)
    @given(case=sts.stream_and_batch(), wt=st.integers(1, 4))
    def test_fused_truncation_parity_property(case, wt):
        _check_truncation_parity(case, wt)


def test_fused_all_padding_batch_row():
    """A symbol with zero events: the whole time row is +inf padding."""
    rng = np.random.default_rng(5)
    n = 80
    types = rng.integers(0, 3, n).astype(np.int32)   # type 3 never occurs
    times = np.cumsum(rng.exponential(0.5, n)).astype(np.float32)
    stream = EventStream(types, times, 4)
    episodes = [serial([0, 3, 1], 0.1, 3.0), serial([0, 1, 2], 0.1, 3.0),
                serial([3, 3, 3], 0.1, 3.0), serial([2, 1, 0], 0.1, 3.0)]
    (cf, nf, of), (cl, nl, ol) = _run_both(stream, episodes)
    assert not of.any()
    np.testing.assert_array_equal(cf, cl)
    np.testing.assert_array_equal(nf, nl)
    assert cf[0] == 0 and cf[2] == 0
    for e, got in zip(episodes, cf):
        assert int(got) == count_fsm_numpy(stream.types, stream.times, e)


def test_fused_truncation_flagged_not_silent():
    """A window covering the whole stream cannot fit one prev tile."""
    rng = np.random.default_rng(9)
    n = 120
    stream = EventStream(rng.integers(0, 2, n).astype(np.int32),
                         np.cumsum(rng.exponential(0.2, n)).astype(np.float32),
                         2)
    episodes = [serial([0, 1], 0.0, 1e6)] * 2
    table, counts, sym, lo, hi = _indexed_batch(stream, episodes)
    for engine in ("dense_pallas_fused", "dense_pallas"):
        _, _, ovf = count_batch_indexed(
            table, counts, sym, lo, hi, engine=engine,
            window_tiles=1, block_next=16, block_prev=16)
        assert np.asarray(ovf).all(), engine


def test_fused_engine_registered_and_in_per_episode_api():
    from repro.core import ENGINES
    assert "dense_pallas_fused" in ENGINES
    rng = np.random.default_rng(2)
    n = 100
    stream = EventStream(rng.integers(0, 4, n).astype(np.int32),
                         np.cumsum(rng.exponential(0.4, n)).astype(np.float32),
                         4)
    ep = serial([0, 1, 2, 3], 0.1, 2.5)
    res = count_nonoverlapped(stream, ep, engine="dense_pallas_fused")
    assert int(res.count) == count_fsm_numpy(stream.types, stream.times, ep)


# ---------------------------------------------------------------------------
# Miner integration + bench compare gate
# ---------------------------------------------------------------------------


def test_mine_fused_engine_and_parallel_schedule_match_dense():
    from repro.core import MinerConfig, mine
    rng = np.random.default_rng(11)
    n = 250
    stream = EventStream(rng.integers(0, 5, n).astype(np.int32),
                         np.cumsum(rng.exponential(0.3, n)).astype(np.float32),
                         5)
    kw = dict(t_low=0.1, t_high=2.0, threshold=12, max_level=3)
    base = mine(stream, MinerConfig(**kw, engine="dense"))
    fused = mine(stream, MinerConfig(**kw, engine="dense_pallas_fused",
                                     parallel_schedule=True))
    assert base.keys() == fused.keys()
    for lvl in base:
        assert base[lvl].episodes == fused[lvl].episodes, lvl
        assert base[lvl].counts == fused[lvl].counts, lvl


def test_bench_compare_entries_gate():
    from benchmarks.run import compare_entries
    cell = dict(episode_len=3, n_events=1024, batch=8, scheduler="scan")
    baseline = [
        {**cell, "engine": "dense", "us_per_call": 100.0},
        {**cell, "engine": "dense_pallas", "us_per_call": 400.0},
    ]
    ok = [
        {**cell, "engine": "dense", "us_per_call": 110.0},
        {**cell, "engine": "dense_pallas", "us_per_call": 900.0},  # not fastest
        {**cell, "engine": "dense_pallas_fused", "us_per_call": 50.0},  # new
    ]
    lines, regressions = compare_entries(baseline, ok)
    assert not regressions
    assert any("(new)" in line for line in lines)
    bad = [{**cell, "engine": "dense", "us_per_call": 130.0}]
    _, regressions = compare_entries(baseline, bad)
    assert len(regressions) == 1 and "dense" in regressions[0]
    # a vanished baseline-fastest engine is an ungated cell, not a pass
    gone = [{**cell, "engine": "dense_pallas", "us_per_call": 380.0}]
    _, regressions = compare_entries(baseline, gone)
    assert len(regressions) == 1 and "missing" in regressions[0]


def test_bench_compare_zero_overlap_is_not_a_pass():
    """A sweep with no cells in common with the baseline (e.g. a smoke run
    against the full checked-in JSON) must not gate vacuously."""
    from benchmarks.run import compare_entries, matched_cells
    baseline = [{"episode_len": 3, "n_events": 1024, "batch": 8,
                 "scheduler": "scan", "engine": "dense", "us_per_call": 100.0}]
    smoke = [{"episode_len": 3, "n_events": 256, "batch": 4,
              "scheduler": "scan", "engine": "dense", "us_per_call": 999.0}]
    _, regressions = compare_entries(baseline, smoke)
    assert regressions and "missing" in regressions[0]
    assert matched_cells(baseline, smoke) == 0
    assert matched_cells(baseline, baseline) == 1
