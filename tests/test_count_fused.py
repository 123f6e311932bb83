"""Single-launch count pipeline: kernel vs host-greedy oracle vs FSM.

The fused engine's ``count_batch`` contract (ISSUE 6): tracking, §IV-D
compaction, and the greedy non-overlap fold all run inside ONE kernel
launch, and the results — counts, carried ``(prev_end, count)`` state,
``n_superset`` — are bit-for-bit identical to every track-then-schedule
engine under BOTH scheduler flags. The carry parity is what keeps the
streaming miner's chain-state stitching exact on the fused path.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import compaction, count_fsm_numpy, serial, tracking
from repro.core.counting import (
    count_batch_dispatch,
    count_batch_indexed,
    count_batch_indexed_stateful,
)
from repro.core.episodes import episode_batch
from repro.core.events import EventStream, type_index

CAP = 128   # fixed capacity so seeded examples share compilations

ENGINES = ("dense", "dense_pallas", "dense_pallas_fused")


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


def _indexed_batch(stream, episodes, cap=CAP):
    table, counts = type_index(
        stream.types, stream.times, stream.n_types, cap)
    # the library's float32 windows (episode_batch), which decide every
    # float32 gap exactly as the oracle's float64 bounds do
    sym, lo, hi = episode_batch(episodes)
    return table, counts, sym, lo, hi


def _dispatch(engine, times, lo, hi, pe, pc, *, parallel_schedule=False,
              chunk=8):
    cfg = tracking.EngineConfig(block_next=32, block_prev=32, chunk=chunk,
                                interpret=True)
    out = count_batch_dispatch(
        engine, jnp.asarray(times), jnp.asarray(lo), jnp.asarray(hi),
        jnp.asarray(pe, jnp.float32), jnp.asarray(pc, jnp.int32), cfg,
        parallel_schedule=parallel_schedule)
    return [np.asarray(x) for x in out]


# ---------------------------------------------------------------------------
# Engine x scheduler differential: fused == track+greedy == FSM oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("parallel_schedule", [False, True])
@pytest.mark.parametrize("seed", range(8))
def test_count_parity_across_engines_and_schedulers(seed, parallel_schedule):
    stream, episodes = _random_case(seed)
    table, counts, sym, lo, hi = _indexed_batch(stream, episodes)
    results = {}
    for engine in ENGINES:
        c, n, o = count_batch_indexed(
            table, counts, sym, lo, hi, engine=engine,
            parallel_schedule=parallel_schedule)
        assert not np.asarray(o).any()
        results[engine] = (np.asarray(c), np.asarray(n))
    for engine in ENGINES[1:]:
        np.testing.assert_array_equal(results[engine][0], results["dense"][0])
        np.testing.assert_array_equal(results[engine][1], results["dense"][1])
    for e, got in zip(episodes, results["dense_pallas_fused"][0]):
        assert int(got) == count_fsm_numpy(stream.types, stream.times, e)


# ---------------------------------------------------------------------------
# Carry-in/carry-out parity: the streaming stitch invariant
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(6))
def test_stateful_carry_parity_kernel_vs_host_greedy(seed):
    """Non-trivial carries in, identical (count, end) carries out."""
    rng = np.random.default_rng(seed)
    b, n = 5, 3
    times = _batch_times(rng, b, n, CAP, empty_rows={(2, 1)})
    lo = rng.uniform(0, 1, (b, n - 1)).astype(np.float32)
    hi = (lo + rng.uniform(0.5, 4, (b, n - 1))).astype(np.float32)
    pe = np.where(rng.random(b) < 0.4, -np.inf,
                  rng.uniform(0, 80, b)).astype(np.float32)
    pc = rng.integers(0, 7, b).astype(np.int32)
    want = _dispatch("dense", times, lo, hi, pe, pc)
    got = _dispatch("dense_pallas_fused", times, lo, hi, pe, pc)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)


def test_streaming_stitch_split_equals_whole():
    """Split the stream at a gap wider than any window: counting the prefix
    fresh, then the suffix seeded with the prefix's carry, must equal one
    whole-stream count — on the fused path AND the track path."""
    rng = np.random.default_rng(11)
    n_types, half = 3, 40
    t_a = np.cumsum(rng.uniform(0.1, 1.0, half)).astype(np.float32)
    t_b = (t_a[-1] + 50.0
           + np.cumsum(rng.uniform(0.1, 1.0, half))).astype(np.float32)
    ty = rng.integers(0, n_types, 2 * half).astype(np.int32)
    whole = EventStream(ty, np.concatenate([t_a, t_b]), n_types)
    prefix = EventStream(ty[:half], t_a, n_types)
    suffix = EventStream(ty[half:], t_b, n_types)
    episodes = [serial(rng.integers(0, n_types, 3).tolist(), 0.0, 2.0)
                for _ in range(4)]
    b = len(episodes)
    fresh = (np.full(b, -np.inf, np.float32), np.zeros(b, np.int32))
    for engine in ("dense", "dense_pallas_fused"):
        def run(stream, pe, pc):
            table, counts, sym, lo, hi = _indexed_batch(stream, episodes)
            c, e, ns, o = count_batch_indexed_stateful(
                table, counts, sym, lo, hi, jnp.asarray(pe), jnp.asarray(pc),
                engine=engine)
            assert not np.asarray(o).any()
            return np.asarray(c), np.asarray(e)
        c_whole, e_whole = run(whole, *fresh)
        c_pre, e_pre = run(prefix, *fresh)
        c_stitch, e_stitch = run(suffix, e_pre, c_pre)
        np.testing.assert_array_equal(c_stitch, c_whole)
        np.testing.assert_array_equal(e_stitch, e_whole)


# ---------------------------------------------------------------------------
# Edge cases: padding, ties, ragged caps/chunks, single-symbol episodes
# ---------------------------------------------------------------------------


def test_all_padding_rows_pass_carry_through():
    rng = np.random.default_rng(0)
    b, n = 4, 3
    empty = {(i, s) for s in range(n) for i in (1, 3)}
    times = _batch_times(rng, b, n, CAP, empty_rows=empty)
    lo = np.zeros((b, n - 1), np.float32)
    hi = np.full((b, n - 1), 2.0, np.float32)
    pe = np.array([-np.inf, 5.0, 1.0, -np.inf], np.float32)
    pc = np.array([0, 3, 1, 2], np.int32)
    cnt, end, nsup, ovf = _dispatch("dense_pallas_fused",
                                    times, lo, hi, pe, pc)
    assert not ovf.any()
    np.testing.assert_array_equal(cnt[[1, 3]], pc[[1, 3]])
    np.testing.assert_array_equal(end[[1, 3]], pe[[1, 3]])
    np.testing.assert_array_equal(nsup[[1, 3]], [0, 0])
    want = _dispatch("dense", times, lo, hi, pe, pc)
    for w, g in zip(want, (cnt, end, nsup, ovf)):
        np.testing.assert_array_equal(w, g)


@pytest.mark.parametrize("seed", range(4))
def test_duplicate_timestamp_ties(seed):
    """Integer-grid streams are full of equal end times; the kernel's strict
    ``start > prev_end`` rule must tie-break exactly like the host greedy."""
    rng = np.random.default_rng(seed)
    b, n = 4, 3
    times = np.full((b, n, CAP), np.inf, np.float32)
    for i in range(b):
        for s in range(n):
            n_real = int(rng.integers(10, CAP))
            times[i, s, :n_real] = np.sort(
                rng.integers(0, 12, n_real)).astype(np.float32)
    lo = np.zeros((b, n - 1), np.float32)
    hi = rng.uniform(1, 4, (b, n - 1)).astype(np.float32)
    pe = np.full(b, -np.inf, np.float32)
    pc = np.zeros(b, np.int32)
    want = _dispatch("dense", times, lo, hi, pe, pc)
    got = _dispatch("dense_pallas_fused", times, lo, hi, pe, pc)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)


@pytest.mark.parametrize("cap", [97, 130, 257])
def test_odd_and_prime_caps_pad_path(cap):
    rng = np.random.default_rng(cap)
    b, n = 3, 3
    times = _batch_times(rng, b, n, cap, empty_rows={(1, 0)})
    lo = rng.uniform(0, 1, (b, n - 1)).astype(np.float32)
    hi = (lo + rng.uniform(0.5, 4, (b, n - 1))).astype(np.float32)
    pe = np.full(b, -np.inf, np.float32)
    pc = np.zeros(b, np.int32)
    want = _dispatch("dense", times, lo, hi, pe, pc)
    got = _dispatch("dense_pallas_fused", times, lo, hi, pe, pc)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)


@pytest.mark.parametrize("batch,chunk", [(9, 8), (7, 3), (5, 16)])
def test_ragged_batch_over_chunk_grid(batch, chunk):
    """Batch sizes that don't divide the rows-per-grid-step chunk exercise
    the kernel's padded tail chunk."""
    rng = np.random.default_rng(batch * 31 + chunk)
    n = 3
    times = _batch_times(rng, batch, n, CAP)
    lo = rng.uniform(0, 1, (batch, n - 1)).astype(np.float32)
    hi = (lo + rng.uniform(0.5, 4, (batch, n - 1))).astype(np.float32)
    pe = np.full(batch, -np.inf, np.float32)
    pc = np.zeros(batch, np.int32)
    want = _dispatch("dense", times, lo, hi, pe, pc, chunk=chunk)
    got = _dispatch("dense_pallas_fused", times, lo, hi, pe, pc, chunk=chunk)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)


def test_single_symbol_episodes():
    """N=1: every first-symbol event is a point occurrence; the greedy fold
    reduces to counting strictly increasing finite times past the carry."""
    rng = np.random.default_rng(5)
    b = 4
    times = _batch_times(rng, b, 1, CAP, empty_rows={(2, 0)})
    times[3, 0, :6] = [1.0, 1.0, 2.0, 2.0, 2.0, 3.0]   # dupes: ties at N=1
    times[3, 0, 6:] = np.inf
    lo = np.zeros((b, 0), np.float32)
    hi = np.zeros((b, 0), np.float32)
    pe = np.array([-np.inf, 50.0, -np.inf, 1.5], np.float32)
    pc = np.array([0, 2, 0, 1], np.int32)
    want = _dispatch("dense", times, lo, hi, pe, pc)
    got = _dispatch("dense_pallas_fused", times, lo, hi, pe, pc)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)
    np.testing.assert_array_equal(got[0][3], 1 + 2)   # 2.0 and 3.0 past 1.5


# ---------------------------------------------------------------------------
# Compaction registry: every method dispatches, unknown names raise
# ---------------------------------------------------------------------------


def _compact_inputs():
    cap, cap_occ, max_window = 16, 8, 4
    t_sym = jnp.asarray(np.sort(np.random.default_rng(0).uniform(
        0, 10, cap)).astype(np.float32))
    wlo = jnp.asarray([0, 2, 5, 9, 0, 0, 0, 0], jnp.int32)
    counts = jnp.asarray([2, 1, 3, 0, 0, 0, 0, 0], jnp.int32)
    carried = jnp.asarray(
        [0.5, 1.0, 2.0, jnp.inf, jnp.inf, jnp.inf, jnp.inf, jnp.inf],
        jnp.float32)
    return t_sym, wlo, counts, carried, cap_occ, max_window


@pytest.mark.parametrize("method", sorted(compaction.METHODS))
def test_compact_accepts_every_registered_method(method):
    t_sym, wlo, counts, carried, cap_occ, max_window = _compact_inputs()
    new_t, new_c, n_out, overflow = compaction.compact(
        t_sym, wlo, counts, carried, cap_occ=cap_occ,
        max_window=max_window, method=method)
    assert new_t.shape == (cap_occ,)
    assert int(n_out) == int(jnp.sum(counts))
    assert not bool(overflow)


def test_compact_unknown_method_raises_value_error():
    t_sym, wlo, counts, carried, cap_occ, max_window = _compact_inputs()
    with pytest.raises(ValueError, match="count_scan_write"):
        compaction.compact(t_sym, wlo, counts, carried, cap_occ=cap_occ,
                           max_window=max_window, method="nope")
    with pytest.raises(ValueError, match="registered methods"):
        compaction.compact(t_sym, wlo, counts, carried, cap_occ=cap_occ,
                           max_window=max_window, method="")


# ---------------------------------------------------------------------------
# Bench gate: fused must be min-time in every cell (run.py --compare)
# ---------------------------------------------------------------------------


def test_fused_cell_failures_gate():
    from benchmarks.run import fused_cell_failures

    def entry(engine, us, batch=8, sched="scan"):
        return {"engine": engine, "scheduler": sched, "episode_len": 3,
                "n_events": 1024, "batch": batch, "us_per_call": us}

    # fused wins outright -> no failures
    assert fused_cell_failures(
        [entry("dense", 100.0), entry("dense_pallas_fused", 80.0)]) == []
    # fused within tolerance of the winner -> still passes
    assert fused_cell_failures(
        [entry("dense", 100.0), entry("dense_pallas_fused", 104.0)],
        tolerance=0.05) == []
    # fused loses a cell -> failure line names the actual winner
    fails = fused_cell_failures(
        [entry("dense", 100.0), entry("dense_pallas_fused", 150.0)],
        tolerance=0.05)
    assert len(fails) == 1 and "dense" in fails[0] and "150.0us" in fails[0]
    # fused missing from a cell -> failure, not a silent pass
    fails = fused_cell_failures([entry("dense", 100.0, batch=32)])
    assert len(fails) == 1 and "not covered" in fails[0]
    # cells are independent: one loss does not mask another cell's win
    fails = fused_cell_failures([
        entry("dense", 100.0), entry("dense_pallas_fused", 90.0),
        entry("dense", 50.0, sched="parallel"),
        entry("dense_pallas_fused", 200.0, sched="parallel")])
    assert len(fails) == 1 and "sched=parallel" in fails[0]
