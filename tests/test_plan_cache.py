"""MiningPlan dispatch spine + AOT executable cache (DESIGN.md §11).

The O(#buckets) compile gate: K distinct input shapes falling into k
capacity-class buckets must trace each cached counting function exactly k
times — with bit-for-bit result parity against the uncached path across
engines x schedulers — plus the cache-behavior contract (LRU bound, warm
idempotency, shared executables across streaming sessions, warned fallback
for uncacheable plans) and the one-rounding-rule regression against every
checked-in tuned_configs.json bucket.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import EventStream, MinerConfig, StreamingMiner, mine_arrays
from repro.core import corpus as corpus_lib
from repro.core import counting, events
from repro.core import plan
from repro.kernels import autotune


@pytest.fixture(autouse=True)
def fresh_cache():
    plan.reset_cache()
    plan.reset_trace_counts()
    yield
    plan.reset_cache()
    plan.reset_trace_counts()


def _stream(n, n_types=4, seed=0, t_max=None):
    """Round-robin types (every type present) with sorted random times."""
    rng = np.random.default_rng(seed)
    types = (np.arange(n) % n_types).astype(np.int32)
    rng.shuffle(types)
    times = np.sort(rng.uniform(0.0, t_max or n * 0.05, n)).astype(np.float32)
    return EventStream(types, times, n_types)


def _flat(results):
    return {lvl: (la.symbols.tolist(), la.counts.tolist(), la.n_candidates)
            for lvl, la in results.items()}


# ---------------------------------------------------------------------------
# One rounding rule, one bucket scheme
# ---------------------------------------------------------------------------


def test_rounding_rule_single_source():
    # autotune's bucket rounding IS plan.pow2_ceil — same object, not a copy
    assert autotune.pow2_ceil is plan.pow2_ceil
    assert autotune._pow2_ceil is plan.pow2_ceil
    for raw, rounded in [(0, 1), (1, 1), (2, 2), (3, 4), (8, 8), (9, 16),
                         (1000, 1024), (1025, 2048)]:
        assert plan.pow2_ceil(raw) == rounded
    # idempotent: rounding before bucket_key changes nothing
    for cap, batch in [(33, 5), (64, 16), (1000, 7)]:
        assert (autotune.bucket_key("count", 2, cap, batch)
                == autotune.bucket_key("count", 2, plan.pow2_ceil(cap),
                                       plan.pow2_ceil(batch)))
    assert plan.capacity_class(5, floor=16) == 16
    assert plan.capacity_class(17, floor=16) == 32


def test_every_tuned_bucket_reachable_from_a_plan():
    """Regression: each checked-in tuned_configs.json bucket is the bucket
    of some MiningPlan, so tuning and plan bucketing cannot drift apart."""
    table = autotune.load_table()
    assert table, "tuned_configs.json went missing or empty"
    engine_for = {"count": "dense_pallas_fused", "track": "dense"}
    for key in table:
        kind, lvl, cap, batch = key.split(":")
        levels, cap, batch = int(lvl[1:]), int(cap[1:]), int(batch[1:])
        p = plan.plan_for(
            "count_indexed", level=levels + 1, n_types=4, cap=cap,
            batch=batch, engine=engine_for[kind])
        assert p.kind == kind, key
        assert p.autotune_key() == key, key
        # and the plan carries exactly the tiles that bucket tunes
        tc = autotune.resolve(kind, levels, cap, batch)
        assert (p.block_next, p.block_prev, p.window_tiles, p.chunk) == (
            tc.block_next, tc.block_prev, tc.window_tiles, tc.chunk), key


# ---------------------------------------------------------------------------
# The O(#buckets) trace gate (tentpole acceptance)
# ---------------------------------------------------------------------------

# 8 distinct lengths in exactly 2 capacity classes (64 and 128): K > 3*k
RAGGED_LENGTHS = (33, 40, 47, 60, 70, 90, 100, 120)


@pytest.mark.parametrize("engine", ["dense", "dense_pallas_fused"])
@pytest.mark.parametrize("parallel", [False, True])
def test_mine_arrays_compiles_per_bucket(engine, parallel):
    cfg = MinerConfig(t_low=0.0, t_high=1.0, threshold=1, max_level=2,
                      engine=engine, parallel_schedule=parallel)
    plan.reset_trace_counts()
    plan.reset_cache()
    cached = {}
    for n in RAGGED_LENGTHS:
        cached[n] = mine_arrays(_stream(n, seed=n), cfg)
    # threshold=1 + every type present => the level-2 batch is always
    # n_types^2 = 16: one batch class, two cap classes => exactly 2 traces
    assert plan.trace_counts() == {"count_indexed": 2}
    stats = plan.cache_stats()
    assert stats["misses"] == 2
    assert stats["hits"] == len(RAGGED_LENGTHS) - 2
    # a second ragged pass over every shape compiles NOTHING new
    for n in RAGGED_LENGTHS:
        again = mine_arrays(_stream(n, seed=n), cfg)
        assert _flat(again) == _flat(cached[n])
    assert plan.trace_counts() == {"count_indexed": 2}
    # bit-for-bit parity with the uncached path
    for n in RAGGED_LENGTHS:
        with plan.cache_disabled():
            ref = mine_arrays(_stream(n, seed=n), cfg)
        assert _flat(ref) == _flat(cached[n])


def test_mine_corpus_compiles_per_bucket():
    cfg = MinerConfig(t_low=0.0, t_high=1.0, threshold=1, max_level=2)
    corpora = [
        [_stream(60, seed=s) for s in range(3)],   # S=3 -> class 4
        [_stream(60, seed=s) for s in range(4)],   # S=4 -> class 4 (shared)
        [_stream(60, seed=s) for s in range(5)],   # S=5 -> class 8
    ]
    plan.reset_trace_counts()
    results = [corpus_lib.mine_corpus(c, cfg) for c in corpora]
    assert plan.trace_counts() == {"count_corpus": 2}
    with plan.cache_disabled():
        ref = corpus_lib.mine_corpus(corpora[0], cfg)
    for got, want in zip(results[0].per_stream, ref.per_stream):
        assert _flat(got) == _flat(want)


def test_streaming_sessions_share_one_executable():
    """Same-bucket appends across concurrent miners: zero extra compiles."""
    cfg = MinerConfig(t_low=0.0, t_high=0.5, threshold=1, max_level=2)
    chunks = [_stream(16, seed=7, t_max=0.8)]
    base = chunks[0]
    for i in range(1, 4):   # identical-shape chunks, shifted in time
        chunks.append(EventStream(base.types, base.times + i * 0.8, 4))

    def run(miner):
        out = None
        for c in chunks:
            out = miner.append(c.types, c.times)
        return out

    plan.reset_trace_counts()
    m1 = StreamingMiner(4, cfg, initial_cap=64)
    out1 = run(m1)
    t_after_one = plan.trace_counts()
    assert t_after_one.get("count_stateful", 0) >= 1    # cold backfill
    assert t_after_one.get("count_tail", 0) >= 1        # warm tail recount
    # a second session over the same bucket compiles NOTHING new ...
    m2 = StreamingMiner(4, cfg, initial_cap=64)
    out2 = run(m2)
    assert plan.trace_counts() == t_after_one
    assert _flat(out2) == _flat(out1)
    # ... and interleaved appends (concurrent sessions) don't either
    m3 = StreamingMiner(4, cfg, initial_cap=64)
    m4 = StreamingMiner(4, cfg, initial_cap=64)
    for c in chunks:
        m3.append(c.types, c.times)
        m4.append(c.types, c.times)
    assert plan.trace_counts() == t_after_one


# ---------------------------------------------------------------------------
# Cache behavior: LRU bound, warm, fallback
# ---------------------------------------------------------------------------


def _indexed_case(n_events, batch, seed=0):
    s = _stream(n_events, seed=seed)
    table, counts = events.type_index(s.types, s.times, s.n_types, n_events)
    sym = np.stack([np.arange(batch) % 4,
                    (np.arange(batch) + 1) % 4], axis=1).astype(np.int32)
    lo = np.zeros((batch, 1), np.float32)
    hi = np.full((batch, 1), 1.0, np.float32)
    return table, counts, sym, lo, hi


def test_lru_eviction_honors_bound_and_retraces_once():
    plan.reset_cache(maxsize=2)
    cases = {n: _indexed_case(n, 8, seed=n) for n in (30, 60, 120)}  # 3 caps
    first = {n: counting.count_batch_indexed(*c) for n, c in cases.items()}
    stats = plan.cache_stats()
    assert stats["size"] == 2
    assert stats["misses"] == 3
    assert stats["evictions"] == 1          # bucket 32 evicted by 128
    p32 = plan.plan_for("count_indexed", level=2, n_types=4, cap=30, batch=8)
    assert plan.plan_trace_counts()[p32] == 1
    # the evicted bucket returns: exactly one re-trace, then cached again
    again = counting.count_batch_indexed(*cases[30])
    assert plan.plan_trace_counts()[p32] == 2
    for a, b in zip(again, first[30]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert plan.cache_stats()["evictions"] == 2      # 64 made room
    counting.count_batch_indexed(*cases[30])
    assert plan.plan_trace_counts()[p32] == 2        # hit, no re-trace
    # shrinking the bound evicts immediately
    plan.set_cache_size(1)
    assert plan.cache_stats()["size"] == 1


def test_warm_is_idempotent_and_primes_real_calls():
    p = plan.plan_for("count_indexed", level=2, n_types=4, cap=60, batch=8)
    assert plan.warm([p]) == {"compiled": 1, "cached": 0, "skipped": 0}
    assert plan.warm([p]) == {"compiled": 0, "cached": 1, "skipped": 0}
    assert plan.plan_trace_counts()[p] == 1
    # a real call in that bucket is a pure hit: no compile, no miss
    out = counting.count_batch_indexed(*_indexed_case(60, 8))
    assert plan.cache_stats()["misses"] == 0
    assert plan.cache_stats()["hits"] == 1
    assert plan.plan_trace_counts()[p] == 1
    with plan.cache_disabled():
        ref = counting.count_batch_indexed(*_indexed_case(60, 8))
    for a, b in zip(out, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_oversized_plan_falls_back_with_warning(monkeypatch):
    case = _indexed_case(60, 8)
    with plan.cache_disabled():
        ref = counting.count_batch_indexed(*case)
    monkeypatch.setattr(plan, "MAX_CACHE_BATCH", 4)
    with pytest.warns(UserWarning, match="not cacheable"):
        out = counting.count_batch_indexed(*case)
    for a, b in zip(out, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    stats = plan.cache_stats()
    assert stats["fallbacks"] == 1
    assert stats["size"] == 0               # nothing cached


def test_cached_executable_rejects_mismatched_inputs():
    """Inputs that miss the plan's specs are an adapter bug: the cached
    executable's error propagates instead of a silent uncached recompile."""
    table, counts, sym, lo, hi = _indexed_case(60, 8)
    counting.count_batch_indexed(table, counts, sym, lo, hi)
    p = plan.plan_for("count_indexed", level=2, n_types=4, cap=60, batch=8)
    assert p in plan.cached_plans()
    wrong = jnp.zeros((4, 2 * p.cap), jnp.float32)   # table twice too wide
    with pytest.raises((TypeError, ValueError)):
        plan.dispatch(p, wrong, counts, jnp.int32(60), jnp.asarray(sym),
                      jnp.asarray(lo), jnp.asarray(hi))
    assert plan.cache_stats()["fallbacks"] == 0


def test_malformed_plan_is_uncacheable_not_fatal():
    bad = plan.MiningPlan(fn="count_indexed", level=1, n_types=4,
                          cap=64, batch=8)
    assert "malformed" in plan.uncacheable_reason(bad)
    tail0 = plan.MiningPlan(fn="count_tail", level=2, n_types=4, cap=64,
                            batch=8, tail_cap=0)
    assert "tail" in plan.uncacheable_reason(tail0)
    ok = plan.plan_for("count_indexed", level=2, n_types=4, cap=64, batch=8)
    assert plan.uncacheable_reason(ok) is None
    with pytest.warns(UserWarning, match="warm: skipping"):
        assert plan.warm([bad]) == {"compiled": 0, "cached": 0, "skipped": 1}


# ---------------------------------------------------------------------------
# Bucket padding must not weaken semantics
# ---------------------------------------------------------------------------


def test_build_cap_preserves_overflow_detection():
    """A table padded from its build width (10) to its class (16) must
    still flag per-type overflow against the BUILD width."""
    n = 60
    s = _stream(n, seed=3)
    table, counts = events.type_index(s.types, s.times, s.n_types, 10)
    assert int(np.asarray(counts).max()) > 10   # truly overflowing
    sym = np.array([[0, 1]], np.int32)
    lo = np.zeros((1, 1), np.float32)
    hi = np.ones((1, 1), np.float32)
    _, _, overflow = counting.count_batch_indexed(table, counts, sym, lo, hi)
    assert bool(np.asarray(overflow)[0])
    # sanity: a wide-enough build does not flag
    table2, counts2 = events.type_index(s.types, s.times, s.n_types, n)
    _, _, ov2 = counting.count_batch_indexed(table2, counts2, sym, lo, hi)
    assert not bool(np.asarray(ov2)[0])


def test_plans_for_miner_covers_a_cold_mine():
    """warm(plans_for_miner(...)) => the first mine_arrays pays 0 compiles."""
    cfg = MinerConfig(t_low=0.0, t_high=1.0, threshold=1, max_level=2)
    s = _stream(60, seed=11)
    plans = plan.plans_for_miner(cfg, n_types=4, n_events=60)
    plan.warm(plans)
    warmed_traces = dict(plan.trace_counts())
    mine_arrays(s, cfg)
    assert plan.trace_counts() == warmed_traces   # zero new compiles
    assert plan.cache_stats()["misses"] == 0
