"""The miner's host spans and device scopes (``core/obs.py``).

A CPU profiler trace of ``mine_arrays`` on a small spike train, local and
sharded over a one-device mesh, must hold the five spans nested as
documented, one ``repro.mine.sync`` per counted
level plus the level-1 fetch, the count stats of every level, and leaf
spans that never overlap; the lowered count plans must carry the
``table_gather`` and ``scan_table`` scopes in their op metadata. A CPU
trace of a small ``MiningSessionServer`` run, a cold flush then a warm
one, must hold ``repro.pool.flush`` around its five leaves, one
``repro.pool.sync`` per counted level, and ``repro.pool.count`` stats
that are the rows the pool dispatched.
"""
import dataclasses
from typing import Dict, List

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core import (MinerConfig, MiningSessionServer,
                        generate_candidates_arrays, mine_arrays, obs)
from repro.core import plan as plan_mod
from repro.core.mining import _pad_to
from repro.data.spikes import NetworkConfig, simulate

LEAVES = ("repro.mine.index", "repro.mine.join", "repro.mine.count",
          "repro.mine.sync")


@dataclasses.dataclass
class Span:
    name: str
    start_ns: int
    end_ns: int
    stats: Dict[str, int]


def _spans(log_dir) -> List[Span]:
    import glob
    import os
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(str(log_dir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out.extend(Span(e.name, int(e.start_ns), int(e.end_ns),
                            dict(e.stats))
                       for e in line.events
                       if e.name.startswith(obs.SPAN_PREFIX))
    return sorted(out, key=lambda s: s.start_ns)


def _traced(fn, log_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        return fn()
    finally:
        jax.profiler.stop_trace()


@pytest.fixture(scope="module", params=["local", "sharded"])
def recorded(request, tmp_path_factory):
    """An untraced call of ``mine_arrays`` (it warms every plan), then a
    traced one: ``(stream, untraced result, traced result, spans)``."""
    net = NetworkConfig(n_neurons=8, n_embedded=1, episode_len=4, seed=3)
    stream = simulate(net, 3.0)
    mesh = (Mesh(np.array(jax.devices()[:1]), ("data",))
            if request.param == "sharded" else None)
    cfg = MinerConfig(t_low=0.0, t_high=0.01, threshold=5,
                      level_thresholds={2: 8}, max_level=4,
                      engine="dense_pallas_fused", max_candidates=64,
                      cap=plan_mod.pow2_ceil(
                          int(np.bincount(np.asarray(stream.types)).max())),
                      mesh=mesh, halo=64)
    untraced = mine_arrays(stream, cfg)
    log_dir = tmp_path_factory.mktemp("trace")
    result = _traced(lambda: mine_arrays(stream, cfg), log_dir)
    return stream, untraced, result, _spans(log_dir)


def _inside(inner: Span, outer: Span) -> bool:
    return outer.start_ns <= inner.start_ns and inner.end_ns <= outer.end_ns


def test_spans_are_named_and_nested(recorded):
    stream, _, result, spans = recorded
    roots = [s for s in spans if s.name == "repro.mine"]
    assert len(roots) == 1
    root, = roots
    assert root.stats["events"] == stream.n_events
    assert {s.name for s in spans} == {"repro.mine", *LEAVES}
    assert all(_inside(s, root) for s in spans)
    assert max(result) == 4 and result[4].n_candidates > 0


def test_one_sync_per_counted_level_plus_one(recorded):
    _, _, result, spans = recorded
    counted = [lv for lv, la in result.items() if lv > 1 and la.n_candidates]
    syncs = [s for s in spans if s.name == "repro.mine.sync"]
    assert len(syncs) == 1 + len(counted)
    assert [s.stats["level"] for s in syncs] == [1, *counted]


def test_count_stats_are_the_join_and_its_capacity_class(recorded):
    _, _, result, spans = recorded
    counts = [s for s in spans if s.name == "repro.mine.count"]
    joins = [s for s in spans if s.name == "repro.mine.join"]
    assert [s.stats["level"] for s in joins] == [s.stats["level"]
                                                 for s in counts]
    for s in counts:
        b = result[s.stats["level"]].n_candidates
        assert s.stats["rows"] == b
        assert s.stats["padded"] == _pad_to(b)


def test_leaf_spans_do_not_overlap(recorded):
    _, _, _, spans = recorded
    leaves = sorted((s for s in spans if s.name in LEAVES),
                    key=lambda s: s.start_ns)
    assert leaves[0].name == "repro.mine.index"
    for a, b in zip(leaves, leaves[1:]):
        assert a.end_ns <= b.start_ns, (a, b)


def test_traced_result_equals_untraced(recorded):
    _, untraced, result, _ = recorded
    assert result.keys() == untraced.keys()
    for lv in result:
        np.testing.assert_array_equal(result[lv].symbols,
                                      untraced[lv].symbols)
        np.testing.assert_array_equal(result[lv].counts, untraced[lv].counts)
        assert result[lv].n_candidates == untraced[lv].n_candidates


PLANS = {
    "count_indexed": {},
    "count_stateful": {},
    "count_tail": {"tail_cap": 64},
    "count_corpus": {"streams": 4},
    "count_corpus_tail": {"streams": 4, "tail_cap": 64},
    "count_corpus_tail_grouped": {"streams": 4, "tail_cap": 64},
}


@pytest.mark.parametrize("fn", sorted(PLANS))
def test_count_plans_carry_the_named_scopes(fn):
    p = plan_mod.plan_for(fn, level=3, n_types=16, cap=256, batch=32,
                          engine="dense_pallas_fused", **PLANS[fn])
    entry = plan_mod._fn_entry(p.fn)
    text = jax.jit(entry.build(p)).lower(*entry.specs(p)).as_text(
        debug_info=True)
    assert "/table_gather/" in text
    assert "/scan_table/" in text


# ---------------------------------------------------------------------------
# the session pool's spans
# ---------------------------------------------------------------------------

POOL_LEAVES = ("repro.pool.absorb", "repro.pool.join", "repro.pool.count",
               "repro.pool.sync", "repro.pool.carry")


def _pool_cfg():
    return MinerConfig(t_low=0.0, t_high=0.01, threshold=5,
                       level_thresholds={2: 8}, max_level=4, engine="dense",
                       max_candidates=64)


def _pool_feeds():
    """Three sessions' streams, each cut in two chunks: the first flush
    counts every row cold, the second carries the rows it counted again."""
    feeds = []
    for seed in (3, 4, 5):
        stream = simulate(NetworkConfig(n_neurons=8, n_embedded=1,
                                        episode_len=4, seed=seed), 3.0)
        types, times = np.asarray(stream.types), np.asarray(stream.times)
        cut = int(np.searchsorted(times, 2.0))
        feeds.append([(types[:cut], times[:cut]), (types[cut:], times[cut:])])
    return feeds


def _serve_pool(feeds, dispatches=()):
    """Serve ``feeds`` through a fresh server, one flush a chunk; returns
    every session's result after each flush, and the entries each flush
    added to ``dispatches``."""
    server = MiningSessionServer(8, _pool_cfg(), max_sessions=4,
                                 initial_cap=1024)
    sids = [server.create_session() for _ in feeds]
    results, per_flush = [], []
    for k in range(len(feeds[0])):
        for sid, chunks in zip(sids, feeds):
            server.append(sid, *chunks[k])
        start = len(dispatches)
        server.flush()
        per_flush.append(dispatches[start:])
        results.append([server.results(sid) for sid in sids])
    return results, per_flush


@pytest.fixture(scope="module")
def pooled(tmp_path_factory):
    """An untraced pool run (it warms every plan), then a traced one that
    records each count dispatch's ``(level, sessions x rows)``."""
    from repro.core import counting

    feeds = _pool_feeds()
    untraced, _ = _serve_pool(feeds)
    dispatches = []
    real = counting.count_corpus_tail_grouped

    def recording(*args, **kw):
        s, b, n = np.shape(args[4])
        dispatches.append((n, s * b))
        return real(*args, **kw)

    log_dir = tmp_path_factory.mktemp("pool_trace")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(counting, "count_corpus_tail_grouped", recording)
        results, per_flush = _traced(
            lambda: _serve_pool(feeds, dispatches), log_dir)
    return feeds, untraced, results, per_flush, _spans(log_dir)


def _flushes(spans):
    """Each ``repro.pool.flush`` span with the pool spans inside it."""
    roots = [s for s in spans if s.name == "repro.pool.flush"]
    return [(root, [s for s in spans if s is not root and _inside(s, root)])
            for root in roots]


def _candidates(result, level):
    if level - 1 not in result:
        return set()
    rows = generate_candidates_arrays(result[level - 1].symbols, level,
                                      _pool_cfg())
    return {tuple(int(x) for x in row) for row in rows}


def _counted_levels(results):
    return sorted({lv for res in results for lv, la in res.items()
                   if lv > 1 and la.n_candidates})


def test_pool_spans_are_named_and_nested(pooled):
    feeds, _, results, _, spans = pooled
    pool = [s for s in spans if s.name.startswith("repro.pool.")]
    flushes = _flushes(pool)
    assert len(flushes) == 2
    assert {s.name for s in pool} == {"repro.pool.flush", *POOL_LEAVES}
    assert {s.name for s in spans} <= set(obs.SPANS)
    assert sum(len(inner) for _, inner in flushes) == len(pool) - 2
    for k, (root, inner) in enumerate(flushes):
        assert root.stats["sessions"] == len(feeds)
        assert root.stats["events"] == sum(f[k][0].size for f in feeds)
        assert inner[0].name == "repro.pool.absorb"
        assert {s.name for s in inner} == set(POOL_LEAVES)
    assert max(_counted_levels(results[1])) >= 3


def test_pool_leaf_spans_do_not_overlap(pooled):
    leaves = sorted((s for s in pooled[-1] if s.name in POOL_LEAVES),
                    key=lambda s: s.start_ns)
    for a, b in zip(leaves, leaves[1:]):
        assert a.end_ns <= b.start_ns, (a, b)


def test_pool_one_sync_per_counted_level(pooled):
    _, _, results, _, spans = pooled
    for (_, inner), res in zip(_flushes(spans), results):
        counted = _counted_levels(res)
        for name in ("repro.pool.count", "repro.pool.sync",
                     "repro.pool.carry"):
            assert [s.stats["level"] for s in inner if s.name == name] == \
                counted, name


def test_pool_count_stats_are_the_rows_dispatched(pooled):
    """``warm`` and ``cold`` are the candidates each session counted again
    and anew (from the answers: a row is warm where the session's previous
    flush counted it), ``padded`` the rows of the level's dispatches."""
    _, _, results, per_flush, spans = pooled
    previous = [{} for _ in results[0]]
    warm_seen = 0
    for (_, inner), res, dispatched in zip(_flushes(spans), results,
                                           per_flush):
        for s in (s for s in inner if s.name == "repro.pool.count"):
            level = s.stats["level"]
            now = [_candidates(r, level) for r in res]
            before = [_candidates(r, level) for r in previous]
            warm = sum(len(a & b) for a, b in zip(now, before))
            assert s.stats["warm"] == warm
            assert s.stats["cold"] == sum(len(a - b)
                                          for a, b in zip(now, before))
            assert s.stats["padded"] == sum(rows for lv, rows in dispatched
                                            if lv == level)
            assert s.stats["warm"] + s.stats["cold"] <= s.stats["padded"]
            warm_seen += warm
        previous = res
    assert warm_seen > 0


def test_pool_traced_results_equal_untraced(pooled):
    _, untraced, results, _, _ = pooled
    for got_flush, want_flush in zip(results, untraced):
        for got, want in zip(got_flush, want_flush):
            assert got.keys() == want.keys()
            for lv in got:
                np.testing.assert_array_equal(got[lv].symbols,
                                              want[lv].symbols)
                np.testing.assert_array_equal(got[lv].counts, want[lv].counts)
                assert got[lv].n_candidates == want[lv].n_candidates
