"""The miner's host spans and device scopes (``core/obs.py``).

A CPU profiler trace of ``mine_arrays`` on a small spike train, local and
sharded over a one-device mesh, must hold the five spans nested as
documented, one ``repro.mine.sync`` per counted
level plus the level-1 fetch, the count stats of every level, and leaf
spans that never overlap; the lowered count plans must carry the
``table_gather`` and ``scan_table`` scopes in their op metadata.
"""
import dataclasses
from typing import Dict, List

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core import MinerConfig, mine_arrays, obs
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
