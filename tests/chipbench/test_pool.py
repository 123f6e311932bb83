"""Tests of the session pool's cell (``benchmarks/chip/drivers/pool.py``,
``pool_bytes.py``, ``pool_spans.py`` and the ``*.pool`` readers).

They run on the CPU: the pool's byte count against a hand count, the
readers of the pool's spans on made-up traces (a program that does not
declare the spans reads as nothing, one that declares them and lacks them
fails), the driver's schedule against a stand-in server, and a small run
whose check compares a session opened on a recycled slot.
"""
from __future__ import annotations

import time
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks.chip import pool_bytes, run, trace as tl
from benchmarks.chip.harness import Reading

from test_chipbench import small_cell

POOL = "pool-live-64ch"
SPAN_READERS = {"absorb_host_ms.pool": "repro.pool.absorb",
                "join_host_ms.pool": "repro.pool.join",
                "count_host_ms.pool": "repro.pool.count",
                "sync_wait_ms.pool": "repro.pool.sync",
                "carry_host_ms.pool": "repro.pool.carry",
                "count_rows_useful_pct.pool": "repro.pool.count"}


def driver():
    return run.load_module(run.HERE / "drivers" / "pool.py")


def reader(name):
    return run.load_module(run.HERE / "metrics" / f"{name}.py").read


# ---------------------------------------------------------------------------
# the byte count
# ---------------------------------------------------------------------------


def levels(*frontiers):
    return {level: SimpleNamespace(symbols=np.asarray(rows, np.int32))
            for level, rows in enumerate(frontiers, start=1)}


def test_step_bytes_hand_count():
    """Three types; the newest chunk starts at 1.2 s. With two candidates
    allowed, level 2 counts (0, 0), carried from the previous step (which
    had only type 0 frequent), and (0, 1), new."""
    types = np.array([0, 1, 2, 0, 1, 0, 2, 1])
    times = np.array([0.0, 0.004, 0.5, 1.0, 1.003, 1.2, 1.25, 1.3],
                     np.float32)
    kw = dict(t_high=0.1, n_types=3, max_candidates=2)
    now = levels([[0], [1]], [])
    before = levels([[0]], [])
    # whole history per type: 3, 3, 2; from 1.2 - 0.1 s on: 1, 1, 1
    carried = 4 * (1 + 1) + 4          # (0, 0) over the chunk's reach
    new = 4 * (3 + 3) + 4              # (0, 1) over the whole history
    assert pool_bytes.step_bytes(now, before, types, times, 1.2,
                                 **kw) == carried + new
    # a session's first step counts every row over its whole history
    assert pool_bytes.step_bytes(now, None, types, times, 1.2,
                                 **kw) == 2 * (4 * (3 + 3) + 4)
    # the reach grows with the level: level 3 reaches back 2 * t_high
    three = levels([[0], [1]], [[0, 1], [1, 0]], [])
    assert pool_bytes.candidates(three, 3, 4096) == [(0, 1, 0), (1, 0, 1)]
    same = pool_bytes.step_bytes(three, three, types, times, 1.2,
                                 t_high=0.1, n_types=3, max_candidates=4096)
    # level 2: four carried pairs over [1.1, 1.3] (types 0 and 1 once
    # each); level 3: two carried rows over [1.0, 1.3] (0: 2, 1: 2)
    assert same == (4 * (2 + 2 + 2 + 2) + 16) + (4 * (6 + 6) + 8)


# ---------------------------------------------------------------------------
# the readers of the pool's spans
# ---------------------------------------------------------------------------


def made_up_trace(spans=True):
    host = []
    if spans:
        host = [tl.Event("repro.pool.flush", 0, 1000),
                tl.Event("repro.pool.absorb", 0, 100),
                tl.Event("repro.pool.join", 100, 150),
                tl.Event("repro.pool.count", 150, 400,
                         (("level", 2), ("warm", 30), ("cold", 10),
                          ("padded", 64))),
                tl.Event("repro.pool.sync", 400, 900),
                tl.Event("repro.pool.carry", 900, 960),
                tl.Event("repro.pool.count", 960, 980,
                         (("level", 3), ("warm", 0), ("cold", 0),
                          ("padded", 16)))]
    return tl.Trace(window=(0, 2000), device_ops={}, spans=[], host=host)


def reading(trace, units=2):
    return Reading(trace=trace, units=units, unit_bytes=0, compiles=0,
                   device_kind="TPU v5 lite")


def test_pool_span_readers_hand_counted():
    r = reading(made_up_trace())
    ms = {name: reader(name)(r) for name in SPAN_READERS}
    assert ms["absorb_host_ms.pool"] == pytest.approx(100e-6 / 2)
    assert ms["join_host_ms.pool"] == pytest.approx(50e-6 / 2)
    assert ms["count_host_ms.pool"] == pytest.approx(270e-6 / 2)
    assert ms["sync_wait_ms.pool"] == pytest.approx(500e-6 / 2)
    assert ms["carry_host_ms.pool"] == pytest.approx(60e-6 / 2)
    assert ms["count_rows_useful_pct.pool"] == pytest.approx(100 * 40 / 80)
    assert reader("absorb_host_ms.pool")(reading(made_up_trace(), 0)) is None


@pytest.mark.parametrize("name", sorted(SPAN_READERS))
def test_pool_span_readers_read_nothing_where_undeclared(monkeypatch, name):
    """A program older than the pool's spans (no ``obs.SPANS``, or one
    without them) reads as nothing, even with units traced."""
    from repro.core import obs

    r = reading(made_up_trace(spans=False))
    monkeypatch.setattr(obs, "SPANS", tuple(
        s for s in obs.SPANS if not s.startswith("repro.pool.")))
    assert reader(name)(r) is None
    monkeypatch.delattr(obs, "SPANS")
    assert reader(name)(r) is None


@pytest.mark.parametrize("name", sorted(SPAN_READERS))
def test_pool_span_readers_fail_where_declared_and_missing(name):
    with pytest.raises(LookupError, match=SPAN_READERS[name]):
        reader(name)(reading(made_up_trace(spans=False)))


# ---------------------------------------------------------------------------
# the driver
# ---------------------------------------------------------------------------


class StandIn:
    """A server that keeps what each session was sent; ``results`` returns
    its stream so far."""

    def __init__(self):
        self.streams, self.next_sid, self.flushes = {}, 0, 0

    def create_session(self):
        self.next_sid += 1
        self.streams[self.next_sid] = ([], [])
        return self.next_sid

    def evict(self, sid):
        del self.streams[sid]

    def append(self, sid, types, times):
        self.streams[sid][0].append(types)
        self.streams[sid][1].append(times)
        return int(types.size)

    def flush(self):
        self.flushes += 1

    def results(self, sid):
        types, times = self.streams[sid]
        return np.concatenate(types), np.concatenate(times)


def test_schedule_staggers_sessions_and_feeds_each_its_prefix():
    pool = driver()
    rng = np.random.default_rng(0)
    recordings = []
    for _ in range(5):
        times = np.sort(rng.uniform(0, 6.0, 300)).astype(np.float32)
        recordings.append((rng.integers(0, 4, 300).astype(np.int32), times))
    sessions, per, n_chunks = 3, 4, 12             # 0.5 s chunks of 6 s
    bounds = [np.searchsorted(t, np.arange(n_chunks + 1) * 0.5)
              for _, t in recordings]
    st = pool.State(recordings, bounds, None, StandIn(), sessions, per,
                    n_chunks)
    for r in range(1, sessions):                    # set-up's histories
        pool.open_due(st, (r - 1) * per)
        pool.feed(st, (r - 1) * per, r * per)
    st.step = (sessions - 1) * per
    for _ in range(5 * per):
        pool.step(st)
        assert len(st.live) == sessions
        newest = (st.step - 1) // per               # due at the last step
        assert sorted(st.live) == list(range(newest - sessions + 1,
                                             newest + 1))
    assert st.server.flushes == 5 * per
    for a in st.answers:                            # every read: the prefix
        types, times = a.result
        want_types, want_times = st.stream(a.opening, a.chunks)
        assert np.array_equal(types, want_types)
        assert np.array_equal(times, want_times)
    assert {a.opening for a in st.answers} == set(range(7))
    assert max(a.chunks for a in st.answers) == n_chunks


def test_small_run_checks_a_session_on_a_recycled_slot(monkeypatch):
    pool = driver()
    kept = {}
    real_check = pool.check

    def check(ctx, st):
        kept["state"] = st
        return real_check(ctx, st)

    monkeypatch.setattr(pool, "check", check)
    seed = 2 ** 33 + 5
    result = run.run_cell(small_cell(POOL), seed=seed, seconds=1.5,
                          trace=False, require_tpu=False, log=lambda _: None,
                          t_start=time.perf_counter())
    assert result["correct"]
    st = kept["state"]
    chosen = [st.answers[k] for k in pool.checked_answers(seed, st)]
    last = st.answers[-1].step
    assert {a.opening for a in chosen if a.step == last} == {
        a.opening for a in st.answers if a.step == last}
    youngest = max(a.opening for a in st.answers
                   if a.opening >= st.sessions)
    assert [a.step for a in chosen if a.opening == youngest] == [
        a.step for a in st.answers if a.opening == youngest]
    others = [a for a in st.answers
              if a.step != last and a.opening != youngest]
    drawn = [a for a in chosen if a in others]
    assert len(drawn) == min(pool.SAMPLE, len(others))


def test_control_float32_edges_is_rejected_on_a_pool_stream():
    """The reference with float32 window edges, on a session of the cell
    at 187.5 s (the oldest age a window step starts from), under the
    cell's thresholds: the comparison must not pass it."""
    from benchmarks.chip import reference, spikes
    from benchmarks.chip.drivers import common

    cell = run.load_cell(POOL)
    conf = cell.config
    threshold, per_level = common.thresholds(conf,
                                             cell.traffic["recording_s"])
    kw = dict(n_types=conf["network"]["n_neurons"],
              t_low=conf["mining"]["t_low"], t_high=conf["mining"]["t_high"],
              threshold=threshold, level_thresholds=per_level,
              max_level=conf["mining"]["max_level"],
              max_candidates=conf["mining"]["max_candidates"])
    types, times = spikes.simulate(common.network(conf, 0), 187.5)
    bad = reference.mismatches(
        reference.mine(types, times, edges="float32", **kw),
        reference.mine(types, times, **kw))
    assert bad > conf["limits"]["mismatched_entries"]
