"""Batch mining through ``repro.core.mine_arrays``: a closed loop of one
caller mining recordings back to back.

Traffic keys: ``recording_s`` (length of each recording), ``recordings``
(distinct recordings, mined in turn). The recordings are the network's
realizations ``0 .. recordings-1`` (``common.catalog_order``): every seed
mines the same recordings, in its own order.
Each call goes from host numpy arrays to the complete per-level result:
the type-index build, every level's join, count and prune, and each
level's sync. ``mine_events_per_s`` is the events of all recordings
completed in the window over the whole window. Every answer is compared
with the plain reference of its recording. After the window the same entry
and configuration also mine one fresh recording, drawn from ``--seed``
(``common.fresh_seed``), compared the same way: a new stream length
compiles programs of its own, so it stays out of set-up and window.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List

import numpy as np

from benchmarks.chip import reference, spikes
from benchmarks.chip.drivers import common
from benchmarks.chip.harness import Window, span


@dataclasses.dataclass
class State:
    recordings: List[tuple]               # (types, times) host arrays
    miner_cfg: object
    answers: List[tuple] = dataclasses.field(default_factory=list)
    errors: List[str] = dataclasses.field(default_factory=list)


def setup(ctx) -> State:
    conf, traffic = ctx.config, ctx.traffic
    duration = float(traffic["recording_s"])
    order = common.catalog_order(ctx.seed, int(traffic["recordings"]))
    recordings = [spikes.simulate(common.network(conf, k), duration)
                  for k in order]
    biggest = max(int(np.bincount(ty, minlength=conf["network"]["n_neurons"])
                      .max()) for ty, _ in recordings)
    st = State(recordings, common.miner_config(
        ctx.core, conf, duration, cap=common.pow2_ceil(biggest)))
    ctx.log(f"set-up: recordings {order} of {duration} s, events "
            f"{[ty.size for ty, _ in recordings]}, per-type cap "
            f"{st.miner_cfg.cap}")
    for types, times in recordings:              # warm every shape used
        mine(ctx, st, types, times)
    return st


def mine(ctx, st: State, types, times):
    stream = ctx.core.EventStream(types, times,
                                  ctx.config["network"]["n_neurons"])
    return ctx.core.mine_arrays(stream, st.miner_cfg)


def window(ctx, st: State):
    tracer, n = ctx.tracer, len(st.recordings)
    compiles0 = ctx.compiles.count
    events = calls = failed = 0
    t0 = last = time.perf_counter()
    durations = []
    tracer.begin(t0)
    while True:
        i = calls % n
        calls += 1
        with span("mine_arrays"):
            try:
                res = mine(ctx, st, *st.recordings[i])
            except Exception as err:     # a failed call is counted, not fatal
                failed += 1
                st.errors.append(repr(err))
                res = None
        now = time.perf_counter()
        durations.append(now - last)
        last = now
        if res is not None:
            events += st.recordings[i][0].size
            st.answers.append((i, res))
        tracer.tick(now, unit=i)
        if now - t0 >= ctx.seconds:
            break
    elapsed = time.perf_counter() - t0
    for err in st.errors[:3]:
        ctx.log(f"failed call: {err}")
    ctx.log(f"window: {calls} calls ({calls / n} per recording) in "
            f"{elapsed} s, {failed} failed")
    q = np.percentile(durations, [0, 25, 50, 75, 100])
    slow = sorted(range(calls), key=durations.__getitem__)[-3:]
    ctx.log(f"window: seconds per call min/q1/median/q3/max {q.tolist()}; "
            f"slowest calls (position, s) "
            f"{[(k, durations[k]) for k in reversed(slow)]}")
    return Window(metrics={"mine_events_per_s": events / elapsed},
                  attempted=calls, failed=failed,
                  compiles=ctx.compiles.count - compiles0)


def check(ctx, st: State) -> Dict[str, tuple]:
    """Every answer against the reference of its recording, and a fresh
    recording mined now against its reference."""
    limit = ctx.config["limits"]["mismatched_entries"]
    want = {}
    bad = 0
    for i, res in st.answers:
        if i not in want:
            want[i] = common.reference_mine(ctx.config, st.miner_cfg,
                                            *st.recordings[i])
        bad += reference.mismatches(common.as_levels(res), want[i])
    ctx.log(f"check: {len(st.answers)} answers of {len(want)} recordings "
            "against the reference")
    return {"mismatched_entries": (bad, limit),
            "fresh_recording_mismatched_entries": (fresh(ctx, st), limit)}


def fresh(ctx, st: State) -> int:
    """Mismatched entries of a recording outside the catalog; a call that
    raises has every entry of the reference's result mismatched."""
    k = common.fresh_seed(ctx.seed, len(st.recordings))
    types, times = spikes.simulate(common.network(ctx.config, k),
                                   float(ctx.traffic["recording_s"]))
    t0 = time.perf_counter()
    try:
        got = common.as_levels(mine(ctx, st, types, times))
    except Exception as err:
        ctx.log(f"fresh recording: failed call: {err!r}")
        got = {}
    ctx.log(f"fresh recording: realization {k}, {types.size} events, mined "
            f"in {time.perf_counter() - t0} s (compiles included)")
    return reference.mismatches(got, common.reference_mine(
        ctx.config, st.miner_cfg, types, times))


def traced_bytes(ctx, st: State, units: List[int]) -> int:
    """Bytes the count kernel needed for the recordings mined in the trace."""
    n_types = ctx.config["network"]["n_neurons"]
    per_recording = {}
    for i, res in st.answers:
        if i not in per_recording:
            per_recording[i] = common.level_bytes(
                res, np.bincount(st.recordings[i][0], minlength=n_types),
                st.miner_cfg.max_candidates)
    return sum(per_recording[i] for i in units)

