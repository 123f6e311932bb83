"""Live sessions through ``repro.core.MiningSessionServer``: a closed loop
of one caller stepping a pool of rigs, each streaming its recording in
chunks and reading its session's full-history result after every chunk.

Traffic keys: ``sessions`` (live sessions, one per rig), ``recording_s``
(each session's life), ``chunk_s`` (one chunk per session per step),
``recordings`` (the catalog: the network's realizations ``0 ..
recordings-1``, in the order ``common.catalog_order`` draws from
``--seed``). Opening ``o`` (the ``o``-th session ever opened) streams the
catalog's recording ``o mod recordings`` and starts ``o * recording_s /
sessions`` seconds into the run, so the live sessions' ages are staggered
by ``recording_s / sessions`` and exactly ``sessions`` are live at every
step. The seed also draws the answers the check samples and the fresh
recording.

A step appends one chunk to every live session, flushes once and reads
every session's result. A session whose recording is used up is evicted
first, and every ``recording_s / sessions`` seconds a new one opens in
the slot it freed. Set-up reaches the staggered ages by feeding each
session's history in chunks of at most ``recording_s / sessions``, one
flush each, then runs whole steps for two eviction periods, so that every
run's window starts at the same phase, and compiles every count plan that
pairs a batch class and a tail class those steps dispatched at one level.
``mine_events_per_s`` is the events of the steps completed in the window
over the window; a unit of the trace is one step.

The check compares, against the plain reference of the session's stream
up to that step: every session's answer at the window's last step, every
answer of the youngest session opened on a recycled slot, and 16 more
answers of the window drawn from ``--seed``. After the window the oldest
session is evicted and one recording outside the catalog
(``common.fresh_seed``) opens in its slot, fed in chunks of
``recording_s / sessions`` with one flush each; every answer is compared.
"""
from __future__ import annotations

import dataclasses
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List

import numpy as np

from benchmarks.chip import pool_bytes, reference, spikes
from benchmarks.chip.drivers import common
from benchmarks.chip.harness import Window, span

SAMPLE = 16                       # answers drawn from --seed for the check
REFERENCE_THREADS = 4             # threads computing the check's references


@dataclasses.dataclass
class Answer:
    step: int                     # the step that read it
    opening: int                  # the session (its opening number)
    chunks: int                   # chunks of its recording absorbed
    result: dict                  # the program's per-level result


@dataclasses.dataclass
class State:
    recordings: List[tuple]       # (types, times) host arrays
    bounds: List[np.ndarray]      # each recording's chunk starts (indices)
    miner_cfg: object
    server: object
    sessions: int
    per: int                      # steps between two openings
    n_chunks: int                 # chunks of a recording
    live: Dict[int, int] = dataclasses.field(default_factory=dict)
                                  # opening -> session id
    step: int = 0                 # the next step
    answers: List[Answer] = dataclasses.field(default_factory=list)
    errors: List[str] = dataclasses.field(default_factory=list)

    def stream(self, opening: int, chunks: int):
        """The first ``chunks`` chunks of ``opening``'s recording."""
        types, times = self.recordings[opening % len(self.recordings)]
        end = self.bounds[opening % len(self.recordings)][chunks]
        return types[:end], times[:end]


def setup(ctx) -> State:
    conf, traffic = ctx.config, ctx.traffic
    duration = float(traffic["recording_s"])
    chunk_s = float(traffic["chunk_s"])
    sessions = int(traffic["sessions"])
    n_chunks = int(round(duration / chunk_s))
    per = n_chunks // sessions
    if per * sessions != n_chunks:
        raise ValueError(f"{sessions} sessions do not stagger {n_chunks} "
                         f"chunks of {chunk_s} s evenly")
    order = common.catalog_order(ctx.seed, int(traffic["recordings"]))
    recordings = [spikes.simulate(common.network(conf, k), duration)
                  for k in order]
    edges = np.arange(n_chunks + 1) * chunk_s
    bounds = [np.searchsorted(times, edges, side="left") for _, times in
              recordings]
    for b, (types, _) in zip(bounds, recordings):
        b[-1] = types.size
    cap = int(conf["initial_cap"])
    cfg = common.miner_config(ctx.core, conf, duration, cap=cap)
    server = ctx.core.MiningSessionServer(
        conf["network"]["n_neurons"], cfg, max_sessions=sessions,
        initial_cap=cap)
    st = State(recordings, bounds, cfg, server, sessions, per, n_chunks)
    ctx.log(f"set-up: recordings {order} of {duration} s, events "
            f"{[ty.size for ty, _ in recordings]}, {sessions} sessions, "
            f"chunks of {chunk_s} s, a new session every {per} steps, "
            f"per-type cap {cap}, thresholds {cfg.threshold} "
            f"{cfg.level_thresholds}")
    t0 = time.perf_counter()
    for r in range(1, sessions):          # histories, one flush a round
        open_due(st, (r - 1) * per)
        feed(st, (r - 1) * per, r * per)
        server.flush()
    st.step = (sessions - 1) * per
    t1 = time.perf_counter()
    for _ in range(2 * per):              # two eviction periods
        step(st)
    st.answers.clear()
    t2 = time.perf_counter()
    seen = plans(ctx)
    warmed = ctx.core.warm(crossed(server, seen))
    pool = server.pool
    ctx.log(f"set-up: histories in {t1 - t0} s, {2 * per} steps in "
            f"{t2 - t1} s, {len(seen)} count plans dispatched; crossed "
            f"plans {warmed} in {time.perf_counter() - t2} s; pool of "
            f"{pool.n_slots} slots at cap {pool.cap}")
    return st


def crossed(server, seen):
    """The pool's count plans that pair, at one level, a batch class and
    a tail class that ``seen`` dispatched at that level. The warm and cold
    row families take their widths from maxima over the sessions, so a
    window step can pair classes that set-up met only apart."""
    batches, tails = {}, {}
    for p in seen:
        batches.setdefault(p.level, set()).add(p.batch)
        tails.setdefault(p.level, set()).add(p.tail_cap)
    every = sorted(set().union(*batches.values())) if batches else []
    return [p for p in server.plans(batches=every)
            if p.batch in batches.get(p.level, ())
            and p.tail_cap in tails.get(p.level, ())]


def plans(ctx):
    """The pool's count plans in the program's plan cache."""
    return {p for p in ctx.core.cached_plans()
            if p.fn == "count_corpus_tail_grouped"}


def open_due(st: State, g: int) -> None:
    """Before step ``g``: evict the sessions whose recording is used up,
    and open the session due at ``g``."""
    for o in [o for o in st.live if g - o * st.per == st.n_chunks]:
        st.server.evict(st.live.pop(o))
    if g % st.per == 0:
        st.live[g // st.per] = st.server.create_session()


def feed(st: State, g0: int, g1: int) -> int:
    """Append chunks ``g0 .. g1-1`` (run steps) of every live session as
    one append each; returns the events appended."""
    events = 0
    for o, sid in st.live.items():
        k = o % len(st.recordings)
        types, times = st.recordings[k]
        lo = st.bounds[k][max(g0 - o * st.per, 0)]
        hi = st.bounds[k][g1 - o * st.per]
        events += st.server.append(sid, types[lo:hi], times[lo:hi])
    return events


def step(st: State) -> int:
    """One step: a chunk to every session, one flush, every session's
    result read. Returns the events it absorbed."""
    g = st.step
    st.step += 1
    open_due(st, g)
    events = feed(st, g, g + 1)
    st.server.flush()
    for o, sid in st.live.items():
        st.answers.append(Answer(g, o, g + 1 - o * st.per,
                                 st.server.results(sid)))
    return events


def window(ctx, st: State):
    tracer = ctx.tracer
    compiles0 = ctx.compiles.count
    plans0 = plans(ctx)
    events = steps = failed = 0
    t0 = last = time.perf_counter()
    durations = []
    tracer.begin(t0)
    while True:
        g = st.step
        steps += 1
        with span("step"):
            try:
                events += step(st)
            except Exception as err:     # a failed step is counted
                failed += 1
                st.errors.append(repr(err))
        now = time.perf_counter()
        durations.append(now - last)
        last = now
        tracer.tick(now, unit=g)
        if now - t0 >= ctx.seconds:
            break
    elapsed = time.perf_counter() - t0
    for err in st.errors[:3]:
        ctx.log(f"failed step: {err}")
    new = sorted((p.level, p.batch, p.tail_cap) for p in plans(ctx) - plans0)
    ctx.log(f"window: {steps} steps ({events} events) in {elapsed} s, "
            f"{failed} failed; count plans compiled in the window "
            f"(level, batch, tail) {new}")
    q = np.percentile(durations, [0, 25, 50, 75, 100])
    ctx.log(f"window: seconds per step min/q1/median/q3/max {q.tolist()}")
    return Window(metrics={"mine_events_per_s": events / elapsed},
                  attempted=steps, failed=failed,
                  compiles=ctx.compiles.count - compiles0)


def check(ctx, st: State) -> Dict[str, tuple]:
    limit = ctx.config["limits"]["mismatched_entries"]
    chosen = [st.answers[k] for k in checked_answers(ctx.seed, st)]
    t0 = time.perf_counter()
    with ThreadPoolExecutor(REFERENCE_THREADS) as pool:
        want = {}
        for a in chosen:
            key = (a.opening, a.chunks)
            if key not in want:
                want[key] = pool.submit(common.reference_mine, ctx.config,
                                        st.miner_cfg, *st.stream(*key))
        fresh_bad = fresh(ctx, st, pool)
        bad = sum(reference.mismatches(common.as_levels(a.result),
                                       want[a.opening, a.chunks].result())
                  for a in chosen)
    ctx.log(f"check: {len(chosen)} of {len(st.answers)} answers, sessions "
            f"{sorted({a.opening for a in chosen})}, and the fresh recording "
            f"against the reference in {time.perf_counter() - t0} s")
    return {"mismatched_entries": (bad, limit),
            "fresh_recording_mismatched_entries": (fresh_bad, limit)}


def checked_answers(seed: int, st: State) -> List[int]:
    """The answers the check compares (indices into ``st.answers``): every
    session's at the window's last step, every one of the youngest session
    opened on a recycled slot, and ``SAMPLE`` more drawn from ``seed``."""
    if not st.answers:
        return []
    last = st.answers[-1].step
    recycled = [a.opening for a in st.answers if a.opening >= st.sessions]
    youngest = max(recycled, default=None)
    chosen = [k for k, a in enumerate(st.answers)
              if a.step == last or a.opening == youngest]
    rest = sorted(set(range(len(st.answers))) - set(chosen))
    rng = np.random.default_rng((seed % common.SEED_SPACE, 2))
    drawn = rng.choice(len(rest), size=min(SAMPLE, len(rest)), replace=False)
    return sorted(chosen + [rest[k] for k in drawn])


def fresh(ctx, st: State, pool: ThreadPoolExecutor) -> int:
    """Mismatched entries of a recording outside the catalog, served in the
    slot of the oldest session (references computed on ``pool``); a step
    that raises has every entry of the reference's result mismatched."""
    conf, server = ctx.config, st.server
    k = common.fresh_seed(ctx.seed, len(st.recordings))
    types, times = spikes.simulate(common.network(conf, k),
                                   float(ctx.traffic["recording_s"]))
    server.evict(st.live.pop(min(st.live)))
    sid = server.create_session()
    ends = np.searchsorted(
        times, np.arange(1, st.sessions) * st.per * float(
            ctx.traffic["chunk_s"]), side="left").tolist() + [types.size]
    t0 = time.perf_counter()
    answers = []
    start = 0
    for end in ends:
        want = pool.submit(common.reference_mine, conf, st.miner_cfg,
                           types[:end], times[:end])
        try:
            server.append(sid, types[start:end], times[start:end])
            got = common.as_levels(server.results(sid))
        except Exception as err:
            ctx.log(f"fresh recording: failed step: {err!r}")
            got = {}
        answers.append((got, want))
        start = end
    ctx.log(f"fresh recording: realization {k}, {types.size} events in "
            f"{len(ends)} flushes, served in {time.perf_counter() - t0} s "
            "(compiles included)")
    return sum(reference.mismatches(got, want.result())
               for got, want in answers)


def traced_bytes(ctx, st: State, units: List[int]) -> int:
    """Bytes the count kernel needed for the steps traced
    (``pool_bytes.step_bytes``, from the answers and the streams)."""
    by_key = {(a.step, a.opening): a for a in st.answers}
    rule = ctx.config["mining"]
    total = 0
    for g in units:
        for a in (a for a in st.answers if a.step == g):
            k = a.opening % len(st.recordings)
            first, end = st.bounds[k][a.chunks - 1:a.chunks + 1]
            if first == end:              # an empty chunk is not counted
                continue
            previous = by_key.get((g - 1, a.opening))
            types, times = st.stream(a.opening, a.chunks)
            total += pool_bytes.step_bytes(
                a.result, None if previous is None else previous.result,
                types, times, float(times[first]), t_high=rule["t_high"],
                n_types=ctx.config["network"]["n_neurons"],
                max_candidates=rule["max_candidates"])
    return total
