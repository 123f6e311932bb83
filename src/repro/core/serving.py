"""Multi-tenant streaming serving: one device-resident level loop for
thousands of concurrent append-only sessions (DESIGN.md §12).

The repo's two scale axes compose here. :class:`streaming.StreamingMiner`
(one stream, incremental appends) and :func:`corpus.mine_corpus` (many
streams, cold) each collapse their dimension into O(1) device programs —
but a serving process has BOTH dimensions live at once: many recording
sessions, each a growing stream, each wanting its full-stream result after
every chunk. Looping per-session miners pays the per-dispatch overhead
``S`` times per level; :class:`StreamingCorpusMiner` pays it once:

* **Session pool** — one ``[S, n_types, cap]`` per-type index pool holds
  every session's incremental index; all pending chunks scatter in ONE
  vmapped pass (:func:`events.type_index_update_batch`). Both the session
  axis and the shared per-type width are capacity classes
  (:func:`plan.capacity_class`), so ragged traffic — sessions of different
  ages, chunks of different sizes — reuses cached executables instead of
  recompiling mid-serve (the PR 4 lesson: per-unseen-shape recompiles
  dominate serving cost).

* **Grouped tail-delta flush** — per level, every dirty session's
  candidate frontier joins on host and counts against the pool through
  :func:`counting.count_corpus_tail_grouped`: PER-SESSION candidate rows
  (``symbols[S, B, N]``, session ``i`` paired with its own frontier — so
  dispatched rows stay proportional to the pool's real work even when a
  thousand sessions' frontiers diverge; a shared union would count every
  key against every session), per-session suffix cutoffs
  (``t_tail_start[S]``), per-session greedy chain-state carries, one
  dispatch family for warm rows (tail recount) and one for cold rows
  (backfill) — the cold family is the same plan shape with the degenerate
  ``-inf`` cutoff and an occupancy-class tail (not the table cap). All
  parts fetch in ONE ``device_get`` per level, the same budget as every
  batch miner in the repo.

* **Sessions are bit-for-bit solo miners** — a pooled session's per-level
  results equal a standalone :class:`StreamingMiner` fed the same chunks:
  the chunk acceptance rule (:func:`streaming.clean_chunk`), the f32
  suffix-cutoff slack (:func:`streaming.suffix_cutoff`), and the chain
  cache (:class:`streaming._ChainState` warmth rule) are the same code,
  and the pool's extra padding (+inf table columns/rows, repeated
  candidate rows) is inert by the DESIGN.md §5 conventions.
  Differentially enforced across engines x interleavings x churn in
  ``tests/test_serving.py``.

* **Spans** — each :meth:`StreamingCorpusMiner.flush` that absorbs data
  emits the profiler span ``repro.pool.flush`` (stats ``sessions``, the
  dirty sessions, and ``events``, the events absorbed) and, inside it,
  leaves that never overlap (``core/obs.py``): ``repro.pool.absorb``
  (coalesce, bincount, cap growth, the chunk matrix, the batched scatter's
  dispatch, per-session buffers and suffix sizing), then per level
  ``repro.pool.join`` (every session's candidate join),
  ``repro.pool.count`` (the warm/cold split, row and carry assembly and
  the dispatches; stats ``level``, ``warm`` and ``cold``, the real rows of
  each family, and ``padded``, the rows dispatched), ``repro.pool.sync``
  (the level's one ``device_get``) and ``repro.pool.carry`` (counts and
  chain states written back).

:class:`MiningSessionServer` is the serving front-end on top: opaque
session ids over recycled pool slots (continuous-batching style — the
``launch/serve.py`` slot-per-request pattern, claimed for mining), with
``create_session`` / ``append`` / ``evict`` / ``results`` and the
``plans()``/``warm()`` startup protocol so a warmed server provably never
compiles mid-serve (``plan.cache_stats()`` misses stay 0 — asserted in
``benchmarks/bench_serving.py``).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import counting
from . import events as events_lib
from . import obs
from . import plan as plan_mod
from .mining import (_OVERFLOW_MSG, LevelArrays, MinerConfig, _prune_level,
                     generate_candidates_arrays)
from .episodes import window_f32
from .streaming import _TAIL_SHORT_MSG, _ChainState, clean_chunk, suffix_cutoff


@dataclasses.dataclass
class _SlotState:
    """Host-side mining state of one live session (one pool slot).

    The per-slot twin of :class:`streaming.StreamingMiner`'s own fields:
    exact host count mirror, amortized-growth event buffers, per-level
    chain-state caches, and the per-session frequency threshold.
    """

    threshold: int
    counts: np.ndarray                      # int64[n_types] exact mirror
    buf_types: np.ndarray                   # host event copies (amortized)
    buf_times: np.ndarray
    n_events: int = 0
    last_time: float = -np.inf              # last ABSORBED event time
    pending_last: float = -np.inf           # last QUEUED event time
    seq: int = 0                            # flushes that absorbed data
    pending: List[Tuple[np.ndarray, np.ndarray]] = dataclasses.field(
        default_factory=list)
    cache: Dict[int, Dict[tuple, _ChainState]] = dataclasses.field(
        default_factory=dict)
    results: Optional[Dict[int, LevelArrays]] = None
    # scratch set by flush() between the scatter and the level loop
    t0: float = -np.inf

    @property
    def all_types(self) -> np.ndarray:
        return self.buf_types[:self.n_events]

    @property
    def all_times(self) -> np.ndarray:
        return self.buf_times[:self.n_events]


def _new_slot_state(n_types: int, threshold: int) -> _SlotState:
    return _SlotState(
        threshold=int(threshold),
        counts=np.zeros((n_types,), np.int64),
        buf_types=np.empty((1024,), np.int32),
        buf_times=np.empty((1024,), np.float32))


class StreamingCorpusMiner:
    """Device-resident session pool: batched incremental level-wise mining.

    Slot-indexed core (the front-end :class:`MiningSessionServer` maps
    session ids onto slots). ``open_slot``/``close_slot`` manage the pool,
    ``queue`` buffers validated chunks, and ``flush`` absorbs EVERY pending
    chunk in one batched level loop — O(levels) dispatches and host syncs
    for the whole pool, regardless of how many sessions appended.

    Args:
      n_types: shared event-type alphabet (level-1 results depend on it,
        so one pool serves one alphabet — same rule as ``mine_corpus``).
      cfg: the usual :class:`MinerConfig`; ``cfg.threshold`` is the
        default per-session threshold, ``cfg.cap`` seeds the initial
        per-type capacity (a growth hint, never a limit), ``cfg.mesh`` is
        rejected (the pool is single-device; shard POOLS, not slots).
      slots: initial slot-count hint (grows in capacity classes).
      initial_cap: overrides the initial per-type capacity.
      growth: per-type capacity growth factor (> 1).
    """

    def __init__(self, n_types: int, cfg: MinerConfig, *, slots: int = 1,
                 initial_cap: Optional[int] = None, growth: float = 2.0):
        if cfg.mesh is not None:
            raise ValueError("StreamingCorpusMiner is single-device; "
                             "cfg.mesh must be None")
        if growth <= 1.0:
            raise ValueError(f"growth must be > 1, got {growth}")
        if n_types < 1:
            raise ValueError(f"n_types must be >= 1, got {n_types}")
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        self.n_types = int(n_types)
        self.cfg = cfg
        self.growth = float(growth)
        if initial_cap is None:
            initial_cap = 256 if cfg.cap is None else cfg.cap
        self.cap = plan_mod.capacity_class(max(1, initial_cap))
        self.n_slots = plan_mod.capacity_class(slots)
        self.tables = jnp.full((self.n_slots, self.n_types, self.cap),
                               jnp.inf, jnp.float32)
        self.counts_dev = jnp.zeros((self.n_slots, self.n_types), jnp.int32)
        self._slots: List[Optional[_SlotState]] = [None] * self.n_slots
        self._free: List[int] = list(range(self.n_slots - 1, -1, -1))
        self._soiled: set = set()   # slots whose device rows hold old data

    # -- slot lifecycle ----------------------------------------------------

    def open_slot(self, *, threshold: Optional[int] = None) -> int:
        """Claim a slot (recycling freed ones; the pool doubles — one new
        capacity class, one new plan bucket — only when none is free)."""
        if not self._free:
            self._grow_slots()
        slot = self._free.pop()
        if slot in self._soiled:
            # recycled slot: wipe the previous tenant's device rows (host
            # state was dropped at close; fresh slots are already clean)
            self.tables = self.tables.at[slot].set(jnp.inf)
            self.counts_dev = self.counts_dev.at[slot].set(0)
            self._soiled.discard(slot)
        self._slots[slot] = _new_slot_state(
            self.n_types,
            self.cfg.threshold if threshold is None else threshold)
        return slot

    def close_slot(self, slot: int) -> None:
        """Free a slot: host state (pending included) is dropped now; the
        device rows are wiped lazily on recycle, so eviction costs no
        device work and a mid-level close cannot perturb other sessions."""
        self._slot_state(slot)
        self._slots[slot] = None
        self._free.append(slot)

    def live_slots(self) -> List[int]:
        return [i for i, st in enumerate(self._slots) if st is not None]

    def _slot_state(self, slot: int) -> _SlotState:
        if not (0 <= slot < self.n_slots) or self._slots[slot] is None:
            raise KeyError(f"slot {slot} is not open")
        return self._slots[slot]

    def _grow_slots(self) -> None:
        new_n = self.n_slots * 2
        pad = new_n - self.n_slots
        self.tables = jnp.concatenate(
            [self.tables, jnp.full((pad,) + self.tables.shape[1:], jnp.inf,
                                   jnp.float32)], axis=0)
        self.counts_dev = jnp.concatenate(
            [self.counts_dev, jnp.zeros((pad, self.n_types), jnp.int32)],
            axis=0)
        self._free.extend(range(new_n - 1, self.n_slots - 1, -1))
        self._slots.extend([None] * pad)
        self.n_slots = new_n

    # -- appends -----------------------------------------------------------

    def queue(self, slot: int, types, times) -> int:
        """Validate one chunk (eagerly — bad input must fail at the append
        call, not a later flush) and buffer it. Returns accepted events."""
        st = self._slot_state(slot)
        types, times = clean_chunk(types, times, self.n_types,
                                   st.pending_last)
        if types.size == 0:
            return 0
        st.pending.append((types, times))
        st.pending_last = float(times[-1])
        return int(types.size)

    def dirty_slots(self) -> List[int]:
        return [i for i, st in enumerate(self._slots)
                if st is not None and st.pending]

    # -- the batched absorb ------------------------------------------------

    def flush(self) -> None:
        """Absorb every pending chunk in ONE batched level loop.

        Chunks queued for one session coalesce into a single absorb —
        streaming results are chunking-invariant (the PR 5 differential
        property), so coalescing cannot change any session's results.
        """
        dirty = [(i, self._slots[i]) for i in self.dirty_slots()]
        if not dirty:
            return
        events = sum(int(c[0].size) for _, st in dirty for c in st.pending)
        with obs.span("pool.flush", sessions=len(dirty), events=events):
            with obs.span("pool.absorb"):
                tail_cap, old_counts_dev = self._absorb(dirty)
            self._mine_levels_pool(dirty, tail_cap, old_counts_dev)

    def _absorb(self, dirty):
        """Scatter every dirty session's coalesced chunk into the pool and
        update its host state; returns the shared warm tail width and the
        per-type totals before the chunks."""
        chunks: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        for i, st in dirty:
            ty = np.concatenate([c[0] for c in st.pending])
            tm = np.concatenate([c[1] for c in st.pending])
            st.pending.clear()
            chunks[i] = (ty, tm)

        # 1) pool-wide incremental index: grow-if-needed (geometric, then
        # class-aligned — O(log n) recompiles over the pool's life), then
        # scatter every session's chunk in one vmapped pass
        old_counts_dev = self.counts_dev
        needed = 0
        for i, st in dirty:
            st.counts = st.counts + np.bincount(chunks[i][0],
                                                minlength=self.n_types)
            needed = max(needed, int(st.counts.max()))
        if needed > self.cap:
            new_cap = self.cap
            while new_cap < needed:
                new_cap = max(new_cap + 1, int(new_cap * self.growth))
            new_cap = plan_mod.capacity_class(new_cap)
            self.tables = plan_mod.pad_width(self.tables, new_cap, jnp.inf)
            self.cap = new_cap
        # chunk matrix [S, M]: M class-rounded so ragged chunk sizes reuse
        # O(log) scatter programs; idle slots ride all-padding rows (no-op)
        m_max = max(c[0].size for c in chunks.values())
        m_cls = plan_mod.capacity_class(m_max, floor=16)
        ty_mat = np.full((self.n_slots, m_cls), -1, np.int32)
        tm_mat = np.full((self.n_slots, m_cls), np.inf, np.float32)
        for i, (ty, tm) in chunks.items():
            ty_mat[i, :ty.size] = ty
            tm_mat[i, :tm.size] = tm
        self.tables, self.counts_dev = events_lib.type_index_update_batch(
            self.tables, self.counts_dev, ty_mat, tm_mat)

        # 2) per-session host bookkeeping + span-bounded suffix sizing
        tail_need = 16
        for i, st in dirty:
            ty, tm = chunks[i]
            if st.n_events + ty.size > st.buf_times.size:
                new_size = max(st.n_events + int(ty.size),
                               2 * st.buf_times.size)
                st.buf_types = np.concatenate(
                    [st.all_types,
                     np.empty((new_size - st.n_events,), np.int32)])
                st.buf_times = np.concatenate(
                    [st.all_times,
                     np.empty((new_size - st.n_events,), np.float32)])
            st.buf_types[st.n_events:st.n_events + ty.size] = ty
            st.buf_times[st.n_events:st.n_events + tm.size] = tm
            st.n_events += int(ty.size)
            st.last_time = float(tm[-1])
            st.seq += 1
            st.t0 = suffix_cutoff(self.cfg, float(tm[0]), float(tm[-1]))
            i0 = int(np.searchsorted(st.all_times, st.t0, side="left"))
            suffix = np.bincount(st.all_types[i0:], minlength=self.n_types)
            tail_need = max(tail_need, int(suffix.max()))
            self._soiled.add(i)
        # ONE shared tail width (the max session's need, class-rounded):
        # a wider-than-needed view only appends +inf columns — inert, so
        # every session's counts stay bit-for-bit its solo miner's
        tail_cap = plan_mod.capacity_class(tail_need, floor=16)

        return tail_cap, old_counts_dev

    # -- level loop (each session mirrors streaming._mine_levels) ----------

    def _mine_levels_pool(self, dirty, tail_cap, old_counts_dev) -> None:
        cfg = self.cfg
        t0_vec = np.full((self.n_slots,), np.inf, np.float32)
        for i, st in dirty:
            t0_vec[i] = st.t0
        results: Dict[int, Dict[int, LevelArrays]] = {}
        frontier: Dict[int, np.ndarray] = {}
        running: Dict[int, bool] = {}
        for i, st in dirty:
            freq = np.nonzero(st.counts >= st.threshold)[0].astype(np.int32)
            results[i] = {1: _prune_level(freq, st.counts, self.n_types)}
            frontier[i] = freq[:, None]
            running[i] = True

        for level in range(2, cfg.max_level + 1):
            joined: Dict[int, np.ndarray] = {}
            with obs.span("pool.join", level=level):
                for i, st in dirty:
                    if not running[i]:
                        continue
                    if frontier[i].shape[0] == 0:
                        running[i] = False               # quiet: no record
                        continue
                    cands = generate_candidates_arrays(frontier[i], level,
                                                       cfg)
                    if cands.shape[0] == 0:
                        results[i][level] = LevelArrays(
                            np.zeros((0, level), np.int32),
                            np.zeros((0,), np.int32), 0)
                        running[i] = False
                        continue
                    joined[i] = cands
            if not joined:
                break
            counts_by_slot = self._count_level_pool(
                level, joined, t0_vec, tail_cap, old_counts_dev)
            override = (cfg.level_thresholds or {}).get(level)
            for i, cands in joined.items():
                st = self._slots[i]
                thr = st.threshold if override is None else override
                counts_h = counts_by_slot[i]
                keep = counts_h >= thr
                frontier[i] = cands[keep]
                results[i][level] = LevelArrays(
                    frontier[i], counts_h[keep].astype(np.int32),
                    cands.shape[0])

        for i, st in dirty:
            st.results = results[i]
            # a level this flush did not count keeps no chain state (the
            # streaming warmth rule: stale states can only recount cold);
            # a counted level's cache holds only this flush's states
            for level, cache in st.cache.items():
                if cache and next(iter(cache.values())).seq != st.seq:
                    st.cache[level] = {}

    def _count_level_pool(self, level, joined, t0_vec, tail_cap,
                          old_counts_dev) -> Dict[int, np.ndarray]:
        """Count one level for every dirty session: grouped dispatches.

        Each dispatch pairs session ``i`` with ITS OWN candidate rows
        (``symbols[S, B, N]``, the :func:`counting.count_corpus_tail_grouped`
        layout) — dispatched rows stay proportional to the work the pool
        actually needs even when sessions' frontiers diverge (a shared
        union of 1k diverse frontiers would count every key against every
        session). Warmth is per (session, episode) — session A can be warm
        on a key session B first reached this flush — so warm
        (tail-recount) and cold (full-backfill) row families dispatch
        separately; the cold family's tail is the pool's occupancy class,
        not the table cap. Unused rows of shorter sessions are computed and
        never read (the ``mine_corpus`` quiet-stream masking rule), and all
        chunks of both families fetch in ONE ``device_get``.
        """
        with obs.span("pool.count", level=level) as span:
            keys_of, dispatched, stats = self._dispatch_level(
                level, joined, t0_vec, tail_cap, old_counts_dev)
            span.set_metadata(**stats)
        with obs.span("pool.sync", level=level):
            fetched = jax.device_get(
                [[p[1] for p in parts] for parts in dispatched])  # ONE sync
        with obs.span("pool.carry", level=level):
            out: Dict[int, np.ndarray] = {
                i: np.zeros((len(keys_of[i]),), np.int64) for i in joined}
            states = {i: {} for i in joined}
            for parts, vals in zip(dispatched, fetched):
                for (sel, _), (cnt, end, _, ovf, short) in zip(parts, vals):
                    for i, rows in sel.items():
                        m = rows.size
                        if short[i, :m].any():
                            raise RuntimeError(_TAIL_SHORT_MSG)
                        if ovf[i, :m].any():
                            raise RuntimeError(
                                f"session slot {i}: {_OVERFLOW_MSG}")
                        seq = self._slots[i].seq
                        out[i][rows] = cnt[i, :m]
                        for j, r in enumerate(rows):
                            states[i][keys_of[i][r]] = _ChainState(
                                prev_end=float(end[i, j]),
                                count=int(cnt[i, j]), seq=seq)
            # the level's cache now holds only the states of this flush
            for i, level_states in states.items():
                self._slots[i].cache[level] = level_states
        return out

    def _dispatch_level(self, level, joined, t0_vec, tail_cap,
                        old_counts_dev):
        """Split one level's rows into the warm and cold families and
        dispatch both; returns each session's row keys, the dispatched
        parts (per family, ``(rows by slot, device outputs)`` per chunk)
        and the ``pool.count`` span's stats."""
        cfg = self.cfg
        keys_of: Dict[int, list] = {}
        warm_rows: Dict[int, np.ndarray] = {}
        cold_rows: Dict[int, np.ndarray] = {}
        cold_need = 0
        for i, cands in joined.items():
            st = self._slots[i]
            cache = st.cache.setdefault(level, {})
            keys = [tuple(int(x) for x in row) for row in cands]
            keys_of[i] = keys
            warm = np.array(
                [cache.get(k) is not None and cache[k].seq == st.seq - 1
                 for k in keys], bool)
            warm_rows[i] = np.nonzero(warm)[0]
            cold_rows[i] = np.nonzero(~warm)[0]
            if cold_rows[i].size:
                # a cold backfill reads this session's whole per-type
                # history, so the cold tail must cover its max occupancy
                cold_need = max(cold_need, int(st.counts.max()))

        knobs = dict(
            engine=cfg.engine, cap_occ=cfg.cap_occ, max_window=cfg.max_window,
            parallel_schedule=cfg.parallel_schedule, block_next=cfg.block_next,
            block_prev=cfg.block_prev, window_tiles=cfg.window_tiles,
            interpret=cfg.interpret)
        chunk = max(cfg.max_candidates, 1)
        cold_tail = plan_mod.capacity_class(cold_need, floor=16)
        dispatched = []   # (rows_of, chunk parts)
        widths = []       # the batch width of every dispatched chunk

        def family(rows_of, tail, t0, oc):
            """Dispatch one row family, chunked along the batch axis; all
            sessions advance through the chunks in lockstep (chunk k holds
            each session's rows [k*chunk, (k+1)*chunk) of the family)."""
            b_max = max(r.size for r in rows_of.values())
            if b_max == 0:
                return
            parts = []
            for start in range(0, b_max, chunk):
                # class-rounded chunk width (floor 16, the MAX_BATCH_PAD
                # discipline) so ragged last chunks reuse warmed buckets
                bc = plan_mod.capacity_class(
                    min(chunk, b_max - start), floor=16)
                widths.append(bc)
                sym = np.zeros((self.n_slots, bc, level), np.int32)
                pe = np.full((self.n_slots, bc), -np.inf, np.float32)
                pc = np.zeros((self.n_slots, bc), np.int32)
                sel = {}
                for i, rows in rows_of.items():
                    rows = rows[start:start + chunk]
                    if rows.size == 0:
                        continue
                    sel[i] = rows
                    sym[i, :rows.size] = joined[i][rows]
                    if oc is None:      # warm family: carried greedy state
                        cache = self._slots[i].cache[level]
                        for j, r in enumerate(rows):
                            cs = cache[keys_of[i][r]]
                            pe[i, j] = cs.prev_end
                            pc[i, j] = cs.count
                lo = np.full((bc, level - 1), window_f32(cfg.t_low))
                hi = np.full((bc, level - 1), window_f32(cfg.t_high))
                parts.append((sel, counting.count_corpus_tail_grouped(
                    self.tables, self.counts_dev,
                    old_counts_dev if oc is None else oc,
                    t0, sym, lo, hi, pe, pc, tail_cap=tail, **knobs)))
            dispatched.append(parts)

        family(warm_rows, tail_cap, t0_vec, None)
        # the degenerate tail: -inf cutoff + zero old_counts + an
        # occupancy-wide view == full stateful backfill, fresh carries
        family(cold_rows, cold_tail,
               np.full((self.n_slots,), -np.inf, np.float32),
               np.zeros((self.n_slots, self.n_types), np.int32))
        stats = dict(warm=sum(int(r.size) for r in warm_rows.values()),
                     cold=sum(int(r.size) for r in cold_rows.values()),
                     padded=self.n_slots * sum(widths))
        return keys_of, dispatched, stats

    # -- results / warm protocol -------------------------------------------

    def slot_results(self, slot: int) -> Dict[int, LevelArrays]:
        """This slot's per-level result. Flushes the WHOLE pool first if
        anything (any session) is pending — one batched absorb, not a
        private one. A never-appended session reports its (empty) level-1
        truthfully without touching the device."""
        st = self._slot_state(slot)
        if self.dirty_slots():
            self.flush()
        if st.results is None:
            # never-appended: the standalone cold `.results` path — mine
            # from scratch (all-cold, -inf cutoff; with any positive
            # threshold this records empty level 1 without device work)
            self._mine_levels_pool([(slot, st)], tail_cap=16,
                                   old_counts_dev=self.counts_dev)
        return dict(st.results)

    def plans(self, *, batches=None, tail_caps=()) -> List[
            plan_mod.MiningPlan]:
        """Every ``count_corpus_tail_grouped`` plan a flush can dispatch
        at the pool's CURRENT capacity classes, for :func:`plan.warm`.

        ``batches`` defaults to every candidate-batch class up to
        ``class(min(max_candidates, n_types^2))`` (the same default as
        ``plan.plans_for_miner``). Tail classes are enumerated completely:
        every flush tail — warm suffix need or cold occupancy — is class
        16..``cap``, so the default set covers every tail bucket this pool
        can ever dispatch (``tail_caps`` stays accepted for callers that
        want extra widths, e.g. ahead of a planned cap growth).
        """
        cfg = self.cfg
        if batches is None:
            top = plan_mod.capacity_class(
                min(cfg.max_candidates, self.n_types * self.n_types))
            batches = []
            b = 16
            while b <= top:
                batches.append(b)
                b *= 2
            batches = batches or [top]
        batches = sorted({plan_mod.pow2_ceil(int(b)) for b in batches})
        tcs = {plan_mod.capacity_class(int(t), floor=16) for t in tail_caps}
        t = 16
        while t <= self.cap:
            tcs.add(t)
            t *= 2
        tcs = sorted(tcs)
        knobs = dict(
            n_types=self.n_types, cap=self.cap, streams=self.n_slots,
            engine=cfg.engine, parallel_schedule=cfg.parallel_schedule,
            cap_occ=cfg.cap_occ, max_window=cfg.max_window,
            block_next=cfg.block_next, block_prev=cfg.block_prev,
            window_tiles=cfg.window_tiles, interpret=cfg.interpret)
        return [plan_mod.plan_for("count_corpus_tail_grouped", level=level,
                                  batch=b, tail_cap=tc, **knobs)
                for level in range(2, cfg.max_level + 1)
                for b in batches for tc in tcs]

    def warm(self, *, batches=None, tail_caps=()) -> Dict[str, int]:
        """Precompile this pool's plans (serving-startup protocol): a
        warmed pool whose capacities don't grow mid-serve pays ZERO
        compiles — and zero plan-cache misses — on live traffic."""
        return plan_mod.warm(self.plans(batches=batches,
                                        tail_caps=tail_caps))


class MiningSessionServer:
    """Session front-end over a :class:`StreamingCorpusMiner` pool.

    Opaque monotonically-increasing session ids map onto recycled pool
    slots (continuous-batching style: an evicted session frees its slot
    for the next ``create_session``; the pool only grows — one capacity
    class at a time — when every slot is live). Appends buffer per
    session and the next ``flush()`` (or any ``results()`` read) absorbs
    ALL of them in one batched device pass.

    The API a serving process needs and nothing else:
    ``create_session() -> sid``, ``append(sid, types, times)``,
    ``evict(sid)``, ``results(sid)``, plus ``flush()`` for explicit batch
    boundaries and ``plans()``/``warm()`` for the startup compile.
    """

    def __init__(self, n_types: int, cfg: MinerConfig, *,
                 max_sessions: int = 1, initial_cap: Optional[int] = None,
                 growth: float = 2.0):
        self.pool = StreamingCorpusMiner(
            n_types, cfg, slots=max_sessions, initial_cap=initial_cap,
            growth=growth)
        self._slot_of: Dict[int, int] = {}
        self._next_sid = 0

    # -- sessions ----------------------------------------------------------

    def create_session(self, *, threshold: Optional[int] = None) -> int:
        """Open a session; returns its id (never reused, unlike slots)."""
        slot = self.pool.open_slot(threshold=threshold)
        sid = self._next_sid
        self._next_sid += 1
        self._slot_of[sid] = slot
        return sid

    def append(self, sid: int, types, times) -> int:
        """Validate and buffer one chunk for ``sid`` (absorbed at the next
        flush). Returns the number of accepted (non-padding) events."""
        return self.pool.queue(self._slot(sid), types, times)

    def evict(self, sid: int) -> None:
        """End a session: drop its state (pending included) and recycle
        its slot. Further ``append``/``results`` calls for ``sid`` raise."""
        slot = self._slot(sid)
        del self._slot_of[sid]
        self.pool.close_slot(slot)

    def results(self, sid: int) -> Dict[int, LevelArrays]:
        """``sid``'s full-stream per-level result — bit-for-bit what a
        standalone ``StreamingMiner`` fed the same chunks returns.
        Triggers a pool flush if any session has pending chunks."""
        return self.pool.slot_results(self._slot(sid))

    def _slot(self, sid: int) -> int:
        if sid not in self._slot_of:
            raise KeyError(f"session {sid} does not exist (evicted?)")
        return self._slot_of[sid]

    def __len__(self) -> int:
        return len(self._slot_of)

    # -- pool passthrough --------------------------------------------------

    def flush(self) -> None:
        """Absorb every session's pending chunks in one batched pass."""
        self.pool.flush()

    def plans(self, **kw):
        return self.pool.plans(**kw)

    def warm(self, **kw):
        return self.pool.warm(**kw)
