"""Non-overlapped episode counting — the paper's redesigned algorithm (§IV).

``count_nonoverlapped`` = parallel local tracking (subproblem 1) + greedy
overlap resolution (subproblem 2). Tracking is dispatched through the
engine registry in tracking.py:

  engine="dense"                  beyond-paper optimized path (see tracking.py)
  engine="dense_pallas"           dense tracking with each level executed by
                                  the Pallas TPU kernel (kernels/episode_track)
                                  via kernels/ops.py; interpret mode off-TPU
  engine="dense_pallas_fused"     dense tracking for an entire candidate
                                  batch in ONE fused Pallas launch: levels
                                  carried in VMEM scratch, scan offsets
                                  read from per-row SMEM tables, dynamic walk;
                                  batched dispatch via ``track_batch``
  engine="count_scan_write"       paper's preferred lock-free pipeline:
                                  backward tracking + count/scan/write
                                  compaction; output auto-sorted by end time
  engine="atomic_sort"            AtomicCompact analogue: forward tracking +
                                  count/scan/write offsets (TPU has no global
                                  atomics) + one final end-time sort
  engine="flags"                  CudppCompact analogue: flag-scan compaction
                                  over the expanded slot array

All engines return identical counts (property-tested against the numpy FSM
oracle) and differ only in cost profile, mirroring the paper's Fig 11/12
method comparison. Kernel tiling knobs (``block_next``, ``block_prev``,
``window_tiles``, ``interpret``) thread from every public entry point down
to the engine; non-Pallas engines ignore them. Block knobs default to
``None`` = resolve through ``kernels.autotune`` (per-(L, N, B)-bucket tuned
tiles from ``kernels/tuned_configs.json``, legacy constants when no entry
exists); explicit integers bypass the table entirely.

Counting itself dispatches through :func:`count_batch_dispatch`: engines
exposing the natively-counting ``count_batch`` protocol method (the fused
Pallas engine) run tracking + count_scan_write compaction + the greedy
scheduler in ONE kernel launch per (level, candidate batch) — occurrence
intervals never round-trip through HBM; every other engine takes the
track-then-host-greedy path. Both produce bit-for-bit identical counts and
carried chain state.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import events as events_lib
from . import plan as plan_mod
from . import scheduling, tracking
from .episodes import Episode

def __getattr__(name: str):
    # ENGINES is a live view of the registry so engines added through
    # tracking.register_engine show up without re-importing (PEP 562).
    if name == "ENGINES":
        return tracking.engine_names()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


@dataclasses.dataclass
class CountResult:
    count: jax.Array        # i32 non-overlapped occurrence count
    n_superset: jax.Array   # i32 size of the tracked (overlapping) superset
    overflow: jax.Array     # bool static-capacity overflow indicator


def count_occurrences(
    times_by_sym: jax.Array,
    t_low: jax.Array,
    t_high: jax.Array,
    *,
    engine: str = "dense",
    cap_occ: Optional[int] = None,
    max_window: int = 32,
    parallel_schedule: bool = False,
    block_next: Optional[int] = None,
    block_prev: Optional[int] = None,
    window_tiles: Optional[int] = None,
    interpret: Optional[bool] = None,
    t_min=None,
) -> CountResult:
    """Count on pre-gathered per-symbol time tables (jit/vmap-friendly core).

    ``t_min`` (optional, traced) restricts the count to occurrences seeded
    at time >= ``t_min`` — equal to counting on the substream of events
    at/after the cutoff, for every engine (see EngineConfig.t_min).
    """
    eng = tracking.get_engine(engine)
    n, cap = times_by_sym.shape[-2], times_by_sym.shape[-1]
    bn, bp, wt, chunk = _resolve_tiles(
        eng, n - 1, cap, 1, block_next, block_prev, window_tiles)
    cfg = tracking.EngineConfig(
        cap_occ=cap_occ, max_window=max_window, block_next=bn,
        block_prev=bp, window_tiles=wt, chunk=chunk, interpret=interpret,
        t_min=t_min)
    count, _, n_superset, overflow = count_batch_dispatch(
        eng, times_by_sym[None], t_low[None], t_high[None],
        *_fresh_carries(1), cfg, parallel_schedule=parallel_schedule)
    return CountResult(
        count=count[0], n_superset=n_superset[0], overflow=overflow[0])


def count_nonoverlapped(
    stream: events_lib.EventStream,
    episode: Episode,
    *,
    engine: str = "dense",
    cap: Optional[int] = None,
    cap_occ: Optional[int] = None,
    max_window: int = 32,
    parallel_schedule: bool = False,
    block_next: Optional[int] = None,
    block_prev: Optional[int] = None,
    window_tiles: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> CountResult:
    """End-to-end count for one episode on one stream (public API)."""
    # `is None`, not `or`: an explicit cap=0 (or any falsy value) must be
    # honored — events.type_index rejects cap < 1 loudly — instead of
    # silently behaving like the unset default
    cap = max(1, stream.n_events) if cap is None else cap
    table, counts = events_lib.type_index(
        stream.types, stream.times, stream.n_types, cap)
    sym, lo, hi = episode.as_arrays()
    times_by_sym, _ = events_lib.episode_symbol_times(table, counts, sym)
    res = count_occurrences(
        times_by_sym, lo, hi, engine=engine, cap_occ=cap_occ,
        max_window=max_window, parallel_schedule=parallel_schedule,
        block_next=block_next, block_prev=block_prev,
        window_tiles=window_tiles, interpret=interpret)
    per_type_overflow = jnp.any(counts > cap)
    return CountResult(res.count, res.n_superset, res.overflow | per_type_overflow)


def _resolve_tiles(eng, levels: int, cap: int, batch: int,
                   block_next, block_prev, window_tiles):
    """(block_next, block_prev, window_tiles, chunk) for one count/track call.

    ``None`` knobs resolve through the autotune bucket table — kind
    ``"count"`` when the engine counts natively (the single-launch pipeline
    has its own tuned shapes), ``"track"`` otherwise; explicit integers win
    field-by-field. Resolution is trace-time only (shapes are static under
    jit), so the hot path pays a dict lookup, nothing more. Thin wrapper
    over :func:`plan.resolve_tiles` — the MiningPlan spine and the direct
    per-episode path must resolve identically.
    """
    bn, bp, wt, chunk, _ = plan_mod.resolve_tiles(
        eng, levels, cap, batch, block_next=block_next,
        block_prev=block_prev, window_tiles=window_tiles)
    return bn, bp, wt, chunk


def count_batch_dispatch(
    engine,                    # str name or TrackingEngine
    times_by_sym: jax.Array,   # f32[..., N, cap] sorted rows, +inf padded
    t_low: jax.Array,          # f32[..., N-1]
    t_high: jax.Array,         # f32[..., N-1]
    prev_end: jax.Array,       # f32[...] greedy carry in
    prev_count: jax.Array,     # i32[...] count carry in
    cfg: tracking.EngineConfig,
    *,
    parallel_schedule: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Batched counting through any engine — THE one counting dispatch.

    Engines exposing the native ``count_batch`` protocol method (see
    tracking.TrackingEngine) run the whole pipeline — tracking, compaction,
    greedy scheduling — in one kernel launch; everything else tracks via
    :func:`tracking.track_batch_dispatch` and folds the host-side greedy.
    ``cfg.t_min`` is consumed HERE (seed-row restriction), so no engine can
    double-apply it. The two schedulers are bit-identical including carried
    state (property-tested), so the in-kernel fold serves both
    ``parallel_schedule`` settings.

    Returns ``(counts i32[...], end_out f32[...], n_superset i32[...],
    overflow bool[...])`` with the ``(prev_end, prev_count)`` carry folded
    in. Stacked leading dims (a corpus) are folded into one batch axis and
    unfolded on the way out.
    """
    lead = times_by_sym.shape[:-2]
    if len(lead) > 1:
        import math as _math
        rows = _math.prod(lead)
        counts, end_out, nsup, ovf = count_batch_dispatch(
            engine, times_by_sym.reshape((rows,) + times_by_sym.shape[-2:]),
            t_low.reshape((rows,) + t_low.shape[-1:]),
            t_high.reshape((rows,) + t_high.shape[-1:]),
            jnp.reshape(prev_end, rows), jnp.reshape(prev_count, rows),
            cfg, parallel_schedule=parallel_schedule)
        return (counts.reshape(lead), end_out.reshape(lead),
                nsup.reshape(lead), ovf.reshape(lead))
    eng = tracking.get_engine(engine) if isinstance(engine, str) else engine
    times_by_sym, cfg = tracking.consume_seed_restriction(times_by_sym, cfg)
    count_batch = getattr(eng, "count_batch", None)
    if count_batch is not None:
        return count_batch(times_by_sym, t_low, t_high,
                           jnp.asarray(prev_end, jnp.float32),
                           jnp.asarray(prev_count, jnp.int32), cfg)
    occ = tracking.track_batch_dispatch(eng, times_by_sym, t_low, t_high, cfg)
    end_out, count_out = _greedy_batch_state(
        occ, prev_end, prev_count, parallel_schedule=parallel_schedule)
    return count_out, end_out, occ.n_superset, occ.overflow


def _greedy_batch_state(occ, prev_end, prev_count, parallel_schedule):
    """vmap the stateful greedy over batch-leading Occurrences.

    THE one greedy epilogue every batched counter shares — stateless
    callers pass fresh ``(-inf, 0)`` carries and drop the returned ends.
    Returns ``(end_out f32[B], count_out i32[B])``.
    """

    def schedule(starts, ends, valid, pe, pc):
        one = tracking.Occurrences(
            starts, ends, valid, jnp.int32(0), jnp.bool_(False))
        return scheduling.greedy_state(one, pe, pc, parallel=parallel_schedule)

    return jax.vmap(schedule)(
        occ.starts, occ.ends, occ.valid,
        jnp.asarray(prev_end, jnp.float32), jnp.asarray(prev_count, jnp.int32))


def _fresh_carries(batch: int):
    return (jnp.full((batch,), -jnp.inf, jnp.float32),
            jnp.zeros((batch,), jnp.int32))


# ---------------------------------------------------------------------------
# MiningPlan builders: the traced bodies behind the AOT executable cache.
# Each closes over a plan (static config) and reads batch/level/cap from its
# argument shapes; `plan.note_trace` inside the body makes trace counts ==
# compile counts observable (DESIGN.md §11). `build_cap` rides as a TRACED
# i32 scalar: adapters pad tables out to the plan's capacity class with
# +inf, so the overflow check must compare against the width the index was
# *built* at, not the padded width — bit-for-bit the unpadded semantics.
# ---------------------------------------------------------------------------


def _engine_cfg(p: plan_mod.MiningPlan, t_min=None) -> tracking.EngineConfig:
    return tracking.EngineConfig(
        cap_occ=p.cap_occ, max_window=p.max_window, block_next=p.block_next,
        block_prev=p.block_prev, window_tiles=p.window_tiles, chunk=p.chunk,
        interpret=p.interpret, t_min=t_min)


def _build_count_indexed(p: plan_mod.MiningPlan):
    def fn(table, counts, build_cap, symbols, t_low, t_high):
        plan_mod.note_trace(p)
        index_overflow = jnp.any(counts > build_cap)
        with jax.named_scope("table_gather"):
            rows = table[symbols]
        batch_counts, _, n_superset, overflow = count_batch_dispatch(
            tracking.get_engine(p.engine), rows, t_low, t_high,
            *_fresh_carries(symbols.shape[0]), _engine_cfg(p),
            parallel_schedule=p.parallel_schedule)
        return batch_counts, n_superset, overflow | index_overflow
    return fn


def _build_count_stateful(p: plan_mod.MiningPlan):
    def fn(table, counts, build_cap, symbols, t_low, t_high,
           prev_end, prev_count):
        plan_mod.note_trace(p)
        index_overflow = jnp.any(counts > build_cap)
        with jax.named_scope("table_gather"):
            rows = table[symbols]
        count_out, end_out, n_superset, overflow = count_batch_dispatch(
            tracking.get_engine(p.engine), rows, t_low, t_high,
            prev_end, prev_count, _engine_cfg(p),
            parallel_schedule=p.parallel_schedule)
        return count_out, end_out, n_superset, overflow | index_overflow
    return fn


def _build_count_tail(p: plan_mod.MiningPlan):
    tail_cap = p.tail_cap

    def fn(table, counts, old_counts, build_cap, t_tail_start,
           symbols, t_low, t_high, prev_end, prev_count):
        plan_mod.note_trace(p)
        cap = table.shape[1]
        t_tail_start = jnp.asarray(t_tail_start, jnp.float32)
        # per-type suffix offset: first indexed event at/after the cutoff
        # (one searchsorted over the [n_types, cap] table, not per row)
        suffix_start = jax.vmap(
            lambda row: jnp.searchsorted(row, t_tail_start, side="left"))(
            table).astype(jnp.int32)                       # [n_types]
        starts = suffix_start[symbols]                     # [B, N]
        starts = starts.at[:, -1].set(old_counts[symbols[:, -1]])
        # clip at build_cap, not the padded width: entries past the build
        # width never existed, so they must not inflate the suffix need
        needed = jnp.minimum(counts, build_cap)[symbols] - starts
        tail_short = jnp.any(needed > tail_cap, axis=-1)   # [B]
        idx = starts[:, :, None] + jnp.arange(tail_cap, dtype=jnp.int32)
        with jax.named_scope("table_gather"):
            view = table[symbols[:, :, None], jnp.minimum(idx, cap - 1)]
            view = jnp.where(idx < cap, view, jnp.inf)     # [B, N, tail_cap]

        index_overflow = jnp.any(counts > build_cap)
        count_out, end_out, n_superset, overflow = count_batch_dispatch(
            tracking.get_engine(p.engine), view, t_low, t_high,
            prev_end, prev_count, _engine_cfg(p, t_min=t_tail_start),
            parallel_schedule=p.parallel_schedule)
        return (count_out, end_out, n_superset,
                overflow | index_overflow, tail_short)
    return fn


def _build_count_corpus(p: plan_mod.MiningPlan):
    def fn(tables, counts, build_cap, symbols, t_low, t_high, thresholds):
        plan_mod.note_trace(p)
        s, b = tables.shape[0], symbols.shape[0]
        index_overflow = jnp.any(counts > build_cap, axis=-1)   # [S]
        eng = tracking.get_engine(p.engine)
        cfg = _engine_cfg(p)
        with jax.named_scope("table_gather"):
            rows = tables[:, symbols]
        if getattr(eng, "count_batch", None) is not None:
            # corpus-native counting: (stream, episode) rows fold into ONE
            # single-launch count pipeline call — fresh carries, stateless
            corpus_counts, _, n_superset, overflow = count_batch_dispatch(
                eng, rows,
                jnp.broadcast_to(t_low[None], (s,) + t_low.shape),
                jnp.broadcast_to(t_high[None], (s,) + t_high.shape),
                jnp.full((s, b), -jnp.inf, jnp.float32),
                jnp.zeros((s, b), jnp.int32), cfg,
                parallel_schedule=p.parallel_schedule)
        else:
            occ = tracking.track_corpus_dispatch(
                eng, rows, t_low, t_high, cfg)

            def schedule(starts, ends, valid):
                one = tracking.Occurrences(
                    starts, ends, valid, jnp.int32(0), jnp.bool_(False))
                return scheduling.greedy_count(
                    one, parallel=p.parallel_schedule)

            corpus_counts = jax.vmap(jax.vmap(schedule))(
                occ.starts, occ.ends, occ.valid)
            n_superset, overflow = occ.n_superset, occ.overflow
        keep = corpus_counts >= thresholds.astype(jnp.int32)[:, None]
        return (corpus_counts, keep, n_superset,
                overflow | index_overflow[:, None])
    return fn


def _build_count_corpus_tail(p: plan_mod.MiningPlan):
    tail_cap = p.tail_cap

    def fn(tables, counts, old_counts, build_cap, t_tail_start,
           symbols, t_low, t_high, prev_end, prev_count):
        plan_mod.note_trace(p)
        cap = tables.shape[2]
        s, b = tables.shape[0], symbols.shape[0]
        t_tail_start = jnp.asarray(t_tail_start, jnp.float32)
        # per-(session, type) suffix offset: each session's own cutoff over
        # its own table rows (one nested searchsorted over [S, n_types, cap])
        suffix_start = jax.vmap(
            lambda tbl, t0: jax.vmap(
                lambda row: jnp.searchsorted(row, t0, side="left"))(tbl))(
            tables, t_tail_start).astype(jnp.int32)        # [S, n_types]
        starts = suffix_start[:, symbols]                  # [S, B, N]
        starts = starts.at[:, :, -1].set(old_counts[:, symbols[:, -1]])
        needed = jnp.minimum(counts, build_cap)[:, symbols] - starts
        tail_short = jnp.any(needed > tail_cap, axis=-1)   # [S, B]
        idx = starts[..., None] + jnp.arange(tail_cap, dtype=jnp.int32)
        stream_ix = jnp.arange(s, dtype=jnp.int32)[:, None, None, None]
        with jax.named_scope("table_gather"):
            view = tables[stream_ix, symbols[None, :, :, None],
                          jnp.minimum(idx, cap - 1)]
            view = jnp.where(idx < cap, view, jnp.inf)  # [S, B, N, tail_cap]
        # no t_min here: each session's seed row already starts at its own
        # suffix_start, so the scalar seed restriction count_tail threads
        # through EngineConfig is a provable no-op on this view (the shift
        # restrict_seed_row computes is 0 for every row) — and a per-session
        # t_min could not ride a single EngineConfig scalar anyway
        index_overflow = jnp.any(counts > build_cap, axis=-1)   # [S]
        count_out, end_out, n_superset, overflow = count_batch_dispatch(
            tracking.get_engine(p.engine), view,
            jnp.broadcast_to(t_low[None], (s,) + t_low.shape),
            jnp.broadcast_to(t_high[None], (s,) + t_high.shape),
            prev_end, prev_count, _engine_cfg(p),
            parallel_schedule=p.parallel_schedule)
        return (count_out, end_out, n_superset,
                overflow | index_overflow[:, None], tail_short)
    return fn


def _build_count_corpus_tail_grouped(p: plan_mod.MiningPlan):
    tail_cap = p.tail_cap

    def fn(tables, counts, old_counts, build_cap, t_tail_start,
           symbols, t_low, t_high, prev_end, prev_count):
        plan_mod.note_trace(p)
        cap = tables.shape[2]
        s = tables.shape[0]
        t_tail_start = jnp.asarray(t_tail_start, jnp.float32)
        suffix_start = jax.vmap(
            lambda tbl, t0: jax.vmap(
                lambda row: jnp.searchsorted(row, t0, side="left"))(tbl))(
            tables, t_tail_start).astype(jnp.int32)        # [S, n_types]
        # symbols are per-session here ([S, B, N], each session its own
        # candidate rows) so every gather pairs session s with ITS symbols
        starts = jax.vmap(lambda ss, sym: ss[sym])(
            suffix_start, symbols)                         # [S, B, N]
        starts = starts.at[:, :, -1].set(
            jax.vmap(lambda oc, last: oc[last])(old_counts, symbols[:, :, -1]))
        totals = jax.vmap(lambda c, sym: c[sym])(
            jnp.minimum(counts, build_cap), symbols)       # [S, B, N]
        needed = totals - starts
        tail_short = jnp.any(needed > tail_cap, axis=-1)   # [S, B]
        idx = starts[..., None] + jnp.arange(tail_cap, dtype=jnp.int32)
        stream_ix = jnp.arange(s, dtype=jnp.int32)[:, None, None, None]
        with jax.named_scope("table_gather"):
            view = tables[stream_ix, symbols[..., None],
                          jnp.minimum(idx, cap - 1)]
            view = jnp.where(idx < cap, view, jnp.inf)  # [S, B, N, tail_cap]
        # same no-t_min argument as count_corpus_tail: each row's seed view
        # already begins at its own suffix_start, so seed restriction is a
        # provable no-op
        index_overflow = jnp.any(counts > build_cap, axis=-1)   # [S]
        count_out, end_out, n_superset, overflow = count_batch_dispatch(
            tracking.get_engine(p.engine), view,
            jnp.broadcast_to(t_low[None], (s,) + t_low.shape),
            jnp.broadcast_to(t_high[None], (s,) + t_high.shape),
            prev_end, prev_count, _engine_cfg(p),
            parallel_schedule=p.parallel_schedule)
        return (count_out, end_out, n_superset,
                overflow | index_overflow[:, None], tail_short)
    return fn


def _specs_count_indexed(p):
    S, f32, i32 = jax.ShapeDtypeStruct, jnp.float32, jnp.int32
    return (S((p.n_types, p.cap), f32), S((p.n_types,), i32), S((), i32),
            S((p.batch, p.level), i32), S((p.batch, p.level - 1), f32),
            S((p.batch, p.level - 1), f32))


def _specs_count_stateful(p):
    S, f32, i32 = jax.ShapeDtypeStruct, jnp.float32, jnp.int32
    return _specs_count_indexed(p) + (S((p.batch,), f32), S((p.batch,), i32))


def _specs_count_tail(p):
    S, f32, i32 = jax.ShapeDtypeStruct, jnp.float32, jnp.int32
    return (S((p.n_types, p.cap), f32), S((p.n_types,), i32),
            S((p.n_types,), i32), S((), i32), S((), f32),
            S((p.batch, p.level), i32), S((p.batch, p.level - 1), f32),
            S((p.batch, p.level - 1), f32), S((p.batch,), f32),
            S((p.batch,), i32))


def _specs_count_corpus(p):
    S, f32, i32 = jax.ShapeDtypeStruct, jnp.float32, jnp.int32
    return (S((p.streams, p.n_types, p.cap), f32),
            S((p.streams, p.n_types), i32), S((), i32),
            S((p.batch, p.level), i32), S((p.batch, p.level - 1), f32),
            S((p.batch, p.level - 1), f32), S((p.streams,), i32))


def _specs_count_corpus_tail(p):
    S, f32, i32 = jax.ShapeDtypeStruct, jnp.float32, jnp.int32
    return (S((p.streams, p.n_types, p.cap), f32),
            S((p.streams, p.n_types), i32), S((p.streams, p.n_types), i32),
            S((), i32), S((p.streams,), f32),
            S((p.batch, p.level), i32), S((p.batch, p.level - 1), f32),
            S((p.batch, p.level - 1), f32), S((p.streams, p.batch), f32),
            S((p.streams, p.batch), i32))


plan_mod.register_fn("count_indexed", _build_count_indexed,
                     _specs_count_indexed)
plan_mod.register_fn("count_stateful", _build_count_stateful,
                     _specs_count_stateful)
plan_mod.register_fn("count_tail", _build_count_tail, _specs_count_tail)
plan_mod.register_fn("count_corpus", _build_count_corpus, _specs_count_corpus)
def _specs_count_corpus_tail_grouped(p):
    S, f32, i32 = jax.ShapeDtypeStruct, jnp.float32, jnp.int32
    return (S((p.streams, p.n_types, p.cap), f32),
            S((p.streams, p.n_types), i32), S((p.streams, p.n_types), i32),
            S((), i32), S((p.streams,), f32),
            S((p.streams, p.batch, p.level), i32),
            S((p.batch, p.level - 1), f32), S((p.batch, p.level - 1), f32),
            S((p.streams, p.batch), f32), S((p.streams, p.batch), i32))


plan_mod.register_fn("count_corpus_tail", _build_count_corpus_tail,
                     _specs_count_corpus_tail)
plan_mod.register_fn("count_corpus_tail_grouped",
                     _build_count_corpus_tail_grouped,
                     _specs_count_corpus_tail_grouped)


# ---------------------------------------------------------------------------
# Public batched entries: thin adapters over the MiningPlan dispatch spine.
# Each resolves a plan (shapes rounded to capacity classes), pads inputs to
# the bucket (+inf table columns / repeated candidate rows — both inert by
# the DESIGN.md §5 padding conventions), dispatches the cached executable,
# and slices the true rows back out. Signatures are unchanged from the
# pre-plan jitted versions; `build_cap` is new (default: the incoming table
# width, i.e. exactly the old overflow semantics).
# ---------------------------------------------------------------------------


def _plan_knobs(engine, parallel_schedule, cap_occ, max_window, block_next,
                block_prev, window_tiles, interpret):
    return dict(engine=engine, parallel_schedule=parallel_schedule,
                cap_occ=cap_occ, max_window=max_window, block_next=block_next,
                block_prev=block_prev, window_tiles=window_tiles,
                interpret=interpret)


def count_batch_indexed(
    table: jax.Array,       # f32[n_types, cap] per-type time index
    counts: jax.Array,      # i32[n_types] true per-type totals (pre-clip)
    symbols: jax.Array,     # i32[B, N]
    t_low: jax.Array,       # f32[B, N-1]
    t_high: jax.Array,      # f32[B, N-1]
    *,
    engine: str = "dense",
    cap_occ: Optional[int] = None,
    max_window: int = 32,
    parallel_schedule: bool = False,
    block_next: Optional[int] = None,
    block_prev: Optional[int] = None,
    window_tiles: Optional[int] = None,
    interpret: Optional[bool] = None,
    build_cap: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Count a batch of same-length episodes on a *pre-built* type index.

    The miner builds the index once per stream and calls this for every
    level — the paper's pre-processing amortization extended across the
    whole level-wise search. Returns (counts[B], n_superset[B], overflow[B]).

    Adapter over the MiningPlan spine (plan.py): the (level, cap-class,
    batch-class, engine, knobs) bucket maps to ONE cached AOT executable,
    so ragged shapes compile O(#buckets) times. ``build_cap`` is the width
    the index was built at when the caller pre-padded the table to a
    capacity class (default: the table's width). Counting goes through
    :func:`count_batch_dispatch`: engines exposing the natively-counting
    ``count_batch`` protocol method run tracking + compaction + greedy
    scheduling in ONE kernel launch per (level, batch).
    """
    table = jnp.asarray(table, jnp.float32)
    counts = jnp.asarray(counts, jnp.int32)
    symbols = jnp.asarray(symbols, jnp.int32)
    t_low = jnp.asarray(t_low, jnp.float32)
    t_high = jnp.asarray(t_high, jnp.float32)
    if build_cap is None:
        build_cap = table.shape[1]
    b, n = symbols.shape
    if b == 0:
        z = jnp.zeros((0,), jnp.int32)
        return z, z, jnp.zeros((0,), bool)
    p = plan_mod.plan_for(
        "count_indexed", level=n, n_types=table.shape[0],
        cap=table.shape[1], batch=b,
        **_plan_knobs(engine, parallel_schedule, cap_occ, max_window,
                      block_next, block_prev, window_tiles, interpret))
    out = plan_mod.dispatch(
        p, plan_mod.pad_width(table, p.cap, jnp.inf), counts,
        jnp.asarray(build_cap, jnp.int32),
        plan_mod.pad_rows(symbols, p.batch),
        plan_mod.pad_rows(t_low, p.batch), plan_mod.pad_rows(t_high, p.batch))
    return tuple(a[:b] for a in out)


def count_batch_indexed_stateful(
    table: jax.Array,       # f32[n_types, cap] per-type time index
    counts: jax.Array,      # i32[n_types] true per-type totals (pre-clip)
    symbols: jax.Array,     # i32[B, N]
    t_low: jax.Array,       # f32[B, N-1]
    t_high: jax.Array,      # f32[B, N-1]
    prev_end: jax.Array,    # f32[B] greedy carry in (-inf for a fresh scan)
    prev_count: jax.Array,  # i32[B] count carry in (0 for a fresh scan)
    *,
    engine: str = "dense",
    cap_occ: Optional[int] = None,
    max_window: int = 32,
    parallel_schedule: bool = False,
    block_next: Optional[int] = None,
    block_prev: Optional[int] = None,
    window_tiles: Optional[int] = None,
    interpret: Optional[bool] = None,
    build_cap: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """:func:`count_batch_indexed` that threads the greedy chain state.

    Same tracking, same counts — but the scheduler is seeded with
    ``(prev_end, prev_count)`` per episode and the final carry is returned,
    so a caller can resume the fold later over intervals that all end at or
    after this call's (the streaming miner's cold *backfill* path: a newly
    frequent candidate is counted once over the whole indexed history with
    a fresh carry, then kept warm by tail-delta recounts).

    Returns ``(counts[B], prev_end[B], n_superset[B], overflow[B])``.
    """
    table = jnp.asarray(table, jnp.float32)
    counts = jnp.asarray(counts, jnp.int32)
    symbols = jnp.asarray(symbols, jnp.int32)
    t_low = jnp.asarray(t_low, jnp.float32)
    t_high = jnp.asarray(t_high, jnp.float32)
    prev_end = jnp.asarray(prev_end, jnp.float32)
    prev_count = jnp.asarray(prev_count, jnp.int32)
    if build_cap is None:
        build_cap = table.shape[1]
    b, n = symbols.shape
    if b == 0:
        zi = jnp.zeros((0,), jnp.int32)
        return zi, jnp.zeros((0,), jnp.float32), zi, jnp.zeros((0,), bool)
    p = plan_mod.plan_for(
        "count_stateful", level=n, n_types=table.shape[0],
        cap=table.shape[1], batch=b,
        **_plan_knobs(engine, parallel_schedule, cap_occ, max_window,
                      block_next, block_prev, window_tiles, interpret))
    out = plan_mod.dispatch(
        p, plan_mod.pad_width(table, p.cap, jnp.inf), counts,
        jnp.asarray(build_cap, jnp.int32),
        plan_mod.pad_rows(symbols, p.batch),
        plan_mod.pad_rows(t_low, p.batch), plan_mod.pad_rows(t_high, p.batch),
        plan_mod.pad_rows(prev_end, p.batch),
        plan_mod.pad_rows(prev_count, p.batch))
    return tuple(a[:b] for a in out)


def count_tail_batch_indexed(
    table: jax.Array,       # f32[n_types, cap] per-type time index (updated)
    counts: jax.Array,      # i32[n_types] per-type totals incl. the new chunk
    old_counts: jax.Array,  # i32[n_types] per-type totals BEFORE the chunk
    t_tail_start: jax.Array,  # f32 scalar: suffix cutoff (t_chunk0 - span)
    symbols: jax.Array,     # i32[B, N]
    t_low: jax.Array,       # f32[B, N-1]
    t_high: jax.Array,      # f32[B, N-1]
    prev_end: jax.Array,    # f32[B] greedy carry through the OLD stream
    prev_count: jax.Array,  # i32[B]
    *,
    tail_cap: int,
    engine: str = "dense",
    cap_occ: Optional[int] = None,
    max_window: int = 32,
    parallel_schedule: bool = False,
    block_next: Optional[int] = None,
    block_prev: Optional[int] = None,
    window_tiles: Optional[int] = None,
    interpret: Optional[bool] = None,
    build_cap: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Tail-delta recount: only what one appended chunk can change.

    An occurrence ending at a chunk event spans at most ``span = sum(hi)``
    back in time, so every event of every such occurrence lies in the
    stream suffix at/after ``t_tail_start = t_chunk0 - span`` (DESIGN.md
    §9). This entry gathers a ``tail_cap``-wide *view* of each symbol row —
    the suffix events for inner symbols, ONLY the chunk's new events for
    the final symbol (slicing at ``old_counts`` is what keeps duplicate
    boundary timestamps exact: an old end event tied at the chunk's first
    time belongs to the already-cached history, not the delta) — tracks it
    with any registered engine, and folds the resulting intervals onto the
    carried greedy state. Work is O(B * N * tail_cap * log tail_cap),
    independent of the indexed stream length. ``tail_cap`` is semantic (it
    bounds ``tail_short``), so the plan bucket keeps it exact — the
    streaming miner already sizes it in capacity classes.

    Returns ``(counts[B], prev_end[B], n_superset[B], overflow[B],
    tail_short[B])``; ``tail_short`` flags a view too narrow for some
    symbol's suffix (the caller re-runs with a wider ``tail_cap`` — flagged,
    never silently wrong, same convention as every other capacity miss).
    """
    table = jnp.asarray(table, jnp.float32)
    counts = jnp.asarray(counts, jnp.int32)
    old_counts = jnp.asarray(old_counts, jnp.int32)
    symbols = jnp.asarray(symbols, jnp.int32)
    t_low = jnp.asarray(t_low, jnp.float32)
    t_high = jnp.asarray(t_high, jnp.float32)
    prev_end = jnp.asarray(prev_end, jnp.float32)
    prev_count = jnp.asarray(prev_count, jnp.int32)
    if build_cap is None:
        build_cap = table.shape[1]
    b, n = symbols.shape
    if b == 0:
        zi = jnp.zeros((0,), jnp.int32)
        zb = jnp.zeros((0,), bool)
        return zi, jnp.zeros((0,), jnp.float32), zi, zb, zb
    p = plan_mod.plan_for(
        "count_tail", level=n, n_types=table.shape[0], cap=table.shape[1],
        batch=b, tail_cap=int(tail_cap),
        **_plan_knobs(engine, parallel_schedule, cap_occ, max_window,
                      block_next, block_prev, window_tiles, interpret))
    out = plan_mod.dispatch(
        p, plan_mod.pad_width(table, p.cap, jnp.inf), counts, old_counts,
        jnp.asarray(build_cap, jnp.int32),
        jnp.asarray(t_tail_start, jnp.float32),
        plan_mod.pad_rows(symbols, p.batch),
        plan_mod.pad_rows(t_low, p.batch), plan_mod.pad_rows(t_high, p.batch),
        plan_mod.pad_rows(prev_end, p.batch),
        plan_mod.pad_rows(prev_count, p.batch))
    return tuple(a[:b] for a in out)


def count_corpus_indexed(
    tables: jax.Array,      # f32[S, n_types, cap] per-stream type indexes
    counts: jax.Array,      # i32[S, n_types] true per-type totals (pre-clip)
    symbols: jax.Array,     # i32[B, N] shared candidate batch
    t_low: jax.Array,       # f32[B, N-1]
    t_high: jax.Array,      # f32[B, N-1]
    thresholds: jax.Array,  # i32[S] per-stream frequency thresholds
    *,
    engine: str = "dense",
    cap_occ: Optional[int] = None,
    max_window: int = 32,
    parallel_schedule: bool = False,
    block_next: Optional[int] = None,
    block_prev: Optional[int] = None,
    window_tiles: Optional[int] = None,
    interpret: Optional[bool] = None,
    build_cap: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Count one candidate batch against a whole corpus of streams at once.

    The stream axis of the *pre-built* batched type index
    (:func:`events.type_index_batch`) rides through tracking as a fold into
    the candidate-batch dimension (:func:`tracking.track_corpus_dispatch`):
    with a corpus-native engine the entire ``S x B`` grid is ONE kernel
    launch per mining level, and every stream's keep mask is computed on
    device against its own threshold — the corpus miner fetches (counts,
    keep, overflow) for all streams in a single per-level host sync.

    Adapter over the MiningPlan spine: the stream axis rounds to its own
    capacity class (padded streams are all-+inf — they track nothing and
    their rows are sliced away), so corpora of nearby sizes share one
    executable.

    Returns ``(counts i32[S, B], keep bool[S, B], n_superset i32[S, B],
    overflow bool[S, B])``. Per-row results are bit-for-bit what
    :func:`count_batch_indexed` returns for that stream alone — tracking,
    scheduling, and overflow math are per-(stream, episode)-row, so batch
    composition cannot perturb them (differentially tested).
    """
    tables = jnp.asarray(tables, jnp.float32)
    counts = jnp.asarray(counts, jnp.int32)
    symbols = jnp.asarray(symbols, jnp.int32)
    t_low = jnp.asarray(t_low, jnp.float32)
    t_high = jnp.asarray(t_high, jnp.float32)
    thresholds = jnp.asarray(thresholds, jnp.int32)
    if thresholds.shape[0] != tables.shape[0]:
        raise ValueError(
            f"thresholds must have shape ({tables.shape[0]},), got "
            f"{thresholds.shape}")
    if build_cap is None:
        build_cap = tables.shape[2]
    s, b = tables.shape[0], symbols.shape[0]
    if b == 0:
        zi = jnp.zeros((s, 0), jnp.int32)
        zb = jnp.zeros((s, 0), bool)
        return zi, zb, zi, zb
    p = plan_mod.plan_for(
        "count_corpus", level=symbols.shape[1], n_types=tables.shape[1],
        cap=tables.shape[2], batch=b, streams=s,
        **_plan_knobs(engine, parallel_schedule, cap_occ, max_window,
                      block_next, block_prev, window_tiles, interpret))
    tables = plan_mod.pad_width(tables, p.cap, jnp.inf)
    if p.streams != s:
        # padded streams are empty (+inf index, zero counts, zero
        # thresholds): they count nothing and their rows are sliced away
        tables = jnp.concatenate(
            [tables, jnp.full((p.streams - s,) + tables.shape[1:], jnp.inf,
                              jnp.float32)], axis=0)
        counts = jnp.concatenate(
            [counts, jnp.zeros((p.streams - s, counts.shape[1]), jnp.int32)],
            axis=0)
        thresholds = jnp.concatenate(
            [thresholds, jnp.zeros((p.streams - s,), jnp.int32)], axis=0)
    out = plan_mod.dispatch(
        p, tables, counts, jnp.asarray(build_cap, jnp.int32),
        plan_mod.pad_rows(symbols, p.batch),
        plan_mod.pad_rows(t_low, p.batch), plan_mod.pad_rows(t_high, p.batch),
        thresholds)
    return tuple(a[:s, :b] for a in out)


def _pad_cols(arr: jax.Array, target: int) -> jax.Array:
    """Pad axis 1 to ``target`` by repeating column 0 (the carry twin of
    ``plan.pad_rows``: padded candidate rows repeat episode 0, so their
    carries must repeat episode 0's carry — computed, then discarded)."""
    b = arr.shape[1]
    if b == target:
        return jnp.asarray(arr)
    reps = jnp.broadcast_to(jnp.asarray(arr)[:, :1],
                            (arr.shape[0], target - b) + arr.shape[2:])
    return jnp.concatenate([jnp.asarray(arr), reps], axis=1)


def count_corpus_tail_indexed(
    tables: jax.Array,       # f32[S, n_types, cap] per-session type indexes
    counts: jax.Array,       # i32[S, n_types] totals incl. the new chunks
    old_counts: jax.Array,   # i32[S, n_types] totals BEFORE the chunks
    t_tail_start: jax.Array,  # f32[S] per-session suffix cutoffs
    symbols: jax.Array,      # i32[B, N] shared (union) candidate batch
    t_low: jax.Array,        # f32[B, N-1]
    t_high: jax.Array,       # f32[B, N-1]
    prev_end: jax.Array,     # f32[S, B] per-(session, episode) greedy carry
    prev_count: jax.Array,   # i32[S, B]
    *,
    tail_cap: int,
    engine: str = "dense",
    cap_occ: Optional[int] = None,
    max_window: int = 32,
    parallel_schedule: bool = False,
    block_next: Optional[int] = None,
    block_prev: Optional[int] = None,
    window_tiles: Optional[int] = None,
    interpret: Optional[bool] = None,
    build_cap: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Tail-delta recount of one candidate batch against a session pool.

    The serving miner's workhorse (:class:`serving.StreamingCorpusMiner`):
    :func:`count_tail_batch_indexed` with the stream axis of
    :func:`count_corpus_indexed` — each session's suffix view is cut at its
    OWN ``t_tail_start`` / ``old_counts`` and folded onto its own carried
    greedy state, but the whole ``S x B`` grid dispatches as ONE cached
    executable (with a corpus-native engine, one kernel launch).

    Two degenerate settings make this the only counting entry a serving
    flush needs: ``t_tail_start = -inf`` + ``old_counts = 0`` +
    ``tail_cap = cap`` turns a session's row into exactly the full
    stateful backfill (`count_batch_indexed_stateful` semantics, carries
    out), while finite cutoffs give the warm tail-delta recount. Per-row
    results are bit-for-bit the single-stream entries' (differentially
    tested) — tracking/scheduling/overflow are per-(session, episode)-row.

    Returns ``(counts i32[S, B], prev_end f32[S, B], n_superset i32[S, B],
    overflow bool[S, B], tail_short bool[S, B])``.
    """
    tables = jnp.asarray(tables, jnp.float32)
    counts = jnp.asarray(counts, jnp.int32)
    old_counts = jnp.asarray(old_counts, jnp.int32)
    t_tail_start = jnp.asarray(t_tail_start, jnp.float32)
    symbols = jnp.asarray(symbols, jnp.int32)
    t_low = jnp.asarray(t_low, jnp.float32)
    t_high = jnp.asarray(t_high, jnp.float32)
    prev_end = jnp.asarray(prev_end, jnp.float32)
    prev_count = jnp.asarray(prev_count, jnp.int32)
    if build_cap is None:
        build_cap = tables.shape[2]
    s, b = tables.shape[0], symbols.shape[0]
    if b == 0:
        zi = jnp.zeros((s, 0), jnp.int32)
        zb = jnp.zeros((s, 0), bool)
        return zi, jnp.zeros((s, 0), jnp.float32), zi, zb, zb
    p = plan_mod.plan_for(
        "count_corpus_tail", level=symbols.shape[1], n_types=tables.shape[1],
        cap=tables.shape[2], batch=b, streams=s, tail_cap=int(tail_cap),
        **_plan_knobs(engine, parallel_schedule, cap_occ, max_window,
                      block_next, block_prev, window_tiles, interpret))
    tables = plan_mod.pad_width(tables, p.cap, jnp.inf)
    prev_end = _pad_cols(prev_end, p.batch)
    prev_count = _pad_cols(prev_count, p.batch)
    if p.streams != s:
        # padded sessions are empty (+inf index, zero counts, -inf cutoff):
        # they count nothing and their rows are sliced away
        pad = p.streams - s
        tables = jnp.concatenate(
            [tables, jnp.full((pad,) + tables.shape[1:], jnp.inf,
                              jnp.float32)], axis=0)
        counts = jnp.concatenate(
            [counts, jnp.zeros((pad, counts.shape[1]), jnp.int32)], axis=0)
        old_counts = jnp.concatenate(
            [old_counts, jnp.zeros((pad, old_counts.shape[1]), jnp.int32)],
            axis=0)
        t_tail_start = jnp.concatenate(
            [t_tail_start, jnp.full((pad,), -jnp.inf, jnp.float32)], axis=0)
        prev_end = jnp.concatenate(
            [prev_end, jnp.full((pad, p.batch), -jnp.inf, jnp.float32)],
            axis=0)
        prev_count = jnp.concatenate(
            [prev_count, jnp.zeros((pad, p.batch), jnp.int32)], axis=0)
    out = plan_mod.dispatch(
        p, tables, counts, old_counts, jnp.asarray(build_cap, jnp.int32),
        t_tail_start, plan_mod.pad_rows(symbols, p.batch),
        plan_mod.pad_rows(t_low, p.batch), plan_mod.pad_rows(t_high, p.batch),
        prev_end, prev_count)
    return tuple(a[:s, :b] for a in out)


def count_corpus_tail_grouped(
    tables: jax.Array,       # f32[S, n_types, cap] per-session type indexes
    counts: jax.Array,       # i32[S, n_types] totals incl. the new chunks
    old_counts: jax.Array,   # i32[S, n_types] totals BEFORE the chunks
    t_tail_start: jax.Array,  # f32[S] per-session suffix cutoffs
    symbols: jax.Array,      # i32[S, B, N] PER-SESSION candidate rows
    t_low: jax.Array,        # f32[B, N-1]
    t_high: jax.Array,       # f32[B, N-1]
    prev_end: jax.Array,     # f32[S, B] per-(session, row) greedy carry
    prev_count: jax.Array,   # i32[S, B]
    *,
    tail_cap: int,
    engine: str = "dense",
    cap_occ: Optional[int] = None,
    max_window: int = 32,
    parallel_schedule: bool = False,
    block_next: Optional[int] = None,
    block_prev: Optional[int] = None,
    window_tiles: Optional[int] = None,
    interpret: Optional[bool] = None,
    build_cap: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """:func:`count_corpus_tail_indexed` with PER-SESSION candidate rows.

    The union layout dispatches every session against every key any session
    wants — fine when frontiers agree, quadratic waste when they diverge
    (the multi-tenant serving regime: row (s, b) is computed whether or not
    session ``s`` ever reads key ``b``). Here ``symbols[s]`` holds session
    ``s``'s OWN b-th candidate, so the dispatched grid is exactly the work
    the pool needs: ``rows == sum_s |frontier_s|`` padded to one batch
    class. Row semantics (suffix cutoffs, carries, overflow/tail_short
    flags) are identical to the union entry — only the pairing changes.

    Sessions with fewer than B rows pad by repeating their row 0 (a
    session with no rows at all pads with type-0 rows); padded cells are
    computed and never read, per the quiet-stream masking rule.

    Returns ``(counts i32[S, B], prev_end f32[S, B], n_superset i32[S, B],
    overflow bool[S, B], tail_short bool[S, B])``.
    """
    tables = jnp.asarray(tables, jnp.float32)
    counts = jnp.asarray(counts, jnp.int32)
    old_counts = jnp.asarray(old_counts, jnp.int32)
    t_tail_start = jnp.asarray(t_tail_start, jnp.float32)
    symbols = jnp.asarray(symbols, jnp.int32)
    t_low = jnp.asarray(t_low, jnp.float32)
    t_high = jnp.asarray(t_high, jnp.float32)
    prev_end = jnp.asarray(prev_end, jnp.float32)
    prev_count = jnp.asarray(prev_count, jnp.int32)
    if build_cap is None:
        build_cap = tables.shape[2]
    s, b = tables.shape[0], symbols.shape[1]
    if b == 0:
        zi = jnp.zeros((s, 0), jnp.int32)
        zb = jnp.zeros((s, 0), bool)
        return zi, jnp.zeros((s, 0), jnp.float32), zi, zb, zb
    p = plan_mod.plan_for(
        "count_corpus_tail_grouped", level=symbols.shape[2],
        n_types=tables.shape[1], cap=tables.shape[2], batch=b, streams=s,
        tail_cap=int(tail_cap),
        **_plan_knobs(engine, parallel_schedule, cap_occ, max_window,
                      block_next, block_prev, window_tiles, interpret))
    tables = plan_mod.pad_width(tables, p.cap, jnp.inf)
    symbols = _pad_cols(symbols, p.batch)
    prev_end = _pad_cols(prev_end, p.batch)
    prev_count = _pad_cols(prev_count, p.batch)
    if p.streams != s:
        pad = p.streams - s
        tables = jnp.concatenate(
            [tables, jnp.full((pad,) + tables.shape[1:], jnp.inf,
                              jnp.float32)], axis=0)
        counts = jnp.concatenate(
            [counts, jnp.zeros((pad, counts.shape[1]), jnp.int32)], axis=0)
        old_counts = jnp.concatenate(
            [old_counts, jnp.zeros((pad, old_counts.shape[1]), jnp.int32)],
            axis=0)
        t_tail_start = jnp.concatenate(
            [t_tail_start, jnp.full((pad,), -jnp.inf, jnp.float32)], axis=0)
        symbols = jnp.concatenate(
            [symbols, jnp.zeros((pad,) + symbols.shape[1:], jnp.int32)],
            axis=0)
        prev_end = jnp.concatenate(
            [prev_end, jnp.full((pad, p.batch), -jnp.inf, jnp.float32)],
            axis=0)
        prev_count = jnp.concatenate(
            [prev_count, jnp.zeros((pad, p.batch), jnp.int32)], axis=0)
    out = plan_mod.dispatch(
        p, tables, counts, old_counts, jnp.asarray(build_cap, jnp.int32),
        t_tail_start, symbols,
        plan_mod.pad_rows(t_low, p.batch), plan_mod.pad_rows(t_high, p.batch),
        prev_end, prev_count)
    return tuple(a[:s, :b] for a in out)


# staticcheck: disable=REPRO003 -- sanctioned outer jit: fuses index build +
# counting in one trace; plan.dispatch inlines its traced body underneath
@functools.partial(
    jax.jit,
    static_argnames=("n_types", "cap", "engine", "cap_occ", "max_window",
                     "parallel_schedule", "block_next", "block_prev",
                     "window_tiles", "interpret"),
)
def count_batch(
    types: jax.Array,
    times: jax.Array,
    symbols: jax.Array,     # i32[B, N]
    t_low: jax.Array,       # f32[B, N-1]
    t_high: jax.Array,      # f32[B, N-1]
    *,
    n_types: int,
    cap: int,
    engine: str = "dense",
    cap_occ: Optional[int] = None,
    max_window: int = 32,
    parallel_schedule: bool = False,
    block_next: Optional[int] = None,
    block_prev: Optional[int] = None,
    window_tiles: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Count a batch of same-length episodes over one stream (vmapped).

    Builds the per-type index then defers to :func:`count_batch_indexed`;
    jitted end-to-end so the index build fuses with the counting pass.
    """
    table, counts = events_lib.type_index(types, times, n_types, cap)
    return count_batch_indexed(
        table, counts, symbols, t_low, t_high, engine=engine,
        cap_occ=cap_occ, max_window=max_window,
        parallel_schedule=parallel_schedule, block_next=block_next,
        block_prev=block_prev, window_tiles=window_tiles, interpret=interpret)
