"""Parallel local tracking (paper §IV-C, Algorithm 2) — subproblem 1.

Given the per-type pre-index, find a superset of episode occurrences with
full data parallelism. Two engines:

``track_faithful_*`` — the paper's algorithm: one "thread" per current-level
  entry scans its constraint window and records *every* matching next event
  (duplicates kept), then the per-thread variable-length outputs are
  compacted (see core/compaction.py). ``_backward`` starts from the *last*
  symbol so the final occurrence list is automatically ordered by end time
  (paper §IV-E's sort-elimination trick); ``_forward`` is the variant whose
  output must be sorted (the AtomicCompact cost profile).

``track_dense`` — beyond-paper: per *event* (not per occurrence-path) keep
  only the latest start time of any partial occurrence ending at that event.
  Dominance argument: if two occurrences end at the same event, the one with
  the later start is contained in the other, so any non-overlapped set using
  the longer one remains valid after swapping in the shorter one. Hence one
  interval per reachable end event (with the latest start) preserves the
  maximum non-overlapped count, and each level reduces to a windowed
  range-max: searchsorted window bounds + an O(n log n) sparse-table max.
  Work is independent of episode frequency — this removes the superset
  blow-up the paper observes in Fig 12 — and no compaction step exists at
  all. Validated against the numpy oracle and the faithful engines.

All functions are static-shaped: event tables are ``+inf``-padded, value
(latest-start) tables are ``-inf``-padded.

Engines are exposed through a registry (see :class:`TrackingEngine` and
:func:`register_engine` at the bottom of this module): ``counting.py``
dispatches by name, so adding an engine is one ``register_engine`` call —
no if/elif ladder to extend. The ``dense_pallas`` engine drives the Pallas
TPU kernel (``kernels/episode_track.py``) through ``kernels/ops.py``,
falling back to interpret mode off-TPU (DESIGN.md §4).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, NamedTuple, Optional, Protocol, Tuple

import jax
import jax.numpy as jnp

from . import compaction
from .episodes import add_floor, sub_ceil

NEG = -jnp.inf


class Occurrences(NamedTuple):
    """A padded set of candidate occurrence intervals, plus tracking stats."""

    starts: jax.Array   # f32[cap] (-inf padding)
    ends: jax.Array     # f32[cap] (+inf padding)
    valid: jax.Array    # bool[cap]
    n_superset: jax.Array  # i32 — total (possibly overlapping) occurrences tracked
    overflow: jax.Array    # bool — capacity exceeded somewhere (count unsafe)


# ---------------------------------------------------------------------------
# Sparse-table range maximum (shared with kernels/ref.py)
# ---------------------------------------------------------------------------


def build_sparse_table(v: jax.Array) -> jax.Array:
    """Stacked doubling max table M[k, i] = max(v[i : i+2^k]); [K, cap]."""
    cap = v.shape[-1]
    levels = [v]
    k = 1
    while (1 << k) <= max(cap, 1):
        half = 1 << (k - 1)
        prev = levels[-1]
        shifted = jnp.concatenate(
            [prev[..., half:], jnp.full(prev.shape[:-1] + (half,), NEG, prev.dtype)],
            axis=-1,
        )
        levels.append(jnp.maximum(prev, shifted))
        k += 1
    return jnp.stack(levels, axis=0)


def range_max(table: jax.Array, lo: jax.Array, hi: jax.Array) -> jax.Array:
    """Vectorized max(v[lo:hi]) queries; -inf where hi <= lo."""
    cap = table.shape[-1]
    length = jnp.clip(hi - lo, 0, cap)
    # floor(log2(L)) via frexp (exact for L < 2^24)
    _, exp = jnp.frexp(jnp.maximum(length, 1).astype(jnp.float32))
    k = (exp - 1).astype(jnp.int32)
    pow2 = jnp.left_shift(jnp.int32(1), k)
    lo_c = jnp.clip(lo, 0, cap - 1)
    hi_c = jnp.clip(hi - pow2, 0, cap - 1)
    a = table[k, lo_c]
    b = table[k, hi_c]
    return jnp.where(length > 0, jnp.maximum(a, b), NEG)


# ---------------------------------------------------------------------------
# Dense (beyond-paper) tracking
# ---------------------------------------------------------------------------


def track_dense(
    times_by_sym: jax.Array,  # f32[N, cap] sorted rows, +inf padded
    t_low: jax.Array,         # f32[N-1]
    t_high: jax.Array,        # f32[N-1]
) -> Occurrences:
    n = times_by_sym.shape[0]
    t0 = times_by_sym[0]
    value = jnp.where(jnp.isfinite(t0), t0, NEG)   # latest start per event
    n_superset = jnp.sum(jnp.isfinite(t0)).astype(jnp.int32)
    for i in range(n - 1):
        t_prev = times_by_sym[i]
        t_next = times_by_sym[i + 1]
        # valid prev times s for next time t:  t - hi <= s < t - lo
        lo_idx = jnp.searchsorted(t_prev, sub_ceil(t_next, t_high[i]),
                                  side="left")
        hi_idx = jnp.searchsorted(t_prev, sub_ceil(t_next, t_low[i]),
                                  side="left")
        table = build_sparse_table(value)
        value = range_max(table, lo_idx.astype(jnp.int32), hi_idx.astype(jnp.int32))
        value = jnp.where(jnp.isfinite(t_next), value, NEG)
        n_superset = n_superset + jnp.sum(value > NEG).astype(jnp.int32)
    ends = times_by_sym[n - 1]
    valid = (value > NEG) & jnp.isfinite(ends)
    return Occurrences(
        starts=value,
        ends=jnp.where(valid, ends, jnp.inf),
        valid=valid,
        n_superset=n_superset,
        overflow=jnp.bool_(False),
    )


# ---------------------------------------------------------------------------
# Faithful tracking (paper Algorithm 2) with pluggable compaction
# ---------------------------------------------------------------------------


def _window_bounds_backward(t_prevsym, cur_t, lo, hi):
    """Events s of the *earlier* symbol valid for a later event at cur_t:
    lo < cur_t - s <= hi  <=>  s in [cur_t - hi, cur_t - lo)."""
    wlo = jnp.searchsorted(t_prevsym, sub_ceil(cur_t, hi), side="left")
    whi = jnp.searchsorted(t_prevsym, sub_ceil(cur_t, lo), side="left")
    return wlo.astype(jnp.int32), whi.astype(jnp.int32)


def _window_bounds_forward(t_nextsym, cur_t, lo, hi):
    """Events t of the *later* symbol valid after cur_t:
    lo < t - cur_t <= hi  <=>  t in (cur_t + lo, cur_t + hi]."""
    wlo = jnp.searchsorted(t_nextsym, add_floor(cur_t, lo), side="right")
    whi = jnp.searchsorted(t_nextsym, add_floor(cur_t, hi), side="right")
    return wlo.astype(jnp.int32), whi.astype(jnp.int32)


def track_faithful(
    times_by_sym: jax.Array,
    t_low: jax.Array,
    t_high: jax.Array,
    *,
    cap_occ: int,
    max_window: int,
    method: str = "count_scan_write",
    direction: str = "backward",
) -> Occurrences:
    """Paper-faithful parallel local tracking.

    Args:
      cap_occ: static capacity of the per-level occurrence list (the paper's
        "preallocated array"); overflow is flagged, not silently wrong.
      max_window: static bound on next-events found per thread (the paper's
        per-thread scan stops past t_high; here it is a BlockSpec-style tile).
      method: 'count_scan_write' (paper's preferred, §IV-E), 'flags'
        (CudppCompact analogue), also used by the forward/sort pipeline.
      direction: 'backward' (auto end-sorted output — paper's trick) or
        'forward' (requires the caller to sort; AtomicCompact profile).
    """
    n = times_by_sym.shape[0]
    cap = times_by_sym.shape[1]
    if direction == "backward":
        cur_t = times_by_sym[n - 1]
        carried = cur_t  # end time of the chain
        level_iter = range(n - 2, -1, -1)
    else:
        cur_t = times_by_sym[0]
        carried = cur_t  # start time of the chain
        level_iter = range(1, n)

    # widen to cap_occ
    pad = cap_occ - cap
    if pad < 0:
        raise ValueError("cap_occ must be >= per-type capacity")
    cur_t = jnp.concatenate([cur_t, jnp.full((pad,), jnp.inf, cur_t.dtype)])
    carried = jnp.concatenate([carried, jnp.full((pad,), jnp.inf, carried.dtype)])

    n_superset = jnp.sum(jnp.isfinite(cur_t)).astype(jnp.int32)
    overflow = jnp.bool_(False)

    for i in level_iter:
        if direction == "backward":
            t_sym = times_by_sym[i]
            wlo, whi = _window_bounds_backward(t_sym, cur_t, t_low[i], t_high[i])
        else:
            t_sym = times_by_sym[i]
            wlo, whi = _window_bounds_forward(t_sym, cur_t, t_low[i - 1], t_high[i - 1])
        counts = jnp.clip(whi - wlo, 0, max_window)
        overflow = overflow | jnp.any((whi - wlo) > max_window)
        cur_t, carried, n_out, ovf = compaction.compact(
            t_sym, wlo, counts, carried, cap_occ=cap_occ,
            max_window=max_window, method=method)
        overflow = overflow | ovf
        n_superset = n_superset + n_out

    if direction == "backward":
        starts, ends = cur_t, carried
    else:
        starts, ends = carried, cur_t
    valid = jnp.isfinite(starts) & jnp.isfinite(ends)
    return Occurrences(
        starts=jnp.where(valid, starts, NEG),
        ends=jnp.where(valid, ends, jnp.inf),
        valid=valid,
        n_superset=n_superset,
        overflow=overflow,
    )


def sort_by_end(occ: Occurrences) -> Occurrences:
    """End-time sort for forward-tracked occurrences (AtomicCompact's final
    sort, §IV-D: 'this procedure requires sorting')."""
    order = jnp.argsort(occ.ends)
    return Occurrences(
        starts=occ.starts[order],
        ends=occ.ends[order],
        valid=occ.valid[order],
        n_superset=occ.n_superset,
        overflow=occ.overflow,
    )


# ---------------------------------------------------------------------------
# Engine registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Per-call knobs threaded from the counting API down to the engines.

    ``cap_occ``/``max_window`` size the faithful engines' static occurrence
    buffers; ``block_next``/``block_prev``/``window_tiles`` are the Pallas
    kernel's tile shape and grid-pruning bound; ``chunk`` is the fused count
    kernel's episode-rows-per-grid-step; ``interpret=None`` lets the kernel
    layer decide (interpret mode anywhere but TPU). Callers that accept
    ``None`` block knobs resolve them through ``kernels.autotune`` (per-
    (L, N, B)-bucket tuned tiles) before building this config.

    ``t_min`` restricts tracking to occurrences *seeded* at time >= t_min
    (windows only look backward, so this equals counting on the substream of
    events at/after ``t_min``). It is a traced value, not a static knob — the
    streaming miner passes a new cutoff every append without recompiling.
    The restriction is applied engine-agnostically at the dispatch layer
    (:func:`restrict_seed_row` shifts pre-cutoff events out of the
    first-symbol row), so every registered engine honors it identically.
    """

    cap_occ: Optional[int] = None
    max_window: int = 32
    block_next: int = 128
    block_prev: int = 128
    window_tiles: int = 0
    chunk: int = 32
    interpret: Optional[bool] = None
    t_min: Optional[jax.Array] = None


def restrict_seed_row(times_by_sym: jax.Array, t_min) -> jax.Array:
    """Drop first-symbol events before ``t_min`` from ``[..., N, cap]`` rows.

    The seed row is shifted left past its first index with time >= ``t_min``
    and +inf-refilled — it stays sorted, so no engine needs to know the
    restriction happened. Only the seed row is touched: earlier events of
    *later* symbols cannot appear in any occurrence seeded at/after
    ``t_min`` anyway (chains run forward in time), and leaving them in place
    keeps the transform O(cap) instead of O(N * cap).
    """
    row0 = times_by_sym[..., 0, :]
    cap = row0.shape[-1]
    t_min = jnp.asarray(t_min, jnp.float32)
    flat = row0.reshape(-1, cap)
    k = jax.vmap(
        lambda r: jnp.searchsorted(r, t_min, side="left"))(flat).astype(jnp.int32)
    idx = k[:, None] + jnp.arange(cap, dtype=jnp.int32)[None, :]
    shifted = jnp.take_along_axis(flat, jnp.minimum(idx, cap - 1), axis=-1)
    shifted = jnp.where(idx < cap, shifted, jnp.inf).reshape(row0.shape)
    return jnp.concatenate(
        [shifted[..., None, :], times_by_sym[..., 1:, :]], axis=-2)


def consume_seed_restriction(
    times_by_sym: jax.Array, cfg: EngineConfig
) -> Tuple[jax.Array, EngineConfig]:
    """Apply ``cfg.t_min`` to the tables and strip it from the config.

    Called once at each dispatch altitude (single episode, batch, corpus)
    so engines — including future natively-batched ones — can never
    double-apply the restriction.
    """
    if cfg.t_min is None:
        return times_by_sym, cfg
    return (restrict_seed_row(times_by_sym, cfg.t_min),
            dataclasses.replace(cfg, t_min=None))


class TrackingEngine(Protocol):
    """One per-level windowed tracking strategy + compaction scheme.

    ``track`` must be jit/vmap-traceable: static shapes in, static shapes
    out, with the Occurrences padding convention (+inf ends, -inf starts).

    Engines MAY additionally provide a natively-batched

        ``track_batch(times_by_sym f32[B, N, cap], t_low f32[B, N-1],
                      t_high f32[B, N-1], cfg) -> Occurrences``

    returning batch-leading Occurrences (``starts/ends/valid`` are
    ``[B, cap]``, ``n_superset``/``overflow`` are ``[B]``). When present,
    ``counting.count_batch_indexed`` dispatches an entire candidate batch
    through it in one call instead of vmapping the per-episode ``track`` —
    the fused-kernel fast path.

    Engines MAY further provide a natively corpus-batched

        ``track_corpus(times_by_sym f32[S, B, N, cap], t_low f32[B, N-1],
                       t_high f32[B, N-1], cfg) -> Occurrences``

    with ``[S, B]``-leading outputs: one shared candidate batch tracked
    against ``S`` independent streams (the per-episode windows broadcast
    over the stream axis). ``counting.count_corpus_indexed`` dispatches
    whole corpora through it — the fused engine folds ``(stream, episode)``
    into its batch grid dimension, ONE launch per mining level for the
    whole corpus.

    Engines MAY also provide a natively-counting

        ``count_batch(times_by_sym f32[B, N, cap], t_low f32[B, N-1],
                      t_high f32[B, N-1], prev_end f32[B], prev_count i32[B],
                      cfg) -> (counts i32[B], end_out f32[B],
                               n_superset i32[B], overflow bool[B])``

    running tracking + compaction + the ``greedy_scan_state`` non-overlap
    scheduler end-to-end (carry-in/carry-out chain state, so the streaming
    stitch is engine-invariant). When present,
    ``counting.count_batch_dispatch`` routes whole count calls through it —
    ONE kernel launch per (level, candidate batch), occurrence intervals
    never leaving VMEM. Engines without it fall back to
    ``track_batch_dispatch`` + the host-side greedy fold; results are
    bit-for-bit identical either way.
    """

    name: str

    def track(
        self,
        times_by_sym: jax.Array,   # f32[N, cap] sorted rows, +inf padded
        t_low: jax.Array,          # f32[N-1]
        t_high: jax.Array,         # f32[N-1]
        cfg: EngineConfig,
    ) -> Occurrences:
        ...


_REGISTRY: Dict[str, TrackingEngine] = {}


def register_engine(engine: TrackingEngine, *,
                    overwrite: bool = False) -> TrackingEngine:
    if engine.name in _REGISTRY and not overwrite:
        raise ValueError(f"engine {engine.name!r} already registered")
    _REGISTRY[engine.name] = engine
    return engine


def get_engine(name: str) -> TrackingEngine:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"engine must be one of {engine_names()}, got {name!r}") from None


def engine_names() -> Tuple[str, ...]:
    return tuple(_REGISTRY)


def track_batch_dispatch(
    engine,                    # str name or TrackingEngine
    times_by_sym: jax.Array,   # f32[B, N, cap] sorted rows, +inf padded
    t_low: jax.Array,          # f32[B, N-1]
    t_high: jax.Array,         # f32[B, N-1]
    cfg: EngineConfig,
) -> Occurrences:
    """Batch-leading tracking through any engine.

    Engines exposing the native ``track_batch`` protocol method get the
    whole batch in one call (the fused-kernel fast path); everything else is
    vmapped over its per-episode ``track``. This is the ONE place batched
    dispatch lives — ``counting.count_batch_indexed`` and the sharded
    counters in ``core/distributed.py`` both route through it, so an engine
    gains multi-device support by registering, nothing more.

    Returns batch-leading Occurrences: ``starts/ends/valid`` are
    ``[B, cap]``, ``n_superset``/``overflow`` are ``[B]``.
    """
    eng = get_engine(engine) if isinstance(engine, str) else engine
    times_by_sym, cfg = consume_seed_restriction(times_by_sym, cfg)
    track_batch = getattr(eng, "track_batch", None)
    if track_batch is not None:
        return track_batch(times_by_sym, t_low, t_high, cfg)
    return jax.vmap(lambda t, lo, hi: eng.track(t, lo, hi, cfg))(
        times_by_sym, t_low, t_high)


def track_corpus_dispatch(
    engine,                    # str name or TrackingEngine
    times_by_sym: jax.Array,   # f32[S, B, N, cap] sorted rows, +inf padded
    t_low: jax.Array,          # f32[B, N-1] shared across streams
    t_high: jax.Array,         # f32[B, N-1]
    cfg: EngineConfig,
) -> Occurrences:
    """Corpus-leading tracking through any engine.

    One candidate batch, ``S`` independent streams: engines exposing the
    native ``track_corpus`` protocol method get the whole corpus in one
    call (the fused engine folds the stream axis into its batch grid — one
    launch per level for every stream); everything else is vmapped over the
    stream axis of :func:`track_batch_dispatch`, which in turn uses the
    engine's ``track_batch`` when present. This is the ONE place corpus
    dispatch lives — the local and sharded corpus counters both route
    through it, so an engine gains multi-stream (and stream-sharded)
    support by registering, nothing more.

    Returns ``[S, B]``-leading Occurrences: ``starts/ends/valid`` are
    ``[S, B, cap]``, ``n_superset``/``overflow`` are ``[S, B]``.
    """
    eng = get_engine(engine) if isinstance(engine, str) else engine
    times_by_sym, cfg = consume_seed_restriction(times_by_sym, cfg)
    track_corpus = getattr(eng, "track_corpus", None)
    if track_corpus is not None:
        return track_corpus(times_by_sym, t_low, t_high, cfg)
    return jax.vmap(
        lambda t: track_batch_dispatch(eng, t, t_low, t_high, cfg))(
        times_by_sym)


@dataclasses.dataclass(frozen=True)
class DenseEngine:
    """Beyond-paper windowed range-max tracking (no compaction at all)."""

    name: str = "dense"

    def track(self, times_by_sym, t_low, t_high, cfg: EngineConfig) -> Occurrences:
        return track_dense(times_by_sym, t_low, t_high)


@dataclasses.dataclass(frozen=True)
class FaithfulEngine:
    """Paper Algorithm 2 tracking with a pluggable compaction strategy."""

    name: str
    method: str = "count_scan_write"
    direction: str = "backward"
    sort_output: bool = False   # AtomicCompact profile: forward + final sort

    def track(self, times_by_sym, t_low, t_high, cfg: EngineConfig) -> Occurrences:
        cap = times_by_sym.shape[1]
        # `is None`, not `or`: an explicit cap_occ=0 must be rejected by
        # track_faithful's capacity check, not silently widened to cap
        cap_occ = cap if cfg.cap_occ is None else cfg.cap_occ
        occ = track_faithful(
            times_by_sym, t_low, t_high,
            cap_occ=cap_occ, max_window=cfg.max_window,
            method=self.method, direction=self.direction)
        return sort_by_end(occ) if self.sort_output else occ


def _pallas_tile_geometry(cap: int, cfg: EngineConfig):
    """(bn, bp, padded_cap) for the Pallas engines: the engine-policy block
    clamp ([8, 256]) composed with the single shared tiling rule in
    kernels/ops.py, so the per-level and fused engines tile identically
    (their conservative window-truncation checks must agree tile-for-tile).
    Tiles the chip cannot run raise there."""
    from ..kernels import ops  # deferred: core stays importable sans pallas

    return ops.tile_geometry(
        cap, max(8, min(cfg.block_next, 256)),
        max(8, min(cfg.block_prev, 256)), interpret=cfg.interpret)


@dataclasses.dataclass(frozen=True)
class DensePallasEngine:
    """Dense tracking with each level executed by the Pallas TPU kernel.

    Same dominance argument (and therefore the same counts) as ``dense``,
    but the windowed range-max runs as tiled broadcast-compare + row-max in
    VMEM (kernels/episode_track.py). The level arrays are padded up to a
    common multiple of the tile sizes — max-accumulation over +inf/-inf
    padding is a no-op, so this is harmless — and sliced back afterwards.

    ``window_tiles > 0`` caps how many prev tiles each next tile scans; a
    too-small cap would truncate constraint windows, so any level where a
    next tile's window may not fit is reported through ``overflow`` (the
    same convention as the faithful engines' capacity misses — flagged,
    never silently wrong). ``window_tiles=0`` is always exact.
    """

    name: str = "dense_pallas"

    def track(self, times_by_sym, t_low, t_high, cfg: EngineConfig) -> Occurrences:
        from ..kernels import ops  # deferred: core stays importable sans pallas

        n, cap = times_by_sym.shape
        bn, bp, pcap = _pallas_tile_geometry(cap, cfg)

        def pad_t(row):
            return jnp.concatenate(
                [row, jnp.full((pcap - cap,), jnp.inf, row.dtype)])

        t0 = times_by_sym[0]
        v = jnp.where(jnp.isfinite(t0), t0, NEG)
        n_superset = jnp.sum(jnp.isfinite(t0)).astype(jnp.int32)
        overflow = jnp.bool_(False)
        v = jnp.concatenate([v, jnp.full((pcap - cap,), NEG, v.dtype)])
        t_prev = pad_t(t0)
        for i in range(n - 1):
            t_next = pad_t(times_by_sym[i + 1])
            if cfg.window_tiles > 0 and cfg.window_tiles < pcap // bp:
                # same shared predicate as the fused engine's precompute
                overflow = overflow | ops.window_truncated(
                    t_prev, t_next, t_high[i], bn, bp, cfg.window_tiles)
            v = ops.track_level(
                t_prev, v, t_next, t_low[i], t_high[i],
                block_next=bn, block_prev=bp,
                window_tiles=cfg.window_tiles, interpret=cfg.interpret)
            v = jnp.where(jnp.isfinite(t_next), v, NEG)
            n_superset = n_superset + jnp.sum(v > NEG).astype(jnp.int32)
            t_prev = t_next
        v = v[:cap]
        ends = times_by_sym[n - 1]
        valid = (v > NEG) & jnp.isfinite(ends)
        return Occurrences(
            starts=v,
            ends=jnp.where(valid, ends, jnp.inf),
            valid=valid,
            n_superset=n_superset,
            overflow=overflow,
        )


@dataclasses.dataclass(frozen=True)
class FusedDensePallasEngine:
    """Dense tracking for a whole candidate batch in ONE fused Pallas launch.

    Same dominance argument (and counts) as ``dense``/``dense_pallas``, but
    instead of ``B x (N-1)`` per-level kernel launches with HBM round-trips
    between them, one launch walks every episode row through all of its
    levels: latest-start values stay in VMEM scratch across levels, the
    per-(episode, level, tile) scan offsets arrive as a per-row SMEM table,
    and each next tile walks exactly the prev tiles its constraint window
    spans (a dynamic in-kernel loop — no static quadratic tile coverage).
    See kernels/episode_track.py and DESIGN.md §2.

    ``track_batch`` is the native entry point (dispatched by
    ``counting.count_batch_indexed``); ``track`` wraps it with a singleton
    batch so the engine also serves the per-episode API. ``window_tiles``
    keeps the per-level engine's semantics: 0 = exact, > 0 caps each tile's
    scan length and flags possible truncation through ``overflow`` using
    the same conservative span bound as ``dense_pallas``.
    """

    name: str = "dense_pallas_fused"

    def track(self, times_by_sym, t_low, t_high, cfg: EngineConfig) -> Occurrences:
        occ = self.track_batch(
            times_by_sym[None], t_low[None], t_high[None], cfg)
        return Occurrences(*(x[0] for x in occ))

    def track_batch(self, times_by_sym, t_low, t_high,
                    cfg: EngineConfig) -> Occurrences:
        from ..kernels import ops  # deferred: core stays importable sans pallas

        # same policy-clamped blocks as the per-level engine; ops.track_batch
        # applies the shared tile_geometry rule, so the two Pallas engines'
        # conservative truncation checks agree tile-for-tile
        bn, bp, _ = _pallas_tile_geometry(times_by_sym.shape[-1], cfg)
        starts, n_superset, truncated = ops.track_batch(
            times_by_sym, t_low, t_high, block_next=bn, block_prev=bp,
            window_tiles=cfg.window_tiles, interpret=cfg.interpret)
        ends = times_by_sym[:, -1, :]
        valid = (starts > NEG) & jnp.isfinite(ends)
        return Occurrences(
            starts=starts,
            ends=jnp.where(valid, ends, jnp.inf),
            valid=valid,
            n_superset=n_superset,
            overflow=truncated,
        )

    def count_batch(self, times_by_sym, t_low, t_high, prev_end, prev_count,
                    cfg: EngineConfig):
        """Single-launch count pipeline: tracking + the greedy_scan_state
        fold over the keep-masked intervals, one kernel for the whole batch
        (kernels/episode_track.py::count_batch_pallas, DESIGN.md §10).

        Returns ``(counts i32[B], end_out f32[B], n_superset i32[B],
        overflow bool[B])`` with the carried chain state included, exactly
        as the track + host-greedy path would produce.
        """
        from ..kernels import ops  # deferred: core stays importable sans pallas

        bn, bp, _ = _pallas_tile_geometry(times_by_sym.shape[-1], cfg)
        return ops.count_batch(
            times_by_sym, t_low, t_high, prev_end, prev_count,
            block_next=bn, block_prev=bp, window_tiles=cfg.window_tiles,
            chunk=cfg.chunk, interpret=cfg.interpret)

    def track_corpus(self, times_by_sym, t_low, t_high,
                     cfg: EngineConfig) -> Occurrences:
        from ..kernels import ops  # deferred: core stays importable sans pallas

        # stream axis folded into the batch grid dimension (ops.track_corpus):
        # per-row tracking is identical to track_batch row-for-row, so the
        # corpus path inherits the fused engine's exactness bit-for-bit
        bn, bp, _ = _pallas_tile_geometry(times_by_sym.shape[-1], cfg)
        starts, n_superset, truncated = ops.track_corpus(
            times_by_sym, t_low, t_high, block_next=bn, block_prev=bp,
            window_tiles=cfg.window_tiles, interpret=cfg.interpret)
        ends = times_by_sym[:, :, -1, :]
        valid = (starts > NEG) & jnp.isfinite(ends)
        return Occurrences(
            starts=starts,
            ends=jnp.where(valid, ends, jnp.inf),
            valid=valid,
            n_superset=n_superset,
            overflow=truncated,
        )


register_engine(DenseEngine())
register_engine(FaithfulEngine("count_scan_write", direction="backward"))
register_engine(FaithfulEngine("atomic_sort", direction="forward", sort_output=True))
register_engine(FaithfulEngine("flags", method="flags", direction="backward"))
register_engine(DensePallasEngine())
register_engine(FusedDensePallasEngine())
