"""Multi-pod sharded episode counting and mining — the technique at scale.

The event stream is sharded over the mesh ``data`` axis (time-contiguous
blocks). :func:`build_sharded_index` runs ONE ``shard_map`` pass per stream:

  1. a *halo* of the next ``halo`` events past each shard's right boundary
     is fetched with multi-hop ``lax.ppermute`` (the lesson of the paper's
     MapConcat: boundary occurrences need lookahead bounded by
     ``episode.max_span`` — and an occurrence may straddle *several* shards,
     so the halo walks as many right neighbors as it needs);
  2. each shard builds its per-type event index over (own + halo) events
     once; every mining level reuses it (the paper's §IV-A pre-processing
     amortization, extended across shards and levels).

Per level, :func:`count_sharded_batch_indexed` runs the whole candidate
batch through any registered tracking engine *inside* ``shard_map`` (the
fused ``dense_pallas_fused`` engine gets the batch in one launch via
``tracking.track_batch_dispatch``), then merges across shards:

  3. each shard keeps only occurrences seeded at its own events
     (``start <= last own event time`` — ties at duplicate boundary
     timestamps are claimed by BOTH sides: a double-claimed interval is
     still a valid global occurrence and the strict greedy cannot take an
     interval twice, whereas the seed's strict ``start < boundary`` rule
     dropped tied occurrences on the floor, undercounting);
  4. per-shard interval lists are ``all_gather``-ed, end-sorted, and
     resolved with the greedy scheduler (sequential or parallel
     binary-lifting) — subproblem 2 stays cheap exactly as the paper
     claims, and the result is replicated so the miner pays ONE host sync
     per level.

Exactness holds when each shard's halo spans ``max_span`` in time past its
boundary or reaches the global end of the stream; otherwise the
*per-episode* ``halo_short`` flag is set (never a silent undercount — the
adequacy check is strict, ``halo_end - boundary > span``, because an event
at exactly ``halo_end`` may be a duplicate timestamp split across the halo
edge). Static capacity misses surface through ``overflow``, same as the
single-device engines. See DESIGN.md §7.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from . import counting
from . import events as events_lib
from . import scheduling, tracking
from .episodes import Episode
from ..compat import shard_map, shard_map_unchecked


def shard_stream(types, times, n_shards: int) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side prep: pad and reshape a stream into [n_shards, n_local]."""
    types = np.asarray(types, np.int32)
    times = np.asarray(times, np.float32)
    n = types.shape[0]
    n_local = max(1, -(-n // n_shards))
    pt = np.full((n_shards * n_local,), np.inf, np.float32)
    py = np.full((n_shards * n_local,), -1, np.int32)
    pt[:n] = times
    py[:n] = types
    return py.reshape(n_shards, n_local), pt.reshape(n_shards, n_local)


@dataclasses.dataclass(frozen=True)
class ShardedIndex:
    """Per-shard (own + halo) type index + boundary bookkeeping.

    Built once per stream by :func:`build_sharded_index` and reused by every
    mining level. All leading-``n_shards`` arrays live sharded over the mesh
    axis; ``global_type_counts`` is the exact own-events-only per-type total
    (the miner's level-1 counts).
    """

    table: jax.Array              # f32[n_shards, n_types, cap_view]
    type_counts: jax.Array        # i32[n_shards, n_types] own+halo view totals
    t_own_last: jax.Array         # f32[n_shards] last own event time (-inf if none)
    t_boundary: jax.Array         # f32[n_shards] right neighbor's first event time
    halo_end: jax.Array           # f32[n_shards] last halo time; +inf when the
                                  #   halo reaches the global end of the stream
    global_type_counts: jax.Array  # i32[n_types]
    mesh: Mesh
    axis: str
    halo: int

    @property
    def n_types(self) -> int:
        return self.table.shape[1]

    @property
    def cap_view(self) -> int:
        return self.table.shape[2]


def _clamp_halo(halo: int, n_shards: int, n_local: int) -> int:
    """A halo can never need more than all events to the right — and with
    multiple shards it must fetch at least ONE neighbor event: halo=0 would
    leave ``halo_end`` unobserved and the adequacy check blind, so a
    boundary-straddling occurrence could vanish without the ``halo_short``
    flag (the module contract is flagged, never silent)."""
    if n_shards == 1:
        return 0
    return max(1, min(halo, (n_shards - 1) * n_local))


# staticcheck: disable=REPRO003 -- mesh path: shard_map executables
# live in jax's jit cache by design (plan.uncacheable_reason)
@functools.partial(
    jax.jit, static_argnames=("mesh", "axis", "n_types", "halo"))
def _build_sharded_index_impl(types_sharded, times_sharded, *,
                              mesh, axis, n_types, halo):
    n_shards, n_local = types_sharded.shape
    hops = -(-halo // n_local) if halo else 0
    cap_view = n_local + halo

    def shard_fn(ty_blk, tm_blk):
        ty = ty_blk[0]      # [n_local]
        tm = tm_blk[0]
        idx = lax.axis_index(axis)

        # multi-hop halo: the h-th hop fetches the h-th right neighbor's
        # whole block, so a halo longer than one shard (occurrences that
        # straddle >= 3 shards) still arrives; wrapped-around blocks from
        # past the last shard are masked to padding
        halo_ty = jnp.zeros((0,), ty.dtype)
        halo_tm = jnp.zeros((0,), tm.dtype)
        for h in range(1, hops + 1):
            perm = [(i, (i - h) % n_shards) for i in range(n_shards)]
            bty = lax.ppermute(ty, axis, perm)
            btm = lax.ppermute(tm, axis, perm)
            real = idx < n_shards - h
            halo_ty = jnp.concatenate([halo_ty, jnp.where(real, bty, -1)])
            halo_tm = jnp.concatenate([halo_tm, jnp.where(real, btm, jnp.inf)])
        halo_ty = halo_ty[:halo]
        halo_tm = halo_tm[:halo]

        all_ty = jnp.concatenate([ty, halo_ty])
        all_tm = jnp.concatenate([tm, halo_tm])
        table, counts = events_lib.type_index(all_ty, all_tm, n_types, cap_view)

        own_finite = jnp.isfinite(tm)
        t_own_last = jnp.max(jnp.where(own_finite, tm, -jnp.inf))
        if halo:
            t_boundary = halo_tm[0]
            # a halo covering every shard to my right sees the stream out to
            # its global end — there is nothing past it to miss
            reaches_end = halo >= (n_shards - 1 - idx) * n_local
            halo_end = jnp.where(reaches_end, jnp.inf, halo_tm[halo - 1])
        else:
            t_boundary = jnp.float32(jnp.inf)
            halo_end = jnp.float32(jnp.inf)

        own_ty = jnp.where(ty >= 0, ty, n_types)        # padding -> dropped
        own_counts = jnp.zeros((n_types,), jnp.int32).at[own_ty].add(
            1, mode="drop")
        global_counts = lax.psum(own_counts, axis)

        return (table[None], counts[None], t_own_last[None], t_boundary[None],
                halo_end[None], global_counts[None])

    in_spec = P(axis, None)
    fn = shard_map(
        shard_fn, mesh=mesh,
        in_specs=(in_spec, in_spec),
        out_specs=(P(axis, None, None), P(axis, None), P(axis), P(axis),
                   P(axis), P(axis, None)),
    )
    return fn(types_sharded, times_sharded)


def build_sharded_index(
    types_sharded: jax.Array,   # i32[n_shards, n_local] (-1 padding)
    times_sharded: jax.Array,   # f32[n_shards, n_local] (+inf padding)
    mesh: Mesh,
    *,
    axis: str = "data",
    n_types: int,
    halo: int = 256,
) -> ShardedIndex:
    """One shard_map pass: halo exchange + per-shard type index, built once."""
    n_shards, n_local = types_sharded.shape
    axis_size = mesh.shape[axis]
    if axis_size != n_shards:
        raise ValueError(f"stream sharded into {n_shards} != mesh axis {axis_size}")
    halo = _clamp_halo(halo, n_shards, n_local)
    table, counts, own_last, boundary, halo_end, global_counts = (
        _build_sharded_index_impl(
            jnp.asarray(types_sharded), jnp.asarray(times_sharded),
            mesh=mesh, axis=axis, n_types=n_types, halo=halo))
    return ShardedIndex(
        table=table, type_counts=counts, t_own_last=own_last,
        t_boundary=boundary, halo_end=halo_end,
        global_type_counts=global_counts[0], mesh=mesh, axis=axis, halo=halo)


# staticcheck: disable=REPRO003 -- mesh path: shard_map executables
# live in jax's jit cache by design (plan.uncacheable_reason)
@functools.partial(
    jax.jit,
    static_argnames=("mesh", "axis", "engine", "cap_occ", "max_window",
                     "parallel_schedule", "block_next", "block_prev",
                     "window_tiles", "interpret"),
)
def _count_sharded_batch_impl(
    table, type_counts, t_own_last, t_boundary, halo_end,
    symbols, t_low, t_high, *,
    mesh, axis, engine, cap_occ, max_window, parallel_schedule,
    block_next, block_prev, window_tiles, interpret,
):
    cap_view = table.shape[2]
    # the sharded path needs raw intervals for ownership masking + the
    # cross-shard merge, so it always tracks (kind="track" tuned tiles) —
    # the single-launch count pipeline cannot serve it
    from ..kernels import autotune  # deferred: avoid import cycles
    tc = autotune.resolve(
        "track", symbols.shape[1] - 1, cap_view, symbols.shape[0],
        block_next=block_next, block_prev=block_prev,
        window_tiles=window_tiles)
    block_next, block_prev, window_tiles = (
        tc.block_next, tc.block_prev, tc.window_tiles)
    cfg = tracking.EngineConfig(
        cap_occ=cap_occ, max_window=max_window, block_next=block_next,
        block_prev=block_prev, window_tiles=window_tiles, interpret=interpret)

    def shard_fn(tbl, cnt, own_last, boundary, h_end, sym, lo, hi):
        tbl, cnt = tbl[0], cnt[0]
        own_last, boundary, h_end = own_last[0], boundary[0], h_end[0]

        # whole candidate batch through the engine registry (fused kernel
        # when the engine is natively batched) — subproblem 1 per shard
        occ = tracking.track_batch_dispatch(engine, tbl[sym], lo, hi, cfg)

        # ownership: occurrences seeded at my own events. `<=` (not `<`
        # boundary): with duplicate timestamps at a shard boundary, my
        # tied occurrence is invisible to the neighbor, so I must claim it;
        # the neighbor may claim its own identical-time seed too, which is
        # harmless — both are valid global intervals and the strict greedy
        # cannot take two intervals with equal start/end.
        mine = occ.valid & (occ.starts <= own_last)
        starts = jnp.where(mine, occ.starts, -jnp.inf)
        ends = jnp.where(mine, occ.ends, jnp.inf)

        # per-episode halo adequacy: events up to span past the boundary
        # must be in view. Strict `> span` (flag on `== span`): an event at
        # exactly halo_end can be a duplicate timestamp split across the
        # halo edge, with its twin just out of view.
        span = (jnp.sum(hi, axis=-1) if hi.shape[-1]
                else jnp.zeros((hi.shape[0],), jnp.float32))
        short = jnp.isfinite(h_end) & (h_end - boundary <= span)
        short = lax.psum(short.astype(jnp.int32), axis) > 0

        index_overflow = jnp.any(cnt > cap_view)
        overflow = lax.psum(
            (occ.overflow | index_overflow).astype(jnp.int32), axis) > 0
        n_sup = lax.psum(jnp.sum(mine, axis=-1).astype(jnp.int32), axis)

        # cross-shard greedy merge: gather every shard's owned intervals,
        # end-sort per episode, one greedy pass — the stitch step
        g_starts = lax.all_gather(starts, axis)   # [n_shards, B, cap_view]
        g_ends = lax.all_gather(ends, axis)
        b = sym.shape[0]
        g_starts = jnp.moveaxis(g_starts, 0, 1).reshape(b, -1)
        g_ends = jnp.moveaxis(g_ends, 0, 1).reshape(b, -1)
        order = jnp.argsort(g_ends, axis=-1)
        g_starts = jnp.take_along_axis(g_starts, order, axis=-1)
        g_ends = jnp.take_along_axis(g_ends, order, axis=-1)

        def one(st, en):
            merged = tracking.Occurrences(
                st, en, jnp.isfinite(en) & (st > -jnp.inf),
                jnp.int32(0), jnp.bool_(False))
            return scheduling.greedy_count(merged, parallel=parallel_schedule)

        counts = jax.vmap(one)(g_starts, g_ends)
        return counts[None], n_sup[None], short[None], overflow[None]

    # unchecked: the fused engine's pallas_call has no replication rule in
    # the shard_map checker (every output is P(axis)-sharded anyway)
    fn = shard_map_unchecked(
        shard_fn, mesh=mesh,
        in_specs=(P(axis, None, None), P(axis, None), P(axis), P(axis),
                  P(axis), P(), P(), P()),
        out_specs=(P(axis, None),) * 4,
    )
    counts, n_sup, short, overflow = fn(
        table, type_counts, t_own_last, t_boundary, halo_end,
        symbols, t_low, t_high)
    return counts[0], n_sup[0], short[0], overflow[0]


def count_sharded_batch_indexed(
    index: ShardedIndex,
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
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Count a batch of same-length episodes on a pre-built sharded index.

    Returns ``(counts[B], n_superset[B], halo_short[B], overflow[B])`` —
    replicated device values, so the caller pays one host sync for all four.
    ``n_superset`` is the number of owned final-level occurrence intervals
    summed over shards (the size of the merged superset fed to the greedy
    stitch).

    Plan-spine integration (plan.py): a mesh plan is resolved for the
    launch — same rounding rule, same tuned-tile bucket — but dispatch
    stays on jax's own jit cache (``_count_sharded_batch_impl`` keys on
    the identical static args a plan carries, and shard_map executables
    cannot be AOT-held per-bucket the way single-device ones are). The
    bypass is counted in ``plan.cache_stats()["bypasses"]`` so serving
    telemetry still sees every launch.
    """
    from . import plan as plan_mod
    plan_mod.note_bypass(plan_mod.plan_for(
        "count_indexed", level=int(symbols.shape[1]),
        n_types=int(index.table.shape[-2]), cap=int(index.table.shape[-1]),
        batch=int(symbols.shape[0]), engine=engine,
        parallel_schedule=parallel_schedule, cap_occ=cap_occ,
        max_window=max_window, block_next=block_next, block_prev=block_prev,
        window_tiles=window_tiles, interpret=interpret, mesh=index.mesh,
        kind="track"))
    return _count_sharded_batch_impl(
        index.table, index.type_counts, index.t_own_last, index.t_boundary,
        index.halo_end,
        jnp.asarray(symbols, jnp.int32), jnp.asarray(t_low, jnp.float32),
        jnp.asarray(t_high, jnp.float32),
        mesh=index.mesh, axis=index.axis, engine=engine, cap_occ=cap_occ,
        max_window=max_window, parallel_schedule=parallel_schedule,
        block_next=block_next, block_prev=block_prev,
        window_tiles=window_tiles, interpret=interpret)


def count_sharded_batch(
    types_sharded: jax.Array,
    times_sharded: jax.Array,
    symbols: jax.Array,
    t_low: jax.Array,
    t_high: jax.Array,
    mesh: Mesh,
    *,
    axis: str = "data",
    n_types: int,
    halo: int = 256,
    **kw,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Sharded batch counting end-to-end (index build + count)."""
    index = build_sharded_index(
        types_sharded, times_sharded, mesh, axis=axis, n_types=n_types,
        halo=halo)
    return count_sharded_batch_indexed(index, symbols, t_low, t_high, **kw)


def count_sharded(
    types_sharded: jax.Array,   # i32[n_shards, n_local] (-1 padding)
    times_sharded: jax.Array,   # f32[n_shards, n_local] (+inf padding)
    episode: Episode,
    mesh: Mesh,
    *,
    axis: str = "data",
    n_types: int,
    halo: int = 256,
    engine: str = "dense",
    parallel_schedule: bool = True,
    **kw,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Exact non-overlapped count of one episode over a sharded stream.

    Returns ``(count i32, halo_short bool, overflow bool)`` — the
    singleton-batch wrapper over :func:`count_sharded_batch`, so the
    ownership rule, halo adequacy, and engine dispatch are the same code
    the batched miner runs. Works on any mesh whose ``axis`` size equals
    ``types_sharded.shape[0]``; other mesh axes see replicated data (the
    same code runs single-pod and multi-pod).
    """
    sym, lo, hi = episode.as_arrays()
    counts, _, short, overflow = count_sharded_batch(
        types_sharded, times_sharded, sym[None], lo[None], hi[None], mesh,
        axis=axis, n_types=n_types, halo=halo, engine=engine,
        parallel_schedule=parallel_schedule, **kw)
    return counts[0], short[0], overflow[0]


def make_count_sharded_jit(episode: Episode, mesh: Mesh, **kw):
    """jit-wrapped sharded counter for repeated use (benchmarks/serving)."""
    fn = functools.partial(count_sharded, episode=episode, mesh=mesh, **kw)
    # staticcheck: disable=REPRO003 -- mesh path (see module note above)
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# Corpus sharding: the STREAM axis over the mesh (no halo — streams are
# independent, so unlike the time-sharded path above there is no boundary
# occurrence to exchange and no cross-shard greedy merge; each device mines
# its slice of the corpus in complete isolation and the only collective is
# the level-1 type-count assembly the host reads anyway)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CorpusIndex:
    """Per-stream type indexes, stream-sharded over the mesh.

    ``n_streams`` is the real corpus size; rows past it are all-padding
    streams appended so the stream axis divides the mesh axis (they count
    nothing and the host never reads their rows).
    """

    tables: jax.Array        # f32[S_pad, n_types, cap] (stream-sharded)
    type_counts: jax.Array   # i32[S_pad, n_types]
    mesh: Mesh
    axis: str
    n_streams: int

    @property
    def cap(self) -> int:
        return self.tables.shape[2]


def pad_corpus_streams(
    types: np.ndarray, times: np.ndarray, n_shards: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side prep: pad the STREAM axis to a multiple of ``n_shards``
    with all-padding streams (types ``-1``, times ``+inf``)."""
    types = np.asarray(types, np.int32)
    times = np.asarray(times, np.float32)
    n_streams = types.shape[0]
    s_pad = max(1, -(-n_streams // n_shards)) * n_shards
    if s_pad != n_streams:
        pad = s_pad - n_streams
        types = np.concatenate(
            [types, np.full((pad, types.shape[1]), -1, np.int32)])
        times = np.concatenate(
            [times, np.full((pad, times.shape[1]), np.inf, np.float32)])
    return types, times


# staticcheck: disable=REPRO003 -- mesh path: shard_map executables
# live in jax's jit cache by design (plan.uncacheable_reason)
@functools.partial(jax.jit, static_argnames=("mesh", "axis", "n_types", "cap"))
def _build_corpus_index_impl(types, times, *, mesh, axis, n_types, cap):
    def shard_fn(ty_blk, tm_blk):
        return events_lib.type_index_batch(ty_blk, tm_blk, n_types, cap)

    fn = shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(axis, None), P(axis, None)),
        out_specs=(P(axis, None, None), P(axis, None)),
    )
    return fn(types, times)


def build_corpus_index(
    types: np.ndarray,   # i32[S, L] (-1 padding)
    times: np.ndarray,   # f32[S, L] (+inf padding)
    mesh: Mesh,
    *,
    axis: str = "data",
    n_types: int,
    cap: int,
) -> CorpusIndex:
    """One shard_map pass: every shard builds its streams' type indexes.

    No halo exchange happens (or could help): a stream lives wholly on one
    shard, so the per-stream index is exactly the single-device one.
    """
    n_streams = types.shape[0]
    types, times = pad_corpus_streams(types, times, mesh.shape[axis])
    tables, counts = _build_corpus_index_impl(
        jnp.asarray(types), jnp.asarray(times),
        mesh=mesh, axis=axis, n_types=n_types, cap=cap)
    return CorpusIndex(
        tables=tables, type_counts=counts, mesh=mesh, axis=axis,
        n_streams=n_streams)


# staticcheck: disable=REPRO003 -- mesh path: shard_map executables
# live in jax's jit cache by design (plan.uncacheable_reason)
@functools.partial(
    jax.jit,
    static_argnames=("mesh", "axis", "engine", "cap_occ", "max_window",
                     "parallel_schedule", "block_next", "block_prev",
                     "window_tiles", "interpret"),
)
def _count_corpus_sharded_impl(
    tables, type_counts, symbols, t_low, t_high, thresholds, *,
    mesh, axis, engine, cap_occ, max_window, parallel_schedule,
    block_next, block_prev, window_tiles, interpret,
):
    def shard_fn(tbl, cnt, sym, lo, hi, thr):
        # each shard counts its local streams exactly as the single-device
        # corpus counter would — no collective anywhere in the level path
        return counting.count_corpus_indexed(
            tbl, cnt, sym, lo, hi, thr,
            engine=engine, cap_occ=cap_occ, max_window=max_window,
            parallel_schedule=parallel_schedule, block_next=block_next,
            block_prev=block_prev, window_tiles=window_tiles,
            interpret=interpret)

    # unchecked for the same reason as the time-sharded counter: pallas_call
    # has no replication rule in the shard_map checker
    fn = shard_map_unchecked(
        shard_fn, mesh=mesh,
        in_specs=(P(axis, None, None), P(axis, None), P(), P(), P(), P(axis)),
        out_specs=(P(axis, None),) * 4,
    )
    return fn(tables, type_counts, symbols, t_low, t_high, thresholds)


def count_corpus_sharded_indexed(
    index: CorpusIndex,
    symbols: jax.Array,     # i32[B, N] shared candidate batch
    t_low: jax.Array,       # f32[B, N-1]
    t_high: jax.Array,      # f32[B, N-1]
    thresholds: jax.Array,  # i32[S_pad] per-stream frequency thresholds
    *,
    engine: str = "dense",
    cap_occ: Optional[int] = None,
    max_window: int = 32,
    parallel_schedule: bool = False,
    block_next: Optional[int] = None,
    block_prev: Optional[int] = None,
    window_tiles: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Stream-sharded corpus counting: the embarrassingly-parallel path.

    Same contract as :func:`counting.count_corpus_indexed` — returns
    ``(counts, keep, n_superset, overflow)``, each ``[S_pad, B]`` and
    stream-sharded over the mesh; the miner's single per-level
    ``device_get`` assembles them. Every per-stream row is bit-for-bit the
    single-device result: no halo, no merge, no tie-breaking exists on this
    axis because no occurrence can cross a stream boundary.
    """
    return _count_corpus_sharded_impl(
        index.tables, index.type_counts, jnp.asarray(symbols, jnp.int32),
        jnp.asarray(t_low, jnp.float32), jnp.asarray(t_high, jnp.float32),
        jnp.asarray(thresholds, jnp.int32),
        mesh=index.mesh, axis=index.axis, engine=engine, cap_occ=cap_occ,
        max_window=max_window, parallel_schedule=parallel_schedule,
        block_next=block_next, block_prev=block_prev,
        window_tiles=window_tiles, interpret=interpret)
