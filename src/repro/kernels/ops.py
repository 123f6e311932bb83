"""jit'd public wrappers around the Pallas kernel (+ exactness bounds).

Three entry points drive ``episode_track``'s one kernel body:

* :func:`track_level` — one tracking level, seeded with given values.
* :func:`track_batch` — a whole ``[B, N, cap]`` candidate batch's
  multi-level tracking in ONE launch, with the per-(episode, level,
  next-tile) scan table precomputed here (the paper's per-type index made
  block-level, batched) and window-cap truncation *flagged, never silent*.
* :func:`count_batch` — the same walk plus the in-kernel greedy count: the
  single-launch count pipeline.

Arrays of any capacity are accepted: they are padded up to a tile
multiple (+inf times / -inf values — a max-accumulation no-op).
:func:`tile_geometry` is the one tiling rule; compiled for the chip it
refuses tiles Mosaic cannot lay out, and every launch on the chip is sized
against :data:`VMEM_BUDGET_BYTES` before it reaches the compiler.

Each next tile's window query bounds (:func:`_tile_queries`, from the
tile's extrema) feed two formulations. The traced scan table
(:func:`window_scan_table`) needs prev *tiles* only, so it counts the prev
rows' tile boundaries below each bound: a broadcast compare and a sum, no
gather and no loop. Exact prev-*event* spans (:func:`_tile_spans`,
``searchsorted``) serve the static host-side bounds
(:func:`required_window_tiles`, :func:`required_window_tiles_batch`) and
every truncation flag of a ``window_tiles`` cap.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.episodes import sub_ceil
from . import episode_track as _et
from . import ref as _ref

NEG = -jnp.inf

# ---------------------------------------------------------------------------
# Window-span bounds (shared: host bounds + traced fused precompute)
# ---------------------------------------------------------------------------


def _searchsorted_rows(a: jax.Array, v: jax.Array) -> jax.Array:
    """Row-wise ``searchsorted(a[..., :], v[..., :], 'left')`` over any
    (shared) leading batch dims."""
    if a.ndim == 1:
        return jnp.searchsorted(a, v, side="left")
    flat_a = a.reshape(-1, a.shape[-1])
    flat_v = v.reshape(-1, v.shape[-1])
    out = jax.vmap(lambda x, y: jnp.searchsorted(x, y, side="left"))(
        flat_a, flat_v)
    return out.reshape(v.shape)


def _tile_spans(
    t_prev: jax.Array,   # f32[..., cap] sorted rows, +inf padded
    t_next: jax.Array,   # f32[..., cap] sorted rows, +inf padded
    t_high,              # f32[...] (or scalar) per-row window high
    block_next: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Per next-tile prev-event span ``[lo_idx, hi_idx)`` + occupancy mask.

    Spans are *event* indices — callers quantize to prev tiles themselves,
    so ``block_prev`` never enters this computation.

    A next tile with min ``a0`` / finite max ``a1`` needs prev events in
    ``[a0 - t_high, a1)``; rows are sorted so the tile min is element 0 and
    padding tiles (min = +inf) report ``has = False``. Returns
    ``(lo_idx, hi_idx, has)`` each shaped ``[..., next_tiles]``.
    """
    q_lo, q_hi, has = _tile_queries(t_next, t_high, block_next)
    lo_idx = _searchsorted_rows(t_prev, q_lo)
    hi_idx = _searchsorted_rows(t_prev, q_hi)
    return lo_idx.astype(jnp.int32), hi_idx.astype(jnp.int32), has


def _tile_queries(
    t_next: jax.Array, t_high, block_next: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Per next tile the window's query bounds ``(q_lo, q_hi, has)``: prev
    events ``s`` with ``q_lo <= s < q_hi`` can fall in some window of the
    tile (``q_lo = sub_ceil(tile min, t_high)``, ``q_hi`` = finite tile
    max), and ``has`` marks tiles holding an event. Each ``[..., NT]``."""
    nt = t_next.shape[-1] // block_next
    tiles = t_next[..., : nt * block_next].reshape(
        t_next.shape[:-1] + (nt, block_next))
    tile_min = tiles[..., 0]
    finite = jnp.isfinite(tiles)
    tile_max = jnp.max(jnp.where(finite, tiles, -jnp.inf), axis=-1)
    has = finite[..., 0]
    t_high = jnp.asarray(t_high, jnp.float32)[..., None]
    return sub_ceil(tile_min, t_high), tile_max, has


def _count_below(bounds: jax.Array, q: jax.Array) -> jax.Array:
    """``#{k : bounds[..., k] < q[..., j]}`` per query, ``i32[..., NT]``:
    a broadcast compare and a sum, no gather. A NaN query counts every
    bound, as the sort order of ``jnp.searchsorted`` places NaN last."""
    below = (bounds[..., None, :] < q[..., None]) | jnp.isnan(q)[..., None]
    return jnp.sum(below, axis=-1, dtype=jnp.int32)


def required_window_tiles(
    t_prev: np.ndarray, t_next: np.ndarray, t_high: float,
    block_next: int, block_prev: int,
) -> int:
    """Host-side tight bound on prev tiles any next tile's window can span.

    Vectorized (reshape + one searchsorted per side) twin of the old
    per-tile Python loop: span in events plus one tile of misalignment
    slack, maxed over occupied next tiles.
    """
    t_prev = np.asarray(t_prev)
    t_next = np.asarray(t_next)
    cap = t_prev.shape[0]
    lo_idx, hi_idx, has = (np.asarray(x) for x in _tile_spans(
        t_prev, t_next, float(t_high), block_next))
    spans = np.where(has, hi_idx - lo_idx, 0)
    tiles = int(np.max(spans // block_prev + 2, initial=1, where=has))
    return min(max(tiles, 1), cap // block_prev)


def required_window_tiles_batch(
    times_by_sym: np.ndarray,   # f32[B, N, cap] sorted rows, +inf padded
    t_high: np.ndarray,         # f32[B, N-1]
    block_next: int, block_prev: int,
) -> int:
    """Batched :func:`required_window_tiles`: one static bound covering
    every (episode, level) of a candidate batch — callers use it to pick a
    ``window_tiles`` cap that keeps the fused kernel exact."""
    times_by_sym = np.asarray(times_by_sym)
    cap = times_by_sym.shape[-1]
    lo_idx, hi_idx, has = (np.asarray(x) for x in _tile_spans(
        times_by_sym[:, :-1], times_by_sym[:, 1:], np.asarray(t_high),
        block_next))
    spans = np.where(has, hi_idx - lo_idx, 0)
    tiles = int(np.max(spans // block_prev + 2, initial=1, where=has))
    return min(max(tiles, 1), cap // block_prev)


def window_span_exceeds(
    lo_idx: jax.Array, hi_idx: jax.Array, cap: int,
    block_prev: int, window_tiles: int,
) -> jax.Array:
    """THE conservative truncation predicate (span + one tile of
    misalignment slack over the cap), shared by the per-level engine's
    check and the fused precompute so their overflow flags cannot drift."""
    span = jnp.clip(hi_idx - lo_idx, 0, cap)
    return span // block_prev + 2 > window_tiles


def window_truncated(
    t_prev: jax.Array,   # f32[cap] sorted, +inf padded
    t_next: jax.Array,   # f32[cap] sorted, +inf padded
    t_high,
    block_next: int, block_prev: int, window_tiles: int,
) -> jax.Array:
    """Traced per-level truncation flag: may any next tile's constraint
    window span more than ``window_tiles`` prev tiles?"""
    cap = t_prev.shape[-1]
    lo_idx, hi_idx, _ = _tile_spans(
        t_prev, t_next, t_high, block_next)
    return jnp.any(window_span_exceeds(
        lo_idx, hi_idx, cap, block_prev, window_tiles))


@jax.named_scope("scan_table")
def window_scan_table(
    times_by_sym: jax.Array,    # f32[B, N, cap] sorted rows, +inf padded
    t_high: jax.Array,          # f32[B, N-1]
    block_next: int,
    block_prev: int,
    window_tiles: int = 0,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Traced per-(episode, level, next-tile) scan table for the fused kernel.

    Returns ``(start_tile, num_tiles, truncated)``: the first prev tile and
    exact tile count each next tile must scan (both ``i32[B, N-1, NT]``) and
    a per-episode ``bool[B]`` truncation flag. Both tile numbers are counts
    of prev-tile boundaries below the window's query bounds. With
    ``window_tiles > 0`` the scan lengths are capped and any episode whose
    conservative span bound (``span // BP + 2``, the same formula the
    per-level engine checks) may exceed the cap is flagged — capping is
    *reported*, never silent; only this flag searches for event spans. Its
    operations carry the named scope ``scan_table`` in the HLO metadata.
    """
    cap = times_by_sym.shape[-1]
    prev_tiles = cap // block_prev
    t_prev = times_by_sym[:, :-1]
    q_lo, q_hi, has = _tile_queries(times_by_sym[:, 1:], t_high, block_next)
    # On sorted rows, lo_idx // BP counts the prev tiles whose last event
    # lies below q_lo, and ceil(hi_idx / BP) the prev tiles whose first
    # event lies below q_hi: the tile boundaries alone give both.
    last = jax.lax.slice_in_dim(t_prev, block_prev - 1, cap, block_prev, -1)
    first = jax.lax.slice_in_dim(t_prev, 0, cap, block_prev, -1)
    start = _count_below(last, q_lo)
    end = _count_below(first, q_hi)
    num = jnp.where(has, jnp.maximum(end - start, 0), 0)
    if 0 < window_tiles < prev_tiles:
        # the flag bounds exact event spans, which tile counts cannot give
        lo_idx, hi_idx, _ = _tile_spans(
            t_prev, times_by_sym[:, 1:], t_high, block_next)
        truncated = jnp.any(window_span_exceeds(
            lo_idx, hi_idx, cap, block_prev, window_tiles), axis=(1, 2))
        num = jnp.minimum(num, window_tiles)
    else:
        truncated = jnp.zeros((times_by_sym.shape[0],), bool)
    start = jnp.clip(start, 0, max(prev_tiles - 1, 0))
    return start.astype(jnp.int32), num.astype(jnp.int32), truncated


# ---------------------------------------------------------------------------
# Tile geometry (one rule for every Pallas path)
# ---------------------------------------------------------------------------

#: Per-grid-step VMEM the kernel may plan for on the chip (v5e has 128 MiB
#: of VMEM per core; the compiler's default scoped limit is 16 MiB, so
#: launches above it raise ``vmem_limit_bytes`` explicitly).
VMEM_BUDGET_BYTES = 48 * 2**20
_VMEM_HEADROOM_BYTES = 8 * 2**20
_DEFAULT_SCOPED_VMEM_BYTES = 16 * 2**20


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` = interpret mode anywhere but on a TPU backend."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def lanes(block_next: int, block_prev: int) -> int:
    """Lane width ``W`` of the kernel's ``[K, W]`` row layout: the largest
    common divisor of both tiles up to 128 (128 whenever the chip runs)."""
    return math.gcd(block_next, block_prev, _et.LANES)


def tile_geometry(cap: int, block_next: int, block_prev: int, *,
                  interpret: Optional[bool] = None) -> Tuple[int, int, int]:
    """(bn, bp, padded_cap): the ONE tiling rule every Pallas path shares —
    blocks kept as requested, capacity rounded up to their lcm. Padding
    with +inf times / -inf values is a max-accumulation no-op, so tiling
    efficiency never degrades toward block size 1 for prime or odd
    capacities. The truncation-flag parity between the ``dense_pallas``
    and ``dense_pallas_fused`` engines depends on this rule being
    single-sourced (tracking._pallas_tile_geometry delegates here).

    Compiled for the chip (``interpret`` False), both tiles must be
    multiples of the 128-lane vreg width; anything else raises here, before
    Mosaic sees it. Interpret mode accepts any positive tile.
    """
    bn = int(block_next)
    bp = int(block_prev)
    if bn < 1 or bp < 1:
        raise ValueError(f"tiles must be positive, got ({bn}, {bp})")
    if not resolve_interpret(interpret) and (bn % _et.LANES or bp % _et.LANES):
        raise ValueError(
            f"block_next={bn} / block_prev={bp}: the TPU kernel needs tiles "
            f"that are multiples of {_et.LANES} lanes")
    tile = math.lcm(bn, bp)
    pcap = ((cap + tile - 1) // tile) * tile
    return bn, bp, pcap


def _vmem_limit(need: int, interpret: bool) -> Optional[int]:
    if interpret:
        return None
    if need > VMEM_BUDGET_BYTES:
        raise ValueError(
            f"kernel needs ~{need / 2**20:.1f} MiB of VMEM per grid step "
            f"(budget {VMEM_BUDGET_BYTES // 2**20} MiB): lower cap (the "
            "per-type event capacity), the episode length or chunk")
    return max(_DEFAULT_SCOPED_VMEM_BYTES, need + _VMEM_HEADROOM_BYTES)


def count_rows(chunk: int, batch: int, levels: int, pcap: int, w: int, *,
               interpret: bool) -> int:
    """Episode rows per greedy chunk of the count kernel.

    At most ``chunk`` and the (8-rounded, on the chip) batch; on the chip
    halved until the chunk scratch fits :data:`VMEM_BUDGET_BYTES`.
    """
    rows = max(1, min(int(chunk), batch))
    if interpret:
        return rows
    rows = -(-rows // 8) * 8
    while rows > 8 and _et.vmem_bytes(levels, pcap, w, rows,
                                      count=True) > VMEM_BUDGET_BYTES:
        rows //= 2
    return rows


def kernel_vmem_bytes(kind: str, levels: int, cap: int, block_next: int,
                      block_prev: int, chunk: int, batch: int) -> int:
    """Chip VMEM estimate for one launch of ``kind`` (``"count"`` |
    ``"track"``), as :func:`count_batch` / :func:`track_batch` would size
    it — the number staticcheck REPRO104 holds against the budget."""
    bn, bp, pcap = tile_geometry(cap, block_next, block_prev,
                                 interpret=False)
    w = lanes(bn, bp)
    if kind == "count":
        rows = count_rows(chunk, batch, levels, pcap, w, interpret=False)
        return _et.vmem_bytes(levels, pcap, w, rows, count=True)
    if kind == "track":
        return _et.vmem_bytes(levels, pcap, w, 1, count=False)
    raise ValueError(f"unknown kernel kind {kind!r}")


def _pad_tail(x: jax.Array, pcap: int, fill) -> jax.Array:
    cap = x.shape[-1]
    if pcap == cap:
        return x
    pad = jnp.full(x.shape[:-1] + (pcap - cap,), fill, x.dtype)
    return jnp.concatenate([x, pad], axis=-1)


def _pad_rows(x: jax.Array, rows: int, fill) -> jax.Array:
    b = x.shape[0]
    if rows == b:
        return x
    return jnp.concatenate(
        [x, jnp.full((rows - b,) + x.shape[1:], fill, x.dtype)])


def _scan_operands(padded, t_low, t_high, bn, bp, window_tiles):
    """Kernel operands for ``[B, N, pcap]`` padded rows: the ``[B, N, K, W]``
    row layout, the per-row i32 scan table (layout in
    ``episode_track._rows_kernel``), the f32 window table, and the per-row
    truncation flag."""
    batch, n, pcap = padded.shape
    w = lanes(bn, bp)
    start, num, truncated = window_scan_table(
        padded, t_high, bn, bp, window_tiles)
    nt = start.shape[-1]
    # scan next tiles up to the last one with a non-empty window; the rest
    # keep the kernel's -inf prefill
    last = jnp.max(jnp.where(num > 0, jnp.arange(1, nt + 1), 0), axis=-1)
    n_end = jnp.sum(jnp.isfinite(padded[:, -1, :]), axis=-1)
    tab = jnp.concatenate(
        [start.reshape(batch, -1), num.reshape(batch, -1),
         last.astype(jnp.int32), n_end.astype(jnp.int32)[:, None]], axis=-1)
    win = jnp.concatenate(
        [jnp.asarray(t_low, jnp.float32).reshape(batch, -1),
         jnp.asarray(t_high, jnp.float32).reshape(batch, -1)], axis=-1)
    return (padded.reshape(batch, n, pcap // w, w), tab[:, None, :],
            win[:, None, :], truncated)


# ---------------------------------------------------------------------------
# Per-level kernel wrapper
# ---------------------------------------------------------------------------


def track_level(
    t_prev: jax.Array,
    v_prev: jax.Array,
    t_next: jax.Array,
    t_low: float,
    t_high: float,
    *,
    block_next: int = 128,
    block_prev: int = 128,
    window_tiles: int = 0,
    use_kernel: bool = True,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """One tracking level; the batched kernel on a one-row, one-level batch
    seeded with ``v_prev``. ``use_kernel=False`` runs the jnp oracle."""
    if not use_kernel:
        return _ref.track_level_ref(t_prev, v_prev, t_next, t_low, t_high)
    interpret = resolve_interpret(interpret)
    cap = t_prev.shape[0]
    bn, bp, pcap = tile_geometry(cap, block_next, block_prev,
                                 interpret=interpret)
    w = lanes(bn, bp)
    rows = jnp.stack([_pad_tail(jnp.asarray(t_prev, jnp.float32), pcap,
                                jnp.inf),
                      _pad_tail(jnp.asarray(t_next, jnp.float32), pcap,
                                jnp.inf)])[None]
    times4, tab, win, _ = _scan_operands(
        rows, jnp.full((1, 1), t_low, jnp.float32),
        jnp.full((1, 1), t_high, jnp.float32), bn, bp, window_tiles)
    seed = _pad_tail(jnp.asarray(v_prev, jnp.float32), pcap, NEG)
    out, _ = _et.track_batch_pallas(
        times4, tab, win, seed.reshape(1, pcap // w, w),
        block_next=bn, block_prev=bp, interpret=interpret,
        vmem_limit=_vmem_limit(
            _et.vmem_bytes(1, pcap, w, 1, count=False, seeded=True),
            interpret))
    return out.reshape(pcap)[:cap]


def track_episode(
    times_by_sym: jax.Array,   # f32[N, cap]
    t_low,
    t_high,
    *,
    block_next: int = 128,
    block_prev: int = 128,
    window_tiles: int = 0,
    interpret: Optional[bool] = None,
):
    """Full multi-level tracking through the kernel; returns (starts, ends)."""
    n = times_by_sym.shape[0]
    t0 = times_by_sym[0]
    v = jnp.where(jnp.isfinite(t0), t0, NEG)
    lows = np.asarray(t_low, np.float32).reshape(-1)
    highs = np.asarray(t_high, np.float32).reshape(-1)
    for i in range(n - 1):
        v = track_level(
            times_by_sym[i], v, times_by_sym[i + 1],
            float(lows[i]), float(highs[i]),
            block_next=block_next, block_prev=block_prev,
            window_tiles=window_tiles, interpret=interpret)
        v = jnp.where(jnp.isfinite(times_by_sym[i + 1]), v, NEG)
    ends = times_by_sym[n - 1]
    valid = (v > NEG) & jnp.isfinite(ends)
    return jnp.where(valid, v, NEG), jnp.where(valid, ends, jnp.inf)


# ---------------------------------------------------------------------------
# Fused batched multi-level wrapper
# ---------------------------------------------------------------------------


def _fold_lead(fn, times_by_sym, t_low, t_high, *carries, **kw):
    """Fold stacked leading dims (a ``[S, B, ...]`` corpus) into the kernel's
    one batch axis — THE one fold; per-row scan tables are row-independent,
    so the flattened layout is fold-invariant — and unfold the outputs."""
    lead = times_by_sym.shape[:-2]
    rows = math.prod(lead)
    outs = fn(times_by_sym.reshape((rows,) + times_by_sym.shape[-2:]),
              t_low.reshape((rows,) + t_low.shape[-1:]),
              t_high.reshape((rows,) + t_high.shape[-1:]),
              *(jnp.reshape(c, rows) for c in carries), **kw)
    return tuple(o.reshape(lead + o.shape[1:]) for o in outs)


def track_batch(
    times_by_sym: jax.Array,    # f32[..., N, cap] sorted rows, +inf padded
    t_low: jax.Array,           # f32[..., N-1]
    t_high: jax.Array,          # f32[..., N-1]
    *,
    block_next: int = 128,
    block_prev: int = 128,
    window_tiles: int = 0,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Whole candidate batch, all levels, one fused Pallas launch.

    Returns ``(starts f32[B, cap], n_superset i32[B], truncated bool[B])``.
    ``starts`` holds the final-level latest-start values (-inf where no
    occurrence ends at that event); validity masking against the last
    symbol's times is the caller's (engine's) job, mirroring
    ``track_episode``. ``window_tiles`` caps the per-tile scan length for a
    latency bound — possible truncation is flagged, never silent.

    Stream axis: stacked leading dims — a ``[S, B, N, cap]`` corpus of
    ``S`` streams by ``B`` episodes — fold into the kernel's batch axis.
    """
    kw = dict(block_next=block_next, block_prev=block_prev,
              window_tiles=window_tiles, interpret=interpret)
    if times_by_sym.ndim > 3:
        return _fold_lead(track_batch, times_by_sym, t_low, t_high, **kw)
    interpret = resolve_interpret(interpret)
    batch, n, cap = times_by_sym.shape
    t0 = times_by_sym[:, 0, :]
    if n == 1:  # no transitions: every first-symbol event is an occurrence
        starts = jnp.where(jnp.isfinite(t0), t0, NEG)
        nsup = jnp.sum(jnp.isfinite(t0), axis=-1).astype(jnp.int32)
        return starts, nsup, jnp.zeros((batch,), bool)
    bn, bp, pcap = tile_geometry(cap, block_next, block_prev,
                                 interpret=interpret)
    times4, tab, win, truncated = _scan_operands(
        _pad_tail(times_by_sym, pcap, jnp.inf), t_low, t_high, bn, bp,
        window_tiles)
    starts, nsup = _et.track_batch_pallas(
        times4, tab, win, block_next=bn, block_prev=bp, interpret=interpret,
        vmem_limit=_vmem_limit(
            _et.vmem_bytes(n - 1, pcap, lanes(bn, bp), 1, count=False),
            interpret))
    return starts.reshape(batch, pcap)[:, :cap], nsup, truncated


def track_corpus(
    times_by_sym: jax.Array,    # f32[S, B, N, cap] sorted rows, +inf padded
    t_low: jax.Array,           # f32[B, N-1] shared across streams
    t_high: jax.Array,          # f32[B, N-1]
    *,
    block_next: int = 128,
    block_prev: int = 128,
    window_tiles: int = 0,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """A whole corpus of streams x candidate batch in one fused launch.

    The stream axis folds into the kernel's batch axis — ``(stream,
    episode)`` rows are independent, and each folded row's scan offsets
    come from its own stream's per-type index — so ragged stream lengths
    cost +inf padding inside ``cap``, never extra launches.

    Returns ``(starts f32[S, B, cap], n_superset i32[S, B],
    truncated bool[S, B])``; the per-episode windows are broadcast over the
    stream axis (the corpus miner counts one shared candidate frontier
    against every stream).
    """
    s = times_by_sym.shape[0]
    t_low = jnp.broadcast_to(
        jnp.asarray(t_low, jnp.float32)[None], (s,) + t_low.shape)
    t_high = jnp.broadcast_to(
        jnp.asarray(t_high, jnp.float32)[None], (s,) + t_high.shape)
    return track_batch(
        times_by_sym, t_low, t_high,
        block_next=block_next, block_prev=block_prev,
        window_tiles=window_tiles, interpret=interpret)


# ---------------------------------------------------------------------------
# Fused single-launch count pipeline wrapper
# ---------------------------------------------------------------------------


def count_batch(
    times_by_sym: jax.Array,    # f32[..., N, cap] sorted rows, +inf padded
    t_low: jax.Array,           # f32[..., N-1]
    t_high: jax.Array,          # f32[..., N-1]
    prev_end: jax.Array,        # f32[...] carried greedy prev_end
    prev_count: jax.Array,      # i32[...] carried greedy count
    *,
    block_next: int = 128,
    block_prev: int = 128,
    window_tiles: int = 0,
    chunk: int = 32,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Whole candidate batch, tracking + greedy, one launch.

    Returns ``(counts i32[B], end_out f32[B], n_superset i32[B],
    truncated bool[B])``. Counts include the ``prev_count`` carry-in and
    ``end_out`` is the carried greedy state, so streaming chain-state
    stitching works exactly as with the track + host-greedy path. The
    per-(episode, level) latest-start tables and occurrence intervals never
    leave VMEM — only these O(B) scalars do. ``chunk`` bounds how many
    episode rows share one vectorized greedy fold (:func:`count_rows`).

    Stream axis: stacked leading dims fold into the kernel's batch axis,
    mirroring :func:`track_batch`.
    """
    kw = dict(block_next=block_next, block_prev=block_prev,
              window_tiles=window_tiles, chunk=chunk, interpret=interpret)
    if times_by_sym.ndim > 3:
        return _fold_lead(count_batch, times_by_sym, t_low, t_high,
                          prev_end, prev_count, **kw)
    interpret = resolve_interpret(interpret)
    batch, n, cap = times_by_sym.shape
    prev_end = jnp.asarray(prev_end, jnp.float32)
    prev_count = jnp.asarray(prev_count, jnp.int32)
    if n == 1:
        # No transitions: every first-symbol event is a [t, t] occurrence.
        # Greedy over sorted point intervals takes each finite time strictly
        # greater than both the running prev_end and its predecessor (ties
        # rejected, matching greedy_scan_state's strict `start > prev_end`).
        t0 = times_by_sym[:, 0, :]
        finite = jnp.isfinite(t0)
        pred = jnp.concatenate(
            [jnp.full((batch, 1), NEG, t0.dtype), t0[:, :-1]], axis=1)
        take = finite & (t0 > prev_end[:, None]) & (t0 > pred)
        cnt = jnp.sum(take, axis=1).astype(jnp.int32)
        last = jnp.max(jnp.where(take, t0, NEG), axis=1)
        end_out = jnp.where(cnt > 0, last, prev_end)
        nsup = jnp.sum(finite, axis=-1).astype(jnp.int32)
        return prev_count + cnt, end_out, nsup, jnp.zeros((batch,), bool)
    bn, bp, pcap = tile_geometry(cap, block_next, block_prev,
                                 interpret=interpret)
    w = lanes(bn, bp)
    rows = count_rows(chunk, batch, n - 1, pcap, w, interpret=interpret)
    total = -(-batch // rows) * rows     # +inf rows scan zero tiles: no-ops
    times4, tab, win, truncated = _scan_operands(
        _pad_rows(_pad_tail(times_by_sym, pcap, jnp.inf), total, jnp.inf),
        _pad_rows(jnp.asarray(t_low, jnp.float32), total, 0),
        _pad_rows(jnp.asarray(t_high, jnp.float32), total, 0), bn, bp,
        window_tiles)
    counts, end_out, nsup = _et.count_batch_pallas(
        times4, tab, win, _pad_rows(prev_end, total, NEG),
        _pad_rows(prev_count, total, 0),
        block_next=bn, block_prev=bp, rows=rows, interpret=interpret,
        vmem_limit=_vmem_limit(
            _et.vmem_bytes(n - 1, pcap, w, rows, count=True), interpret))
    return (counts[:batch], end_out[:batch], nsup[:batch],
            truncated[:batch])


def count_corpus(
    times_by_sym: jax.Array,    # f32[S, B, N, cap] sorted rows, +inf padded
    t_low: jax.Array,           # f32[B, N-1] shared across streams
    t_high: jax.Array,          # f32[B, N-1]
    *,
    block_next: int = 128,
    block_prev: int = 128,
    window_tiles: int = 0,
    chunk: int = 32,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Corpus count: streams x episodes folded into one fused count launch.

    Fresh (-inf, 0) carries per (stream, episode) row — corpus counting is
    stateless. Returns ``(counts i32[S, B], end_out f32[S, B],
    n_superset i32[S, B], truncated bool[S, B])``.
    """
    s, b = times_by_sym.shape[0], times_by_sym.shape[1]
    t_low = jnp.broadcast_to(
        jnp.asarray(t_low, jnp.float32)[None], (s,) + t_low.shape)
    t_high = jnp.broadcast_to(
        jnp.asarray(t_high, jnp.float32)[None], (s,) + t_high.shape)
    return count_batch(
        times_by_sym, t_low, t_high,
        jnp.full((s, b), NEG, jnp.float32), jnp.zeros((s, b), jnp.int32),
        block_next=block_next, block_prev=block_prev,
        window_tiles=window_tiles, chunk=chunk, interpret=interpret)
