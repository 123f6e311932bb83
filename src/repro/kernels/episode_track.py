"""Pallas TPU kernel for parallel local tracking and non-overlapped counting.

This is the compute hot-spot the paper optimizes (Algorithm 2): for every
event ``t`` of the next episode symbol, combine (max-reduce) the
latest-start values of all previous-symbol events ``s`` inside the
inter-event window ``lo < t - s <= hi`` (exact, ``core.episodes``).

TPU adaptation (DESIGN.md §2, §10). One kernel body, :func:`_rows_kernel`,
serves every Pallas path. Each grid step owns ONE episode row and walks all
of its levels with the latest-start vector resident in a ``(2, K, W)`` VMEM
ping-pong scratch (a row of ``cap`` events is laid out as ``K = cap / W``
sublane rows of ``W`` lanes, ``W`` = 128 on the chip):

* the per-(row, level, next-tile) first prev tile and exact tile count come
  from a scan table computed outside the kernel (``ops.window_scan_table``
  — the paper's per-type index made block-level: counts of the prev row's
  tile boundaries below each next tile's window bounds, no binary search)
  and arrive as a per-row SMEM block, so every read is a scalar-offset
  ``pl.ds`` slice of a ref — no vector gathers;
* for a next sub-row of ``W`` events, its exact window edges
  (``core.episodes.sub_ceil``) are transposed once into columns, and each
  prev sub-row it must scan is a ``(1, W)`` row: the window test is a
  ``(W, W)`` broadcast compare + running max, independent of ``cap`` — the
  per-step footprint is bounded by the tile, not by the stream;
* next tiles past the last one with a non-empty scan are skipped (their
  values are the ``-inf`` prefill).

Two epilogues share the walk:

* ``count=False`` (``track_batch_pallas``): the final-level latest-start
  row goes to HBM, with the tracked superset size.
* ``count=True`` (``count_batch_pallas``): the single-launch count pipeline.
  Each row's final latest-start row and end-time row are parked in a
  ``(K, R, W)`` chunk scratch; the chunk's last row then runs the exact
  ``greedy_scan_state`` recurrence (take iff ``start > prev_end``, strict —
  ties rejected) for all ``R`` rows at once, rows on lanes, events walked in
  end order over ``(W, R)`` transposed blocks. Invalid intervals carry a
  ``-inf`` start, which the strict test never takes, so the greedy over the
  uncompacted keep-masked intervals takes exactly the intervals the paper's
  §IV-D compaction + greedy would — no prefix sum, no searchsorted, no
  gather. Only O(B) scalars leave the kernel: counts, the carried
  ``(prev_end, count)`` chain state, and superset sizes.

VMEM per grid step: the row's times block ``(N, K, W)`` (double-buffered),
the ``(2, K, W)`` ping-pong, the count epilogue's ``2 * R * cap`` chunk
scratch and a few ``(W, W)`` compare temporaries — :func:`vmem_bytes`.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.episodes import sub_ceil

NEG = -jnp.inf

#: Lane width of the row layout on the chip; interpret mode may use any
#: common divisor of the tiles (see ``ops.tile_geometry``).
LANES = 128


def vmem_bytes(levels: int, pcap: int, lanes: int, rows: int, *,
               count: bool, seeded: bool = False) -> int:
    """Per-grid-step VMEM footprint of :func:`_rows_kernel` (bytes).

    Blocks are double-buffered by the pipeline; scratch is single. The
    ``(W, R)`` greedy transposes pad their lane dim to 128.
    """
    n = levels + 1
    blocks = 2 * 4 * n * pcap                    # times block
    if seeded:
        blocks += 2 * 4 * pcap                   # seed values block
    if not count:
        blocks += 2 * 4 * pcap                   # final-level values out
    scratch = 2 * 4 * pcap                       # latest-start ping-pong
    scratch += 6 * 4 * lanes * lanes             # window compare temporaries
    if count:
        scratch += 2 * 4 * rows * pcap           # chunk starts + ends
        scratch += 2 * 4 * lanes * max(rows, 128)
    return blocks + scratch


def _rows_kernel(*refs, levels: int, next_tiles: int, sub_next: int,
                 sub_prev: int, lanes: int, count: bool, seeded: bool):
    it = iter(refs)
    times_ref = next(it)          # f32 (1, N, K, W) one episode row
    # i32 SMEM (1, 1, T) scan table, level-major: start[L, NT] | num[L, NT]
    # | last[L] (next tiles to walk) | n_end (finite end events)
    tab_ref = next(it)
    win_ref = next(it)            # f32 SMEM (1, 1, 2L) window lows | highs
    seed_ref = next(it) if seeded else None      # f32 (1, K, W)
    if count:
        pend_ref = next(it)       # f32 (1, 1, R) carried prev_end
        pcnt_ref = next(it)       # i32 (1, 1, R) carried count
        cnt_ref = next(it)        # i32 (1, 1, R)
        end_ref = next(it)        # f32 (1, 1, R)
    else:
        v_ref = next(it)          # f32 (1, K, W) final-level values
    nsup_ref = next(it)           # i32 SMEM (1, 1, R) superset sizes
    vbuf = next(it)               # f32 (2, K, W) level ping-pong
    if count:
        s_all = next(it)          # f32 (K, R, W) chunk latest starts
        e_all = next(it)          # f32 (K, R, W) chunk end times
        s_blk = next(it)          # f32 (W, R) transposed greedy block
        e_blk = next(it)          # f32 (W, R)
        kmax = next(it)           # i32 SMEM (1,) chunk max end count

    w = lanes
    r = pl.program_id(1)

    def column(row):                      # (1, W) -> (W, W), [n, q] = row[n]
        return jnp.broadcast_to(row, (w, w)).T

    nt = next_tiles
    t0 = times_ref[0, 0]
    if seeded:
        vbuf[0] = seed_ref[0]
    else:
        vbuf[0] = jnp.where(jnp.isfinite(t0), t0, NEG)
    nsup = jnp.sum(jnp.isfinite(t0).astype(jnp.int32))

    for l in range(levels):
        src, dst = l % 2, 1 - l % 2
        vbuf[dst] = jnp.full(vbuf.shape[1:], NEG, jnp.float32)
        t_lo = win_ref[0, 0, l]
        t_hi = win_ref[0, 0, levels + l]

        def next_tile(i, carry, l=l, src=src, dst=dst, t_lo=t_lo, t_hi=t_hi):
            st = tab_ref[0, 0, l * nt + i]
            num = tab_ref[0, 0, (levels + l) * nt + i]
            for a in range(sub_next):
                kn = i * sub_next + a
                tn = times_ref[0, l + 1, pl.ds(kn, 1), :]          # (1, W)
                # exact window edges per next event, as [n, q] = edge[n]
                lo_b = column(sub_ceil(tn, t_hi))
                hi_b = column(sub_ceil(tn, t_lo))

                def prev_tile(j, acc, lo_b=lo_b, hi_b=hi_b):
                    for b in range(sub_prev):
                        kp = (st + j) * sub_prev + b
                        tp = times_ref[0, l, pl.ds(kp, 1), :]      # (1, W)
                        vp = vbuf[src, pl.ds(kp, 1), :]
                        ok = (tp >= lo_b) & (tp < hi_b)            # (W, W)
                        acc = jnp.maximum(acc, jnp.where(ok, vp, NEG))
                    return acc

                acc = jax.lax.fori_loop(
                    0, num, prev_tile, jnp.full((w, w), NEG, jnp.float32))
                best = jnp.max(acc.T, axis=0, keepdims=True)       # (1, W)
                vbuf[dst, pl.ds(kn, 1), :] = jnp.where(
                    jnp.isfinite(tn), best, NEG)
            return carry

        last = tab_ref[0, 0, 2 * levels * nt + l]
        jax.lax.fori_loop(0, last, next_tile, 0)
        nsup = nsup + jnp.sum((vbuf[dst] > NEG).astype(jnp.int32))

    nsup_ref[0, 0, r] = nsup
    v_fin = vbuf[levels % 2]
    if not count:
        v_ref[0] = v_fin
        return

    # --- count epilogue: park this row, then the chunk's last row folds the
    # greedy for every row of the chunk at once (rows on lanes).
    rows = s_all.shape[1]
    s_all[:, pl.ds(r, 1), :] = v_fin[:, None, :]
    e_all[:, pl.ds(r, 1), :] = times_ref[0, levels][:, None, :]
    n_end = tab_ref[0, 0, 2 * levels * nt + levels]

    @pl.when(r == 0)
    def _():
        kmax[0] = 0

    kmax[0] = jnp.maximum(kmax[0], n_end)

    @pl.when(r == rows - 1)
    def _():
        def block(k, carry):
            s_blk[...] = s_all[k].T                                # (W, R)
            e_blk[...] = e_all[k].T

            def step(j, carry):
                prev_e, cnt = carry
                s = s_blk[pl.ds(j, 1), :]
                take = s > prev_e
                return (jnp.where(take, e_blk[pl.ds(j, 1), :], prev_e),
                        cnt + take.astype(jnp.int32))

            return jax.lax.fori_loop(0, w, step, carry)

        n_blocks = (kmax[0] + w - 1) // w
        prev_e, cnt = jax.lax.fori_loop(
            0, n_blocks, block,
            (pend_ref[0], jnp.zeros((1, rows), jnp.int32)))
        cnt_ref[0] = pcnt_ref[0] + cnt
        end_ref[0] = prev_e


def _call(times4, tab, win, extra, *, block_next: int, block_prev: int,
          rows: int, count: bool, seeded: bool, interpret: bool,
          vmem_limit: Optional[int]):
    batch, n, k, w = times4.shape
    levels = n - 1
    next_tiles = (k * w) // block_next
    nchunks = batch // rows
    kernel = functools.partial(
        _rows_kernel, levels=levels, next_tiles=next_tiles,
        sub_next=block_next // w, sub_prev=block_prev // w, lanes=w,
        count=count, seeded=seeded)
    row = lambda c, r: (c * rows + r, 0, 0)              # noqa: E731
    chunk = lambda c, r: (c, 0, 0)                        # noqa: E731
    smem = pltpu.MemorySpace.SMEM
    in_specs = [
        pl.BlockSpec((1, n, k, w), lambda c, r: (c * rows + r, 0, 0, 0)),
        pl.BlockSpec((1, 1, tab.shape[-1]), row, memory_space=smem),
        pl.BlockSpec((1, 1, win.shape[-1]), row, memory_space=smem),
    ]
    if seeded:
        in_specs.append(pl.BlockSpec((1, k, w), row))
    nsup_spec = pl.BlockSpec((1, 1, rows), chunk, memory_space=smem)
    nsup_shape = jax.ShapeDtypeStruct((nchunks, 1, rows), jnp.int32)
    scratch = [pltpu.VMEM((2, k, w), jnp.float32)]
    if count:
        in_specs += [pl.BlockSpec((1, 1, rows), chunk)] * 2
        out_specs = [pl.BlockSpec((1, 1, rows), chunk)] * 2 + [nsup_spec]
        out_shape = [jax.ShapeDtypeStruct((nchunks, 1, rows), jnp.int32),
                     jax.ShapeDtypeStruct((nchunks, 1, rows), jnp.float32),
                     nsup_shape]
        scratch += [pltpu.VMEM((k, rows, w), jnp.float32)] * 2
        scratch += [pltpu.VMEM((w, rows), jnp.float32)] * 2
        scratch += [pltpu.SMEM((1,), jnp.int32)]
    else:
        out_specs = [pl.BlockSpec((1, k, w), row), nsup_spec]
        out_shape = [jax.ShapeDtypeStruct((batch, k, w), jnp.float32),
                     nsup_shape]
    return pl.pallas_call(
        kernel,
        grid=(nchunks, rows),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        interpret=interpret,
        name="count_batch" if count else "track_batch",
    )(times4, tab, win, *extra)


@functools.partial(
    jax.jit,
    static_argnames=("block_next", "block_prev", "interpret", "vmem_limit"))
def track_batch_pallas(
    times4: jax.Array,          # f32[B, N, K, W] sorted rows, +inf padded
    tab: jax.Array,             # i32[B, 1, T] scan tables (_rows_kernel)
    win: jax.Array,             # f32[B, 1, 2(N-1)] window lows | highs
    seed: Optional[jax.Array] = None,   # f32[B, K, W] level-0 values
    *,
    block_next: int,
    block_prev: int,
    interpret: bool = False,
    vmem_limit: Optional[int] = None,
) -> tuple:
    """Whole-batch multi-level tracking in one launch.

    Returns ``(starts f32[B, K, W], n_superset i32[B])``: the final-level
    latest-start values (masked to finite end events) and the per-row
    tracked superset size. ``seed`` replaces the default level-0 values
    (each first-symbol event's own time) — the per-level API passes the
    previous level's values through it.
    """
    batch = times4.shape[0]
    extra = () if seed is None else (seed,)
    v, nsup = _call(times4, tab, win, extra, block_next=block_next,
                    block_prev=block_prev, rows=1, count=False,
                    seeded=seed is not None, interpret=interpret,
                    vmem_limit=vmem_limit)
    return v, nsup.reshape(batch)


@functools.partial(
    jax.jit,
    static_argnames=("block_next", "block_prev", "rows", "interpret",
                     "vmem_limit"))
def count_batch_pallas(
    times4: jax.Array,          # f32[B, N, K, W] sorted rows, +inf padded
    tab: jax.Array,             # i32[B, 1, T] scan tables (_rows_kernel)
    win: jax.Array,             # f32[B, 1, 2(N-1)] window lows | highs
    prev_end: jax.Array,        # f32[B] carried greedy prev_end
    prev_count: jax.Array,      # i32[B] carried greedy count
    *,
    block_next: int,
    block_prev: int,
    rows: int,
    interpret: bool = False,
    vmem_limit: Optional[int] = None,
) -> tuple:
    """Whole-batch tracking + greedy counting, ONE launch.

    ``B`` must be a multiple of ``rows`` (the greedy chunk: episode rows
    folded together, on lanes). Returns ``(counts i32[B], end_out f32[B],
    n_superset i32[B])``: final non-overlapped counts (carry-in
    ``prev_count`` included), the carried-out greedy ``prev_end`` and the
    tracked superset sizes.
    """
    batch = times4.shape[0]
    nchunks = batch // rows
    cnt, end, nsup = _call(
        times4, tab, win,
        (prev_end.astype(jnp.float32).reshape(nchunks, 1, rows),
         prev_count.astype(jnp.int32).reshape(nchunks, 1, rows)),
        block_next=block_next, block_prev=block_prev, rows=rows, count=True,
        seeded=False, interpret=interpret, vmem_limit=vmem_limit)
    return cnt.reshape(batch), end.reshape(batch), nsup.reshape(batch)
