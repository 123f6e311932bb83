"""MapConcat baseline (paper §III-B, Figs 4–5) — the prior GPU approach.

The input stream is cut into S segments; each segment runs state machines in
parallel (the Map step) and per-segment results are stitched (the Concat
step). The CUDA original enumerates all possible FSM entry states by
starting machines at multiple offsets into the previous segment; on TPU the
idiomatic equivalent (DESIGN.md §2) is:

  * Map: one ring-buffer FSM (`statemachine.count_fsm_scan`) per segment,
    vmapped; each segment is extended by a halo of events from the next
    segment so occurrences *starting* in the segment can complete across the
    boundary (paper Fig 4: "continues over into the next segment to complete
    the last occurrence"). Occurrence (start,end) intervals are recorded.
  * Concat: greedy interval scheduling over the concatenated, end-sorted
    per-segment interval lists (paper Fig 5's merge, generalized).

Exactness: unlike the CUDA original (whose multi-offset merge the paper
shows to be fragile), our Map step records the *dominance superset* of
occurrence intervals per segment (latest start per completing end event,
without clearing), so the global greedy Concat is exact by construction
whenever the static capacities hold: ring size covers live same-symbol
events, occ_per_segment covers per-segment completions (overflow is
flagged), and the halo (one full segment of events) spans episode.max_span.
The *cost profile* is the point of the baseline: a sequential scan over
events inside each segment, parallel only across segments.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import lax

from .episodes import Episode, in_window
from .events import EventStream
from .statemachine import NEG
from .tracking import Occurrences
from . import scheduling


def _segment_with_halo(types, times, n_segments: int, halo: int):
    """[n] -> [S, seg+halo] with +inf padded tails; events beyond segment
    boundaries are masked for *seeding* via seg_start_time."""
    n = types.shape[0]
    seg = -(-n // n_segments)  # ceil
    padded_n = seg * n_segments + halo
    pt = jnp.full((padded_n,), jnp.inf, times.dtype).at[:n].set(times)
    py = jnp.full((padded_n,), -1, types.dtype).at[:n].set(types)
    idx = (jnp.arange(n_segments)[:, None] * seg
           + jnp.arange(seg + halo)[None, :])
    return py[idx], pt[idx], seg


def count_mapconcat(
    stream: EventStream,
    episode: Episode,
    *,
    n_segments: int = 8,
    ring: int = 8,
    occ_per_segment: int = 64,
) -> jax.Array:
    """Non-overlapped count via the MapConcat strategy."""
    types = jnp.asarray(stream.types, jnp.int32)
    times = jnp.asarray(stream.times, jnp.float32)
    n = types.shape[0]
    # halo: enough events to cover max_span past the boundary; conservative
    # static bound = all events (cap by segment length)
    seg = -(-n // n_segments)
    halo = min(n, seg)
    seg_types, seg_times, seg_len = _segment_with_halo(types, times, n_segments, halo)
    # boundary time of each segment: occurrences must START inside the segment
    seg_start_idx = jnp.arange(n_segments) * seg_len
    seg_end_time = jnp.where(
        (seg_start_idx + seg_len - 1) < n,
        times[jnp.clip(seg_start_idx + seg_len - 1, 0, n - 1)],
        jnp.inf,
    )

    nsym = episode.n
    sym, lo, hi = episode.as_arrays()

    def map_step(seg_ty, seg_tm, t_hi):
        """FSM over one segment (with halo); records occurrence intervals
        whose start time is <= segment end boundary."""
        ring_bufs = jnp.full((nsym, ring), NEG, jnp.float32)
        ring_start = jnp.full((nsym, ring), NEG, jnp.float32)  # chain start times
        heads = jnp.zeros((nsym,), jnp.int32)
        occ_s = jnp.full((occ_per_segment,), NEG, jnp.float32)
        occ_e = jnp.full((occ_per_segment,), jnp.inf, jnp.float32)
        n_occ = jnp.int32(0)

        def step(carry, ev):
            bufs, bstarts, hds, os_, oe_, cnt = carry
            e, t = ev
            valid = jnp.isfinite(t)

            def match_prev(j):
                ok = (bufs[j - 1] > NEG) & in_window(
                    t, bufs[j - 1], lo[j - 1], hi[j - 1])
                any_ok = jnp.any(ok)
                # latest start among matching predecessors (dominance)
                st = jnp.max(jnp.where(ok, bstarts[j - 1], NEG))
                return any_ok, st

            if nsym == 1:
                completes = valid & (e == sym[0]) & (t <= t_hi)
                comp_start = t
            else:
                any_ok, st = match_prev(nsym - 1)
                completes = valid & (e == sym[nsym - 1]) & any_ok
                comp_start = st

            new_bufs, new_bstarts, new_hds = bufs, bstarts, hds
            for j in range(nsym - 1):
                if j == 0:
                    add = valid & (e == sym[0]) & (t <= t_hi)
                    st_j = t
                else:
                    ok_j, st_j = match_prev(j)
                    add = valid & (e == sym[j]) & ok_j
                # NB: no `~completes` mask — without clearing, a completing
                # event must still be buffered at earlier positions it
                # matches (e.g. the last A of A->A->A seeds the next chain)
                new_bufs = jnp.where(add, new_bufs.at[j, new_hds[j]].set(t), new_bufs)
                new_bstarts = jnp.where(
                    add, new_bstarts.at[j, new_hds[j]].set(st_j), new_bstarts)
                new_hds = jnp.where(
                    add, new_hds.at[j].set((new_hds[j] + 1) % ring), new_hds)

            # record the completed occurrence interval; do NOT clear state —
            # overlap resolution is global (Concat step), mirroring the
            # speculative multi-machine Map of the paper. Entries past the
            # static capacity are dropped (overflow flagged below).
            slot = jnp.where(cnt < occ_per_segment, cnt, occ_per_segment)
            os_ = jnp.where(completes, os_.at[slot].set(comp_start, mode="drop"), os_)
            oe_ = jnp.where(completes, oe_.at[slot].set(t, mode="drop"), oe_)
            cnt = cnt + completes.astype(jnp.int32)
            return (new_bufs, new_bstarts, new_hds, os_, oe_, cnt), None

        carry0 = (ring_bufs, ring_start, heads, occ_s, occ_e, n_occ)
        (_, _, _, os_, oe_, cnt), _ = lax.scan(step, carry0, (seg_ty, seg_tm))
        return os_, oe_, cnt

    occ_s, occ_e, seg_counts = jax.vmap(map_step)(seg_types, seg_times, seg_end_time)

    # Concat: global greedy over all recorded intervals, sorted by end time
    flat_s, flat_e = occ_s.reshape(-1), occ_e.reshape(-1)
    order = jnp.argsort(flat_e)
    flat_s, flat_e = flat_s[order], flat_e[order]
    valid = jnp.isfinite(flat_e) & (flat_s > NEG)
    occ = Occurrences(starts=flat_s, ends=flat_e, valid=valid,
                      n_superset=jnp.sum(seg_counts),
                      overflow=jnp.any(seg_counts > occ_per_segment))
    return scheduling.greedy_scan(occ)
