"""Level-wise (Apriori-style) frequent episode discovery (paper §II-C).

At each level N, candidate N-node episodes are generated from frequent
(N-1)-node episodes by the standard suffix/prefix join (alpha[1:] ==
beta[:-1]); their non-overlapped counts are obtained in one batched
(vmapped) pass over the stream — the counting step the paper accelerates —
and candidates below the frequency threshold are pruned (anti-monotonicity
of the non-overlapped count under sub-episodes guarantees completeness).

Device-resident design (DESIGN.md §5): the search loop never materializes
Python episode objects. Candidates live as padded ``i32[B, N]`` symbol
arrays (windows are uniform per MinerConfig, so ``f32[B, N-1]`` windows are
broadcast fills), the suffix/prefix join is a vectorized group-by over
symbol rows (:func:`generate_candidates_arrays`), the per-type event index
is built **once per stream** and reused by every level through
``counting.count_batch_indexed``, and threshold pruning is computed on
device — each level pays exactly one host sync, fetching (counts, keep
mask, overflow) in a single transfer. The classic Episode-list API
(:func:`mine`, :func:`generate_candidates`) remains as a thin wrapper and
as the join's reference implementation.

The paper's focus is the *later* levels, where few-but-long episodes leave
a one-thread-per-episode scheme under-utilized; here every level uses the
data-parallel counting engines of counting.py, so parallelism is over
(episodes x events) regardless of level. With a natively-batched engine
(``dense_pallas_fused``) the whole level is ONE fused kernel launch:
``count_batch_indexed`` dispatches the entire candidate batch through the
engine's ``track_batch`` instead of vmapping B per-episode pipelines.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from . import counting, distributed, obs
from . import events as events_lib
from . import plan as plan_mod
from .episodes import (Episode, episode_batch, episodes_from_rows,
                       window_f32)
from .events import EventStream

MAX_BATCH_PAD = 16  # minimum candidate-batch capacity class (see _pad_to)


@dataclasses.dataclass
class MinerConfig:
    t_low: float                 # shared inter-event window (low, high]
    t_high: float
    threshold: int               # minimum non-overlapped count
    level_thresholds: Optional[Dict[int, int]] = None  # per-level override
    max_level: int = 4
    engine: str = "dense"        # any registered tracking engine (tracking.py)
    cap: Optional[int] = None    # per-type event capacity (default: n_events)
    cap_occ: Optional[int] = None
    max_window: int = 32
    parallel_schedule: bool = False  # greedy_parallel (O(log^2 n) depth)
                                     # instead of the lax.scan scheduler
    max_candidates: int = 4096   # safety valve per level
    min_streams: Optional[int] = None  # corpus aggregation: episodes frequent
                                       # in >= this many streams (mine_corpus)
    # Pallas tile shape: None = per-(L, N, B)-bucket tuned tiles from
    # kernels/tuned_configs.json (kernels.autotune; legacy 256/256/0 when no
    # entry exists); explicit integers bypass the tuned table
    block_next: Optional[int] = None
    block_prev: Optional[int] = None
    window_tiles: Optional[int] = None   # 0 = exact full-window coverage
    interpret: Optional[bool] = None  # None = interpret off-TPU
    # multi-device sharding: give a mesh and mine()/mine_arrays() dispatch
    # to mine_sharded (stream sharded over `shard_axis`, every level's
    # candidate batch tracked inside shard_map; see core/distributed.py)
    mesh: Optional[Mesh] = None
    shard_axis: str = "data"
    n_shards: Optional[int] = None   # default: mesh axis size
    halo: int = 256              # events of right-neighbor lookahead per shard


@dataclasses.dataclass
class LevelResult:
    episodes: List[Episode]
    counts: List[int]
    n_candidates: int


@dataclasses.dataclass
class LevelArrays:
    """Array-form per-level result: surviving episodes as symbol rows."""

    symbols: np.ndarray     # i32[F, N] surviving (frequent) episodes
    counts: np.ndarray      # i32[F] their non-overlapped counts
    n_candidates: int       # candidates generated at this level (pre-prune)


def _pad_to(n: int) -> int:
    """Candidate batches pad to capacity classes (pow2, floor 16) — the
    same rounding rule the MiningPlan bucket and ``autotune.bucket_key``
    use (plan.capacity_class), so a miner-padded batch always arrives at
    the counting adapters already bucket-aligned: zero re-padding, and the
    executable cache compiles O(#batch classes) times per level."""
    return plan_mod.capacity_class(n, floor=MAX_BATCH_PAD)


def _resolve_cap(cfg: MinerConfig, stream: EventStream) -> int:
    """Explicit cfg.cap wins even when falsy (`is None`, not `or`: a cap of
    0 must surface as events.type_index's loud ValueError, not silently
    become the per-stream default — the old idiom hid exactly that bug)."""
    return max(1, stream.n_events) if cfg.cap is None else cfg.cap


def generate_candidates(
    frequent: Sequence[Episode], level: int, cfg: MinerConfig
) -> List[Episode]:
    """Suffix/prefix join of frequent (level-1)-node episodes (list form).

    Reference implementation; :func:`generate_candidates_arrays` is the
    vectorized twin used by the miner and must match it element-for-element.
    """
    if level == 2:
        types = sorted({e.symbols[0] for e in frequent})
        return [
            Episode((a, b), (cfg.t_low,), (cfg.t_high,))
            for a in types
            for b in types
        ][: cfg.max_candidates]
    by_prefix: Dict[Tuple[int, ...], List[Episode]] = {}
    for e in frequent:
        by_prefix.setdefault(e.symbols[:-1], []).append(e)
    out: List[Episode] = []
    for alpha in frequent:
        for beta in by_prefix.get(alpha.symbols[1:], []):
            out.append(
                Episode(
                    alpha.symbols + (beta.symbols[-1],),
                    alpha.t_low + (cfg.t_low,),
                    alpha.t_high + (cfg.t_high,),
                )
            )
            if len(out) >= cfg.max_candidates:
                return out
    return out


def generate_candidates_arrays(
    frequent: np.ndarray, level: int, cfg: MinerConfig
) -> np.ndarray:
    """Vectorized suffix/prefix join over symbol rows.

    Args:
      frequent: i32[F, level-1] symbol rows of the frequent episodes from
        the previous level, in discovery order.

    Returns i32[B, level] candidate rows in exactly the order of
    :func:`generate_candidates` (property-tested), truncated to
    ``cfg.max_candidates``.
    """
    f = np.asarray(frequent, np.int32).reshape(-1, max(level - 1, 1))
    if f.shape[0] == 0:
        return np.zeros((0, level), np.int32)
    if level == 2:
        types = np.unique(f[:, 0])            # ascending, deduped
        a = np.repeat(types, types.size)      # a-major, b-minor nesting
        b = np.tile(types, types.size)
        return np.stack([a, b], axis=1).astype(np.int32)[: cfg.max_candidates]
    prefix, suffix = f[:, :-1], f[:, 1:]
    nf = f.shape[0]
    # Dense ids for (N-2)-symbol rows so the join is integer searchsorted.
    _, inv = np.unique(
        np.concatenate([prefix, suffix], axis=0), axis=0, return_inverse=True)
    pref_id, suf_id = inv[:nf], inv[nf:]
    # Betas grouped by prefix id; stable sort keeps discovery order in-group
    # (matches the dict-of-lists insertion order of the reference join).
    order = np.argsort(pref_id, kind="stable")
    lo = np.searchsorted(pref_id[order], suf_id, side="left")
    hi = np.searchsorted(pref_id[order], suf_id, side="right")
    reps = hi - lo                            # betas joined per alpha
    total = int(reps.sum())
    if total == 0:
        return np.zeros((0, level), np.int32)
    alpha_rows = np.repeat(np.arange(nf), reps)
    group_start = np.cumsum(reps) - reps
    within = np.arange(total) - np.repeat(group_start, reps)
    beta_rows = order[np.repeat(lo, reps) + within]
    out = np.concatenate([f[alpha_rows], f[beta_rows, -1:]], axis=1)
    return out.astype(np.int32)[: cfg.max_candidates]


def count_candidates(
    stream: EventStream, candidates: Sequence[Episode], cfg: MinerConfig
) -> np.ndarray:
    """Batched counting of equal-length candidates (padded for compile reuse)."""
    if not candidates:
        return np.zeros((0,), np.int32)
    b = len(candidates)
    bp = _pad_to(b)
    padded = list(candidates) + [candidates[0]] * (bp - b)
    sym, lo, hi = episode_batch(padded)
    cap = _resolve_cap(cfg, stream)
    counts, _, overflow = counting.count_batch(
        stream.types, stream.times, sym, lo, hi,
        n_types=stream.n_types, cap=cap, engine=cfg.engine,
        cap_occ=cfg.cap_occ, max_window=cfg.max_window,
        parallel_schedule=cfg.parallel_schedule,
        block_next=cfg.block_next, block_prev=cfg.block_prev,
        window_tiles=cfg.window_tiles, interpret=cfg.interpret)
    counts = np.asarray(counts)[:b]
    if bool(np.any(np.asarray(overflow)[:b])):
        raise RuntimeError(
            "episode counting overflowed static capacity or truncated a "
            "constraint window; raise cap/cap_occ/max_window/window_tiles")
    return counts


_OVERFLOW_MSG = (
    "episode counting overflowed static capacity or truncated a "
    "constraint window; raise cap/cap_occ/max_window/window_tiles")


def pad_candidate_rows(cands: np.ndarray, level: int, cfg: MinerConfig):
    """Pad a non-empty candidate-row batch to a MAX_BATCH_PAD multiple
    (repeating row 0 — counted, then discarded) and broadcast the uniform
    windows; returns ``(sym, lo, hi)`` device arrays. Shared by the
    single-stream miner and the corpus miner's union frontier."""
    b = cands.shape[0]
    bp = _pad_to(b)
    sym = np.concatenate([cands, np.broadcast_to(cands[:1], (bp - b, level))])
    lo = jnp.full((bp, level - 1), window_f32(cfg.t_low))
    hi = jnp.full((bp, level - 1), window_f32(cfg.t_high))
    return jnp.asarray(sym), lo, hi


def _padded_level_batch(frequent: np.ndarray, level: int, cfg: MinerConfig):
    """Join + pad one level's candidates: returns ``(cands, sym, lo, hi)``
    where ``sym`` is padded to a MAX_BATCH_PAD multiple (or ``None`` when
    the join is empty) and lo/hi are the broadcast uniform windows."""
    cands = generate_candidates_arrays(frequent, level, cfg)
    if cands.shape[0] == 0:
        return cands, None, None, None
    sym, lo, hi = pad_candidate_rows(cands, level, cfg)
    return cands, sym, lo, hi


def _prune_level(frequent_types: np.ndarray, counts: np.ndarray,
                 n_types: int) -> LevelArrays:
    """Level-1 result from the per-type counts and a frequency threshold."""
    return LevelArrays(frequent_types[:, None],
                       counts[frequent_types].astype(np.int32), n_types)


def _mine_levels(cfg: MinerConfig, level1: LevelArrays,
                 count_level) -> Dict[int, LevelArrays]:
    """The Apriori level loop shared by the local and sharded miners.

    ``count_level(sym, lo, hi) -> (counts_dev, checks)`` counts one padded
    candidate batch on device; ``checks`` is a list of ``(message,
    flags_dev[B])`` pairs raised on when any flag is set. Each level pays
    exactly ONE host sync: counts, keep mask, and every check flag come
    back in a single ``device_get``.

    Each level emits the spans ``repro.mine.join`` (the numpy join, padding
    and upload), ``repro.mine.count`` (the count launch and keep mask;
    stats ``rows``, the real candidates, and ``padded``, the rows counted)
    and ``repro.mine.sync`` (the fetch); the bookkeeping after the fetch
    lies outside all three.
    """
    results = {1: level1}
    frequent = level1.symbols
    for level in range(2, cfg.max_level + 1):
        if frequent.shape[0] == 0:
            break
        with obs.span("mine.join", level=level):
            cands, sym, lo, hi = _padded_level_batch(frequent, level, cfg)
        b = cands.shape[0]
        if b == 0:
            results[level] = LevelArrays(
                np.zeros((0, level), np.int32), np.zeros((0,), np.int32), 0)
            break
        thr = (cfg.level_thresholds or {}).get(level, cfg.threshold)
        with obs.span("mine.count", level=level, rows=b,
                      padded=sym.shape[0]):
            counts_dev, checks = count_level(sym, lo, hi)
            keep_dev = counts_dev >= jnp.int32(thr)         # pruned on device
        with obs.span("mine.sync", level=level):
            # staticcheck: disable=REPRO004 -- THE sanctioned one-sync-per-level
            fetched = jax.device_get(
                (counts_dev[:b], keep_dev[:b])
                + tuple(flags[:b] for _, flags in checks))
        counts_h, keep_h = fetched[0], fetched[1]
        for (message, _), flags_h in zip(checks, fetched[2:]):
            if bool(np.any(flags_h)):
                raise RuntimeError(message)
        frequent = cands[keep_h]
        results[level] = LevelArrays(
            frequent, np.asarray(counts_h)[keep_h].astype(np.int32), b)
    return results


def mine_arrays(stream: EventStream, cfg: MinerConfig) -> Dict[int, LevelArrays]:
    """Device-resident level-wise mining; returns per-level symbol arrays.

    The per-type index is built once; each level runs candidate counting +
    threshold pruning on device and syncs exactly once (counts, keep mask,
    overflow in a single ``device_get``). The candidate join runs on host
    over compact int32 arrays — it is O(B) numpy work between device
    launches, never per-episode Python.

    With ``cfg.mesh`` set, the same search runs sharded over the mesh via
    :func:`mine_sharded` (identical results, differentially tested).
    """
    if cfg.mesh is not None:
        return mine_sharded(stream, cfg)
    cap = _resolve_cap(cfg, stream)
    with obs.span("mine", events=stream.n_events, cap=cap):
        with obs.span("mine.index"):
            table, type_counts = events_lib.type_index(
                stream.types, stream.times, stream.n_types, cap)  # built ONCE
            # pad the index ONCE to its capacity class (+inf columns are
            # inert): every level's counting call then lands exactly on its
            # plan bucket — zero per-call padding, and streams of nearby
            # lengths share one cached executable. build_cap keeps overflow
            # semantics at the true build width (plan.py / DESIGN.md §11).
            table = plan_mod.pad_width(
                table, plan_mod.capacity_class(cap), jnp.inf)

        # level 1: single-type episodes; count = per-type event count
        with obs.span("mine.sync", level=1):
            binc = np.asarray(type_counts)                  # level-1 host sync
        freq_types = np.nonzero(binc >= cfg.threshold)[0].astype(np.int32)

        def count_level(sym, lo, hi):
            counts_dev, _, overflow = counting.count_batch_indexed(
                table, type_counts, sym, lo, hi,
                engine=cfg.engine, cap_occ=cfg.cap_occ,
                max_window=cfg.max_window,
                parallel_schedule=cfg.parallel_schedule,
                block_next=cfg.block_next, block_prev=cfg.block_prev,
                window_tiles=cfg.window_tiles, interpret=cfg.interpret,
                build_cap=cap)
            return counts_dev, [(_OVERFLOW_MSG, overflow)]

        return _mine_levels(
            cfg, _prune_level(freq_types, binc, stream.n_types), count_level)


def mine_sharded(stream: EventStream, cfg: MinerConfig) -> Dict[int, LevelArrays]:
    """Multi-device level-wise mining on a stream sharded over ``cfg.mesh``.

    The stream is sharded ONCE (halo exchange + per-shard type index in a
    single shard_map pass, :func:`distributed.build_sharded_index`); every
    level then runs its whole candidate batch through the configured
    tracking engine inside shard_map with a cross-shard greedy merge and
    device-side pruning — still exactly one host sync per level, fetching
    (counts, keep mask, halo flags, overflow) in a single ``device_get``.

    Results are identical to :func:`mine_arrays` on the unsharded stream
    (differentially tested); inadequate halo or capacity is raised, never a
    silent undercount.
    """
    if cfg.mesh is None:
        raise ValueError("mine_sharded requires cfg.mesh")
    n_shards = (cfg.mesh.shape[cfg.shard_axis] if cfg.n_shards is None
                else cfg.n_shards)
    with obs.span("mine", events=stream.n_events, shards=n_shards):
        with obs.span("mine.index"):
            ty, tm = distributed.shard_stream(stream.types, stream.times,
                                              n_shards)
            index = distributed.build_sharded_index(
                jnp.asarray(ty), jnp.asarray(tm), cfg.mesh,
                axis=cfg.shard_axis, n_types=stream.n_types, halo=cfg.halo)

        with obs.span("mine.sync", level=1):
            binc = np.asarray(index.global_type_counts)     # level-1 host sync
        freq_types = np.nonzero(binc >= cfg.threshold)[0].astype(np.int32)
        halo_msg = ("halo too short for the candidate episodes' max_span; "
                    f"raise MinerConfig.halo (got {index.halo} events of "
                    "lookahead per shard)")

        def count_level(sym, lo, hi):
            counts_dev, _, short_dev, overflow_dev = (
                distributed.count_sharded_batch_indexed(
                    index, sym, lo, hi,
                    engine=cfg.engine, cap_occ=cfg.cap_occ,
                    max_window=cfg.max_window,
                    parallel_schedule=cfg.parallel_schedule,
                    block_next=cfg.block_next, block_prev=cfg.block_prev,
                    window_tiles=cfg.window_tiles, interpret=cfg.interpret))
            return counts_dev, [(_OVERFLOW_MSG, overflow_dev),
                                (halo_msg, short_dev)]

        return _mine_levels(
            cfg, _prune_level(freq_types, binc, stream.n_types), count_level)


def mine(stream: EventStream, cfg: MinerConfig) -> Dict[int, LevelResult]:
    """Run level-wise mining up to cfg.max_level. Returns per-level results.

    Thin Episode-list wrapper over :func:`mine_arrays` (same search, same
    order, same counts).
    """
    return {
        level: LevelResult(
            episodes_from_rows(la.symbols, cfg.t_low, cfg.t_high) if level > 1
            else [Episode((int(t),)) for t in la.symbols[:, 0]],
            [int(c) for c in la.counts],
            la.n_candidates,
        )
        for level, la in mine_arrays(stream, cfg).items()
    }
