"""MiningPlan dispatch spine + AOT executable cache (DESIGN.md §11).

Every batched counting entry point in this repo — ``mine_arrays``,
``mine_corpus``, ``StreamingMiner.append``, and the batch/corpus paths in
``core/counting.py`` — used to be an independently-jitted function: each
unseen input shape paid a fresh trace+compile, and ragged traffic (ROADMAP
item 5, the PR 4 corpus bench) spent more time in XLA than in kernels.

This module collapses them onto ONE abstraction:

* :class:`MiningPlan` — a frozen, hashable description of a counting
  launch: the *capacity-class bucket* (episode level, table width, batch
  rows, corpus streams — each rounded up to a power of two by
  :func:`capacity_class`, the same rounding rule ``kernels.autotune``'s
  ``bucket_key`` uses, imported from here so tile tuning and plan
  bucketing can never diverge) plus the resolved engine, tile/chunk
  config, scheduler flavor, and (for the sharded path) mesh.

* an **AOT executable cache** — one ``jax.jit(fn).lower(specs).compile()``
  per (plan, function), held in an LRU with a configurable bound and
  hit/miss/eviction counters. Entry points become thin adapters: resolve
  the plan, pad inputs to the bucket (+inf times / repeated candidate
  rows, both already inert by the padding conventions of DESIGN.md §5),
  call the cached executable, slice the true rows back out. K distinct
  input shapes that fall into k buckets compile exactly k times, ever.

* :func:`warm` — precompile a list of plans so a serving process pays its
  compiles at startup, not on the first live feed (ROADMAP item 1).

Trace accounting: every registered counting function calls
:func:`note_trace` inside its traced body, so one trace == one counter
increment — the O(#buckets) claim is asserted directly in
``tests/test_plan_cache.py`` and measured in ``benchmarks/bench_compile.py``.

Fallbacks never change results: a plan the cache refuses (malformed or
over the configured size bounds) runs through a plain ``jax.jit`` with a
warning; a dispatch reached under an outer trace (e.g. ``count_batch``
jits the index build *and* the counting pass together) inlines the traced
body instead of calling a compiled executable.
"""
from __future__ import annotations

import contextlib
import os
import threading
import warnings
from collections import Counter, OrderedDict
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "MiningPlan", "plan_for", "dispatch", "warm", "register_fn",
    "cache_stats", "reset_cache", "set_cache_size", "cached_plans",
    "cached_executable",
    "cache_disabled", "trace_counts", "plan_trace_counts",
    "reset_trace_counts", "pow2_ceil", "capacity_class", "pad_rows",
    "pad_width", "plans_for_miner",
]


# ---------------------------------------------------------------------------
# The one rounding rule (shared with kernels.autotune.bucket_key)
# ---------------------------------------------------------------------------


def pow2_ceil(x: int) -> int:
    """Smallest power of two >= x (1 for x <= 0) — THE bucketing round.

    ``kernels.autotune`` imports this (as its ``_pow2_ceil``) and
    :func:`plan_for` rounds shapes with it *before* resolving tiles, so a
    plan's bucket and the tuned-tile bucket are the same key by
    construction: the round is idempotent, hence
    ``bucket_key(rounded) == bucket_key(raw)``.
    """
    return 1 << max(0, int(x) - 1).bit_length() if x > 0 else 1


def capacity_class(n: int, floor: int = 1) -> int:
    """Capacity class for a size: pow2_ceil with a lower bound.

    ``floor`` must itself be a power of two (or 1) so every class stays a
    pow2 bucket; callers with a minimum pad (e.g. ``mining.MAX_BATCH_PAD``)
    raise the floor without leaving the shared bucketing scheme.
    """
    return max(int(floor), pow2_ceil(n))


# ---------------------------------------------------------------------------
# MiningPlan
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MiningPlan:
    """Hashable static-shape bucket + resolved launch config.

    Shape fields are *already rounded* to capacity classes by
    :func:`plan_for`; tile fields are the resolved integers (never None).
    Two calls with shapes in the same class produce equal plans and hence
    share one compiled executable.
    """

    fn: str                  # registered counting function name
    level: int               # episode length N (symbols per candidate row)
    n_types: int             # alphabet size (exact — it is already static)
    cap: int                 # per-type table width, class-rounded
    batch: int               # candidate rows, class-rounded
    streams: int = 0         # corpus stream rows, class-rounded (0 = none)
    tail_cap: int = 0        # tail-view width (semantic, NOT rounded: it
                             # bounds tail_short, so widening would change
                             # results vs the unbucketed path)
    engine: str = "dense"
    parallel_schedule: bool = False
    cap_occ: Optional[int] = None
    max_window: int = 32
    block_next: int = 128    # resolved tiles (autotune bucket of this plan)
    block_prev: int = 128
    window_tiles: int = 0
    chunk: int = 32
    interpret: Optional[bool] = None
    kind: str = "track"      # autotune kernel kind ("count" | "track")
    tile_cap: int = 0        # cap the tile bucket was resolved at (== cap,
                             # except the tail path which tiles tail_cap)
    mesh: Any = None         # jax Mesh for the sharded path (cache bypass)

    def autotune_key(self) -> str:
        """The tuned-tile bucket this plan resolves through — plan bucket
        and tile bucket are the same key (regression-tested against every
        entry in ``kernels/tuned_configs.json``)."""
        from ..kernels import autotune  # deferred: avoid import cycles
        return autotune.bucket_key(
            self.kind, self.level - 1, self.tile_cap,
            max(self.streams, 1) * self.batch)


def resolve_tiles(
    engine,
    levels: int,
    cap: int,
    batch: int,
    *,
    block_next: Optional[int] = None,
    block_prev: Optional[int] = None,
    window_tiles: Optional[int] = None,
    chunk: Optional[int] = None,
    kind: Optional[str] = None,
) -> Tuple[int, int, int, int, str]:
    """(block_next, block_prev, window_tiles, chunk, kind) for one launch.

    ``None`` knobs resolve through the autotune bucket table — kind
    ``"count"`` when the engine counts natively, ``"track"`` otherwise;
    explicit integers win field-by-field. Pure trace-time work.
    """
    from . import tracking  # deferred: avoid import cycles at module init
    eng = tracking.get_engine(engine) if isinstance(engine, str) else engine
    if kind is None:
        kind = ("count" if getattr(eng, "count_batch", None) is not None
                else "track")
    from ..kernels import autotune  # deferred: avoid import cycles
    cfg = autotune.resolve(
        kind, levels, cap, batch, block_next=block_next,
        block_prev=block_prev, window_tiles=window_tiles, chunk=chunk)
    return cfg.block_next, cfg.block_prev, cfg.window_tiles, cfg.chunk, kind


def plan_for(
    fn: str,
    *,
    level: int,
    n_types: int,
    cap: int,
    batch: int,
    streams: int = 0,
    tail_cap: int = 0,
    engine: str = "dense",
    parallel_schedule: bool = False,
    cap_occ: Optional[int] = None,
    max_window: int = 32,
    block_next: Optional[int] = None,
    block_prev: Optional[int] = None,
    window_tiles: Optional[int] = None,
    interpret: Optional[bool] = None,
    mesh: Any = None,
    kind: Optional[str] = None,
) -> MiningPlan:
    """Resolve a :class:`MiningPlan`: round shapes to capacity classes,
    then resolve tiles on the *rounded* shapes (idempotent pow2 rounding
    makes the tile bucket identical to the raw-shape bucket)."""
    cap_b = capacity_class(cap)
    batch_b = pow2_ceil(batch)
    streams_b = pow2_ceil(streams) if streams else 0
    tile_cap = (int(tail_cap)
                if fn in ("count_tail", "count_corpus_tail",
                          "count_corpus_tail_grouped")
                else cap_b)
    bn, bp, wt, ch, kind = resolve_tiles(
        engine, level - 1, tile_cap, max(streams_b, 1) * batch_b,
        block_next=block_next, block_prev=block_prev,
        window_tiles=window_tiles, kind=kind)
    return MiningPlan(
        fn=fn, level=int(level), n_types=int(n_types), cap=cap_b,
        batch=batch_b, streams=streams_b, tail_cap=int(tail_cap),
        engine=engine, parallel_schedule=bool(parallel_schedule),
        cap_occ=cap_occ, max_window=int(max_window), block_next=bn,
        block_prev=bp, window_tiles=wt, chunk=ch, interpret=interpret,
        kind=kind, tile_cap=tile_cap, mesh=mesh)


# ---------------------------------------------------------------------------
# Padding helpers (adapters pad inputs up to the plan bucket)
# ---------------------------------------------------------------------------


def pad_rows(arr: jax.Array, target: int) -> jax.Array:
    """Pad the leading axis to ``target`` rows by repeating row 0 (the
    existing candidate-pad convention: counted, then discarded)."""
    b = arr.shape[0]
    if b == target:
        return arr
    reps = jnp.broadcast_to(arr[:1], (target - b,) + tuple(arr.shape[1:]))
    return jnp.concatenate([jnp.asarray(arr), reps], axis=0)


def pad_width(arr: jax.Array, target: int, fill) -> jax.Array:
    """Pad the LAST axis to ``target`` with ``fill`` (+inf for time tables
    — inert under every downstream max/searchsorted, DESIGN.md §5)."""
    w = arr.shape[-1]
    if w == target:
        return arr
    pad = jnp.full(tuple(arr.shape[:-1]) + (target - w,), fill,
                   jnp.asarray(arr).dtype)
    return jnp.concatenate([jnp.asarray(arr), pad], axis=-1)


# ---------------------------------------------------------------------------
# Function registry (counting.py registers its builders at import)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class _FnEntry:
    build: Callable[[MiningPlan], Callable]       # plan -> traced callable
    specs: Callable[[MiningPlan], Tuple]          # plan -> ShapeDtypeStructs


_FNS: Dict[str, _FnEntry] = {}


def register_fn(name: str, build, specs) -> None:
    """Register a counting function: ``build(plan)`` returns the traced
    callable (static config closed over from the plan), ``specs(plan)``
    its input ShapeDtypeStructs — everything :func:`warm` needs to compile
    without real inputs."""
    _FNS[name] = _FnEntry(build=build, specs=specs)


def _fn_entry(name: str) -> _FnEntry:
    if name not in _FNS:
        from . import counting  # noqa: F401 — importing registers builders
    if name not in _FNS:
        raise KeyError(f"no counting function registered as {name!r}")
    return _FNS[name]


# ---------------------------------------------------------------------------
# Trace accounting (the O(#buckets) gate)
# ---------------------------------------------------------------------------

_TRACES: Counter = Counter()        # fn name -> traced-body executions
_PLAN_TRACES: Counter = Counter()   # plan -> traced-body executions


def note_trace(plan: MiningPlan) -> None:
    """Called inside every registered fn's traced body: one trace (or
    inline re-trace under an outer jit) == one increment."""
    _TRACES[plan.fn] += 1
    _PLAN_TRACES[plan] += 1


def trace_counts() -> Dict[str, int]:
    return dict(_TRACES)


def plan_trace_counts() -> Dict[MiningPlan, int]:
    return dict(_PLAN_TRACES)


def reset_trace_counts() -> None:
    _TRACES.clear()
    _PLAN_TRACES.clear()


# ---------------------------------------------------------------------------
# AOT executable cache
# ---------------------------------------------------------------------------

#: Size bounds above which a plan is not cached (it still *runs*, through
#: a plain jit with a warning). Monkeypatchable in tests.
MAX_CACHE_LEVEL = 64
MAX_CACHE_BATCH = 1 << 16
MAX_CACHE_CAP = 1 << 22
MAX_CACHE_STREAMS = 1 << 12

_DEFAULT_CACHE_SIZE = 512


class _ExecutableCache:
    """LRU of AOT-compiled executables keyed by MiningPlan."""

    def __init__(self, maxsize: int = _DEFAULT_CACHE_SIZE):
        self.maxsize = int(maxsize)
        self._data: "OrderedDict[MiningPlan, Any]" = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.fallbacks = 0
        self.bypasses = 0

    def lookup(self, plan: MiningPlan):
        with self._lock:
            exe = self._data.get(plan)
            if exe is not None:
                self._data.move_to_end(plan)
                self.hits += 1
            else:
                self.misses += 1
            return exe

    def peek(self, plan: MiningPlan) -> bool:
        with self._lock:
            return plan in self._data

    def insert(self, plan: MiningPlan, exe) -> None:
        with self._lock:
            self._data[plan] = exe
            self._data.move_to_end(plan)
            while len(self._data) > self.maxsize:
                self._data.popitem(last=False)
                self.evictions += 1

    def clear(self) -> None:
        with self._lock:
            self._data.clear()
            self.hits = self.misses = self.evictions = 0
            self.fallbacks = self.bypasses = 0

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {
                "size": len(self._data), "maxsize": self.maxsize,
                "hits": self.hits, "misses": self.misses,
                "evictions": self.evictions, "fallbacks": self.fallbacks,
                "bypasses": self.bypasses,
            }

    def plans(self) -> List[MiningPlan]:
        with self._lock:
            return list(self._data)


_CACHE = _ExecutableCache()

# kill switch: REPRO_PLAN_CACHE=0 routes every dispatch through plain jit
_DISABLED = os.environ.get("REPRO_PLAN_CACHE", "1") == "0"


@contextlib.contextmanager
def cache_disabled():
    """Route dispatches through fresh ``jax.jit`` calls (the uncached
    reference path — bit-for-bit parity with the cache is tested against
    exactly this)."""
    global _DISABLED
    prev = _DISABLED
    _DISABLED = True
    try:
        yield
    finally:
        _DISABLED = prev


def cache_stats() -> Dict[str, int]:
    """Executable-cache counters: size/maxsize, hits, misses, evictions,
    fallbacks (uncacheable plans run via plain jit) and bypasses (mesh
    plans dispatched through jax's own jit cache)."""
    return _CACHE.stats()


def cached_plans() -> List[MiningPlan]:
    """The plans currently holding a compiled executable (LRU order)."""
    return _CACHE.plans()


def cached_executable(plan: MiningPlan):
    """The compiled executable cached for ``plan`` (KeyError if none) —
    e.g. to inspect its HLO (``.as_text()``) without recompiling."""
    with _CACHE._lock:
        return _CACHE._data[plan]


def set_cache_size(maxsize: int) -> None:
    """Shrink/grow the LRU bound; shrinking evicts oldest entries now."""
    with _CACHE._lock:
        _CACHE.maxsize = max(1, int(maxsize))
        while len(_CACHE._data) > _CACHE.maxsize:
            _CACHE._data.popitem(last=False)
            _CACHE.evictions += 1


def reset_cache(maxsize: Optional[int] = None) -> None:
    """Drop every cached executable and zero the counters (tests)."""
    _CACHE.clear()
    _CACHE.maxsize = (_DEFAULT_CACHE_SIZE if maxsize is None
                      else max(1, int(maxsize)))


def uncacheable_reason(plan: MiningPlan) -> Optional[str]:
    """Why a plan cannot hold a cached executable (None = cacheable)."""
    if plan.mesh is not None:
        return "mesh plans dispatch through jax's jit cache (shard_map)"
    if plan.level < 2 or plan.n_types < 1 or plan.cap < 1 or plan.batch < 1:
        return (f"malformed plan shape (level={plan.level}, "
                f"n_types={plan.n_types}, cap={plan.cap}, "
                f"batch={plan.batch})")
    if (plan.fn in ("count_tail", "count_corpus_tail",
                    "count_corpus_tail_grouped") and plan.tail_cap < 1):
        return f"malformed tail view (tail_cap={plan.tail_cap})"
    if plan.level > MAX_CACHE_LEVEL:
        return f"level {plan.level} > MAX_CACHE_LEVEL={MAX_CACHE_LEVEL}"
    if plan.batch > MAX_CACHE_BATCH:
        return f"batch {plan.batch} > MAX_CACHE_BATCH={MAX_CACHE_BATCH}"
    if plan.cap > MAX_CACHE_CAP:
        return f"cap {plan.cap} > MAX_CACHE_CAP={MAX_CACHE_CAP}"
    if plan.streams > MAX_CACHE_STREAMS:
        return (f"streams {plan.streams} > "
                f"MAX_CACHE_STREAMS={MAX_CACHE_STREAMS}")
    return None


def note_bypass(plan: MiningPlan) -> None:
    """Record a dispatch that legitimately sidesteps the cache (the mesh
    path compiles through jax's jit cache, keyed by the same static args a
    plan carries)."""
    _CACHE.bypasses += 1


def _compile(plan: MiningPlan, entry: _FnEntry):
    return jax.jit(entry.build(plan)).lower(*entry.specs(plan)).compile()


def dispatch(plan: MiningPlan, *args):
    """Run a registered counting function through the executable cache.

    Adapters call this with inputs already padded to the plan bucket.
    Under an outer trace the body is inlined (compiled executables reject
    tracers); uncacheable plans fall back to plain jit with a warning —
    results are identical on every path.
    """
    entry = _fn_entry(plan.fn)
    if any(isinstance(leaf, jax.core.Tracer)
           for leaf in jax.tree_util.tree_leaves(args)):
        return entry.build(plan)(*args)
    if _DISABLED:
        return jax.jit(entry.build(plan))(*args)
    reason = uncacheable_reason(plan)
    if reason is not None:
        warnings.warn(
            f"MiningPlan not cacheable ({reason}); dispatching uncached",
            stacklevel=2)
        _CACHE.fallbacks += 1
        return jax.jit(entry.build(plan))(*args)
    exe = _CACHE.lookup(plan)
    if exe is None:
        exe = _compile(plan, entry)
        _CACHE.insert(plan, exe)
    # inputs that do not match the plan's specs are an adapter bug: the
    # executable's TypeError/ValueError propagates (never a silent recompile)
    return exe(*args)


def warm(plans: Iterable[MiningPlan]) -> Dict[str, int]:
    """Precompile executables for ``plans`` (serving-startup protocol).

    Idempotent: already-cached plans are skipped without touching the
    hit/miss counters; uncacheable plans are skipped with a warning.
    Returns ``{"compiled": n, "cached": n, "skipped": n}``.
    """
    out = {"compiled": 0, "cached": 0, "skipped": 0}
    for plan in plans:
        reason = uncacheable_reason(plan)
        if reason is not None:
            warnings.warn(f"warm: skipping plan ({reason})", stacklevel=2)
            out["skipped"] += 1
            continue
        if _CACHE.peek(plan):
            out["cached"] += 1
            continue
        _CACHE.insert(plan, _compile(plan, _fn_entry(plan.fn)))
        out["compiled"] += 1
    return out


# ---------------------------------------------------------------------------
# Serving helpers
# ---------------------------------------------------------------------------


def plans_for_miner(
    cfg,
    *,
    n_types: int,
    n_events: int,
    batches: Optional[Iterable[int]] = None,
    streaming: bool = False,
    tail_caps: Iterable[int] = (),
) -> List[MiningPlan]:
    """Plans a level-wise miner with this config will dispatch, for
    :func:`warm`. ``cfg`` is a ``MinerConfig`` (duck-typed).

    ``batches`` defaults to every capacity class a candidate batch can
    occupy at level 2 (16 .. class(min(max_candidates, n_types^2)));
    later levels reuse the same classes or go quiet. With ``streaming``,
    the cold-backfill (stateful) plans are included, plus a tail-recount
    plan per entry of ``tail_caps`` (the caller's expected suffix widths —
    a feed's event rate bounds them).
    """
    cap = max(1, n_events) if getattr(cfg, "cap", None) is None else cfg.cap
    if batches is None:
        top = capacity_class(min(cfg.max_candidates, n_types * n_types))
        b = 16
        batches = []
        while b <= top:
            batches.append(b)
            b *= 2
        batches = batches or [top]
    batches = sorted({pow2_ceil(int(b)) for b in batches})
    knobs = dict(
        n_types=n_types, cap=cap, engine=cfg.engine,
        parallel_schedule=cfg.parallel_schedule, cap_occ=cfg.cap_occ,
        max_window=cfg.max_window, block_next=cfg.block_next,
        block_prev=cfg.block_prev, window_tiles=cfg.window_tiles,
        interpret=cfg.interpret)
    plans: List[MiningPlan] = []
    for level in range(2, cfg.max_level + 1):
        for b in batches:
            plans.append(plan_for("count_indexed", level=level, batch=b,
                                  **knobs))
            if streaming:
                plans.append(plan_for("count_stateful", level=level,
                                      batch=b, **knobs))
                for tc in tail_caps:
                    plans.append(plan_for("count_tail", level=level,
                                          batch=b, tail_cap=int(tc),
                                          **knobs))
    return plans
