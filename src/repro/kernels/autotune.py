"""Per-bucket tile autotuning for the Pallas tracking / count kernels.

The fused kernels expose three tile knobs (``block_next`` / ``block_prev`` /
``window_tiles``) plus the count pipeline's ``chunk`` (episode rows per grid
step). The best setting depends on the problem *bucket* — episode length L,
capacity N and batch B — not on a global constant: small streams want tiny
tiles (less boundary slack per constraint window, more of the latest-start
row resident per step), large batches amortize per-grid-step overhead with
bigger chunks.

This module is the single source of truth for that resolution:

* :func:`bucket_key` — ``"kind:L{L}:N{pow2}:B{pow2}"`` buckets (capacity and
  batch rounded up to powers of two so nearby shapes share an entry).
* :func:`resolve` — explicit caller overrides > checked-in
  ``tuned_configs.json`` entry > :data:`DEFAULTS`. A table records the
  backend it was tuned on and is only served on that backend: tiles
  wall-clocked in CPU interpret mode never reach the chip. Pure function of
  its arguments and the table file: deterministic, trace-time cheap (dict
  lookup), safe to call from inside ``jit`` with static shapes.
* :func:`candidate_configs` / :func:`model_time` — the tuning search space
  and the cost-model filter. ``model_time`` routes an analytic byte/flop
  estimate through :func:`analysis.roofline.analyze` (plus a per-grid-step
  launch-overhead term the roofline cannot see); ``benchmarks/run.py
  --autotune`` uses it to pre-rank candidates, wall-clocks the survivors,
  and regenerates ``tuned_configs.json`` — wiring the previously write-only
  roofline / hlo_costs models into the hot path.

Every counting/mining entry point resolves ``None`` block knobs through
:func:`resolve`, so ``count_batch_indexed``, ``mine_corpus``,
``mine_sharded`` and ``StreamingMiner`` all inherit tuned tiles without any
signature churn; passing explicit integers keeps the exact legacy behavior.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
from typing import Dict, List, Optional, Tuple

import jax

from ..analysis import roofline
# THE shared rounding rule: plan buckets and tuned-tile buckets round with
# the same function (core/plan.py owns it), so a MiningPlan's capacity
# classes and this module's bucket_key can never diverge — regression-tested
# in tests/test_plan_cache.py against every checked-in tuned_configs entry.
from ..core.plan import pow2_ceil

_CONFIG_PATH = os.path.join(os.path.dirname(__file__), "tuned_configs.json")

# Per-grid-step overhead (s): pallas_call grid sequencing / interpret-mode
# loop step. Dominates tiny cells; the roofline terms dominate large ones.
_STEP_OVERHEAD_S = 15e-6
# Constraint-window span assumed by the analytic model when the true event
# density is unknown at resolve time (fraction of the capacity).
_SPAN_FRACTION = 0.05


@dataclasses.dataclass(frozen=True)
class TileConfig:
    """One bucket's tile/grid shape for a Pallas kernel launch."""
    block_next: int = 128
    block_prev: int = 128
    window_tiles: int = 0
    chunk: int = 32

    def asdict(self) -> Dict[str, int]:
        return dataclasses.asdict(self)


#: Fallbacks when no tuned entry serves the backend: one 128-lane tile per
#: side (the smallest the chip runs) and 32-row greedy chunks — compiled
#: for a v5e at the smoke's widths in tests/test_tpu_compile.py.
DEFAULTS: Dict[str, TileConfig] = {
    "track": TileConfig(block_next=128, block_prev=128, window_tiles=0, chunk=8),
    "count": TileConfig(block_next=128, block_prev=128, window_tiles=0, chunk=32),
}


# back-compat alias: callers/tests that reached for the private name keep
# working; the one definition lives in core/plan.py
_pow2_ceil = pow2_ceil


def bucket_key(kind: str, levels: int, cap: int, batch: int) -> str:
    """Deterministic bucket id for a (kernel kind, L, N, B) problem shape.

    Idempotent under the rounding rule: ``bucket_key(kind, L,
    pow2_ceil(cap), pow2_ceil(batch)) == bucket_key(kind, L, cap, batch)``
    — which is what lets ``plan_for`` round shapes *first* and still
    resolve the same tuned tiles the raw shapes would.
    """
    if kind not in DEFAULTS:
        raise ValueError(
            f"unknown kernel kind {kind!r}; expected one of {sorted(DEFAULTS)}")
    return f"{kind}:L{int(levels)}:N{pow2_ceil(cap)}:B{pow2_ceil(batch)}"


@functools.lru_cache(maxsize=None)
def _load_table(path: str) -> Tuple[Optional[str], Dict[str, Dict[str, int]]]:
    try:
        with open(path) as f:
            raw = json.load(f)
    except (OSError, ValueError):
        return None, {}
    if not isinstance(raw, dict):
        return None, {}
    table = raw.get("configs", raw)
    backend = raw.get("backend") if "configs" in raw else None
    return backend, {k: v for k, v in table.items() if isinstance(v, dict)}


def load_table(path: Optional[str] = None) -> Dict[str, Dict[str, int]]:
    """The checked-in tuned table ({} when missing/invalid — never raises)."""
    return dict(_load_table(path or _CONFIG_PATH)[1])


def table_backend(path: Optional[str] = None) -> Optional[str]:
    """Backend the table was tuned on (None: untagged, served everywhere)."""
    return _load_table(path or _CONFIG_PATH)[0]


def clear_cache() -> None:
    """Drop the memoized table (tests / post-``--autotune`` regeneration)."""
    _load_table.cache_clear()


def resolve(
    kind: str,
    levels: int,
    cap: int,
    batch: int,
    *,
    block_next: Optional[int] = None,
    block_prev: Optional[int] = None,
    window_tiles: Optional[int] = None,
    chunk: Optional[int] = None,
    path: Optional[str] = None,
    backend: Optional[str] = None,
) -> TileConfig:
    """Tile config for a problem bucket on ``backend`` (default: JAX's).

    Precedence per field: explicit (non-None) caller override, then the
    tuned-table entry for :func:`bucket_key` — only when the table was
    tuned on ``backend`` (or is untagged) — then :data:`DEFAULTS[kind]`.
    Deterministic: same arguments + same table file => same answer.
    """
    base = DEFAULTS[kind] if kind in DEFAULTS else None
    key = bucket_key(kind, levels, cap, batch)   # validates kind
    tuned_on, table = _load_table(path or _CONFIG_PATH)
    if backend is None:
        backend = jax.default_backend()
    entry = table.get(key, {}) if tuned_on in (None, backend) else {}

    def pick(override, field):
        if override is not None:
            return int(override)
        return int(entry.get(field, getattr(base, field)))

    return TileConfig(
        block_next=pick(block_next, "block_next"),
        block_prev=pick(block_prev, "block_prev"),
        window_tiles=pick(window_tiles, "window_tiles"),
        chunk=pick(chunk, "chunk"),
    )


# ---------------------------------------------------------------------------
# Tuning search space + roofline-backed cost model
# ---------------------------------------------------------------------------


def candidate_configs(kind: str, cap: int, batch: int) -> List[TileConfig]:
    """Deterministic candidate grid for one bucket (exact-tiling configs
    only; ``window_tiles`` stays 0 — exactness is non-negotiable). On the
    chip only 128-lane multiples compile."""
    if kind not in DEFAULTS:
        raise ValueError(
            f"unknown kernel kind {kind!r}; expected one of {sorted(DEFAULTS)}")
    if jax.default_backend() == "tpu":
        blocks = [128, 256]
    else:
        blocks = [b for b in (8, 16, 32, 64, 128, 256) if b <= cap]
    chunks = [c for c in (8, 16, 32) if c <= max(batch, 8)]
    out = []
    for b in blocks:
        for c in (chunks if kind == "count" else [DEFAULTS[kind].chunk]):
            out.append(TileConfig(block_next=b, block_prev=b,
                                  window_tiles=0, chunk=c))
    return out


def model_cost(
    kind: str, levels: int, cap: int, batch: int, cfg: TileConfig,
) -> Dict[str, float]:
    """Analytic per-launch cost estimate, in the cost-dict dialect
    ``hlo_costs.module_costs`` / ``roofline.analyze`` speak
    (``flops`` + ``"bytes accessed"``), plus the grid step count."""
    bn, bp = cfg.block_next, cfg.block_prev
    next_tiles = max(1, cap // max(bn, 1))
    # prev events each next event's window is assumed to span, plus the two
    # boundary tiles of misalignment slack the scan table always includes
    span = _SPAN_FRACTION * cap + 2 * bp
    tiles = max(1.0, span / max(bp, 1))
    pair_ops = batch * levels * cap * tiles * bp     # (next, prev) compares
    if kind == "count":
        steps = -(-batch // max(cfg.chunk, 1))
        # compaction prefix-scan + searchsorted gather + greedy fold
        epilogue = batch * cap * 8.0
    else:
        steps = batch * levels * next_tiles * tiles
        epilogue = 0.0
    return {
        "flops": 4.0 * pair_ops + epilogue,
        "bytes accessed": 8.0 * pair_ops + 4.0 * epilogue,
        "grid_steps": float(steps),
    }


def model_time(
    kind: str, levels: int, cap: int, batch: int, cfg: TileConfig,
) -> float:
    """Modelled launch latency (s): roofline compute/memory terms + the
    per-grid-step overhead the roofline cannot express."""
    cost = model_cost(kind, levels, cap, batch, cfg)
    r = roofline.analyze(
        arch="v5e", shape=f"{kind}:L{levels}:N{cap}:B{batch}",
        mesh_name="single", chips=1, cost=cost, coll={"total": 0.0},
        model_flops=0.0, device_kind=roofline.V5E)
    return max(r.t_compute, r.t_memory) + cost["grid_steps"] * _STEP_OVERHEAD_S


def rank_candidates(
    kind: str, levels: int, cap: int, batch: int, top_k: int = 4,
) -> List[TileConfig]:
    """Model-ranked candidate shortlist for wall-clock confirmation."""
    cands = candidate_configs(kind, cap, batch)
    scored = sorted(cands, key=lambda c: (
        model_time(kind, levels, cap, batch, c),
        c.block_next, c.block_prev, c.chunk))
    return scored[: max(1, top_k)]
