"""Layer 1 — jaxpr/trace checks over the registered plan matrix.

For every registered counting fn × registered engine (× a shape matrix),
the plan builder is traced with :func:`jax.make_jaxpr` and the resulting
jaxpr is walked recursively (scan/while/cond/pjit/pallas sub-jaxprs
included) to enforce four contracts the repo otherwise only learns about
dynamically:

* **REPRO101** — no host-transfer/callback primitive anywhere in the
  traced body. The miner's level loop performs exactly ONE host sync per
  level (PR 1/6); a callback inside a traced counting body would add a
  hidden one per launch.
* **REPRO102** — every plan shape field and every input-spec dimension is
  a fixed point of :func:`plan.capacity_class`/:func:`plan.pow2_ceil`
  (or a plan-derived semantic size). This is the O(#buckets) compile
  contract (PR 7): a non-class-rounded shape entering ``dispatch()``
  mints unbounded cache keys.
* **REPRO103** — ``tracking.restrict_seed_row`` runs exactly once for
  plans that carry a ``t_min`` (``count_tail``) and never otherwise: the
  PR 6 double-apply hazard, counted by instrumenting the function during
  tracing.
* **REPRO104** — Pallas tile contracts hold statically for the plan's
  resolved tiles and for every ``tuned_configs.json`` entry: the lcm-
  padded capacity is covered exactly by the tiles and the row layout, and
  — for launches compiled for the chip — tiles are 128-lane multiples and
  the kernel's own per-grid-step VMEM sizing
  (``kernels.ops.kernel_vmem_bytes``) stays under its budget.
"""
from __future__ import annotations

import re
from contextlib import contextmanager
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.extend.core as jax_core

from .findings import Finding

__all__ = [
    "check_plan", "check_plans", "check_tuned_table", "check_hlo",
    "default_matrix", "full_matrix", "FORBIDDEN_PRIMITIVES",
    "estimate_vmem_bytes", "plan_path",
]

#: Primitive names that imply a host round-trip inside a traced body.
FORBIDDEN_PRIMITIVES = frozenset({
    "outside_call", "host_callback", "infeed", "outfeed", "device_put",
    "debug_print",
})

#: Fns whose EngineConfig legitimately carries a t_min (applied exactly
#: once by `consume_seed_restriction` at the dispatch altitude). All other
#: fns must never touch the seed row.
EXPECTED_TMIN_APPLICATIONS = {"count_tail": 1}



def plan_path(plan) -> str:
    """Stable pseudo-path for plan-level findings (baseline-matchable)."""
    return (f"plan://{plan.fn}/{plan.engine}/L{plan.level}"
            f"N{plan.cap}B{plan.batch}S{plan.streams}T{plan.tail_cap}")


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------


def _subjaxprs(params: dict):
    for v in params.values():
        stack = [v]
        while stack:
            item = stack.pop()
            if isinstance(item, (tuple, list)):
                stack.extend(item)
            elif isinstance(item, jax_core.ClosedJaxpr):
                yield item.jaxpr
            elif isinstance(item, jax_core.Jaxpr):
                yield item


def iter_primitives(jaxpr) -> Iterable[Tuple[str, dict]]:
    """(primitive_name, params) for every eqn, recursing into sub-jaxprs
    (scan/while/cond bodies, nested pjit, pallas kernel jaxprs)."""
    seen = set()
    stack = [jaxpr]
    while stack:
        jx = stack.pop()
        if id(jx) in seen:
            continue
        seen.add(id(jx))
        for eqn in jx.eqns:
            yield eqn.primitive.name, eqn.params
            stack.extend(_subjaxprs(eqn.params))


def _is_forbidden(prim_name: str) -> bool:
    return prim_name in FORBIDDEN_PRIMITIVES or "callback" in prim_name


# ---------------------------------------------------------------------------
# tracing with t_min instrumentation
# ---------------------------------------------------------------------------


@contextmanager
def _count_seed_restrictions():
    """Count tracking.restrict_seed_row calls made while tracing."""
    from ...core import tracking
    counter = {"n": 0}
    original = tracking.restrict_seed_row

    def counting(times_by_sym, t_min):
        counter["n"] += 1
        return original(times_by_sym, t_min)

    tracking.restrict_seed_row = counting
    try:
        yield counter
    finally:
        tracking.restrict_seed_row = original


def trace_plan(plan) -> Tuple[object, int]:
    """(closed_jaxpr, n_seed_restrictions) for one plan's traced body."""
    from ...core import plan as plan_mod
    entry = plan_mod._fn_entry(plan.fn)
    fn = entry.build(plan)
    specs = entry.specs(plan)
    with _count_seed_restrictions() as counter:
        closed = jax.make_jaxpr(fn)(*specs)
    return closed, counter["n"]


# ---------------------------------------------------------------------------
# individual checks
# ---------------------------------------------------------------------------


def check_callbacks(plan, closed) -> List[Finding]:
    path = plan_path(plan)
    out = []
    for name, _params in iter_primitives(closed.jaxpr):
        if _is_forbidden(name):
            out.append(Finding(
                path, 0, "REPRO101",
                f"forbidden primitive `{name}` in traced body of "
                f"{plan.fn!r} (engine {plan.engine!r}) — hidden host sync"))
    return out


def check_rounding(plan, specs) -> List[Finding]:
    from ...core.plan import capacity_class, pow2_ceil
    path = plan_path(plan)
    out = []
    if plan.cap != capacity_class(plan.cap):
        out.append(Finding(path, 0, "REPRO102",
                           f"plan.cap={plan.cap} is not a capacity class "
                           f"(expected {capacity_class(plan.cap)})"))
    if plan.batch != pow2_ceil(plan.batch):
        out.append(Finding(path, 0, "REPRO102",
                           f"plan.batch={plan.batch} is not pow2-rounded "
                           f"(expected {pow2_ceil(plan.batch)})"))
    if plan.streams and plan.streams != pow2_ceil(plan.streams):
        out.append(Finding(path, 0, "REPRO102",
                           f"plan.streams={plan.streams} is not "
                           "pow2-rounded"))
    # every spec dim must be plan-derived: the bucket axes (already checked
    # above) or a semantic size the bucket carries. Anything else is a
    # shape that will mint fresh cache keys per call site.
    allowed = {plan.cap, plan.batch, plan.streams, plan.level,
               plan.level - 1, plan.n_types, plan.tail_cap, 1}
    for i, spec in enumerate(specs):
        for d in spec.shape:
            if d not in allowed:
                out.append(Finding(
                    path, 0, "REPRO102",
                    f"spec[{i}] dim {d} (shape {tuple(spec.shape)}) is "
                    "not derived from the plan bucket"))
    return out


def check_tmin(plan, n_restrictions: int) -> List[Finding]:
    expected = EXPECTED_TMIN_APPLICATIONS.get(plan.fn, 0)
    if n_restrictions == expected:
        return []
    return [Finding(
        plan_path(plan), 0, "REPRO103",
        f"restrict_seed_row ran {n_restrictions}x while tracing "
        f"{plan.fn!r} (expected {expected}x) — t_min must be consumed "
        "exactly once per dispatch path")]


# -- Pallas tile/grid/VMEM contracts ----------------------------------------


def estimate_vmem_bytes(kind: str, levels: int, cap: int, bn: int,
                        bp: int, chunk: int, batch: int = 1) -> int:
    """Per-grid-step chip VMEM of one kernel launch — the kernel's own
    sizing rule (``kernels.ops.kernel_vmem_bytes``), not a second model."""
    from ...kernels import ops
    return ops.kernel_vmem_bytes(kind, levels, cap, bn, bp, chunk, batch)


def _tile_contract(path: str, kind: str, levels: int, cap: int,
                   batch_rows: int, bn: int, bp: int, wt: int,
                   chunk: int, interpret: bool = False) -> List[Finding]:
    """Tile/grid/VMEM contract of one launch. Compiled for the chip, tiles
    must be 128-lane multiples and the launch must fit the VMEM budget;
    interpret mode checks the tiling arithmetic only."""
    from ...kernels import ops
    out: List[Finding] = []
    try:
        ebn, ebp, pcap = ops.tile_geometry(cap, bn, bp, interpret=interpret)
    except ValueError as err:
        return [Finding(path, 0, "REPRO104", str(err))]
    if pcap < cap:
        out.append(Finding(path, 0, "REPRO104",
                           f"padded cap {pcap} < cap {cap}"))
    if pcap % ebn or pcap % ebp or pcap % ops.lanes(ebn, ebp):
        out.append(Finding(
            path, 0, "REPRO104",
            f"tiles ({ebn},{ebp}) do not divide padded cap {pcap} — "
            "pallas_call would raise at launch"))
        return out
    if wt < 0:
        out.append(Finding(path, 0, "REPRO104",
                           f"negative window_tiles {wt}"))
    if interpret:
        return out
    vmem = estimate_vmem_bytes(kind, max(1, levels), cap, ebn, ebp, chunk,
                               batch_rows)
    if vmem > ops.VMEM_BUDGET_BYTES:
        out.append(Finding(
            path, 0, "REPRO104",
            f"estimated VMEM {vmem / 2**20:.2f} MiB per grid step exceeds "
            f"the {ops.VMEM_BUDGET_BYTES // 2**20} MiB budget "
            f"(kind={kind}, cap={cap}, bn={ebn}, bp={ebp}, "
            f"chunk={chunk})"))
    return out


def check_plan_tiles(plan) -> List[Finding]:
    if plan.tile_cap < 1:
        return []  # malformed plans are uncacheable_reason'd, not tiled
    from ...kernels import ops
    return _tile_contract(
        plan_path(plan), plan.kind, plan.level - 1, plan.tile_cap,
        max(plan.streams, 1) * plan.batch, plan.block_next,
        plan.block_prev, plan.window_tiles, plan.chunk,
        interpret=ops.resolve_interpret(plan.interpret))


_KEY_RE = re.compile(r"^(count|track):L(\d+):N(\d+):B(\d+)$")


def check_tuned_table(path: Optional[str] = None) -> List[Finding]:
    """Static contract check of every tuned_configs.json entry, on the
    backend the table serves (chip rules for a ``tpu`` table)."""
    from ...kernels import autotune
    table = autotune.load_table(path)
    backend = autotune.table_backend(path) or "tpu"
    out: List[Finding] = []
    src = "src/repro/kernels/tuned_configs.json"
    for key, cfg in sorted(table.items()):
        m = _KEY_RE.match(key)
        if not m:
            out.append(Finding(src, 0, "REPRO104",
                               f"malformed bucket key {key!r}"))
            continue
        kind, levels, cap, batch = (m.group(1), int(m.group(2)),
                                    int(m.group(3)), int(m.group(4)))
        resolved = autotune.resolve(kind, levels, cap, batch, path=path,
                                    backend=backend)
        out.extend(_tile_contract(
            f"{src}#{key}", kind, levels, cap, batch,
            resolved.block_next, resolved.block_prev,
            resolved.window_tiles, resolved.chunk,
            interpret=backend != "tpu"))
    return out


# ---------------------------------------------------------------------------
# HLO-level spot check (compiled module, reuses analysis.hlo_costs)
# ---------------------------------------------------------------------------


def check_hlo(plan) -> Tuple[List[Finding], Dict[str, float]]:
    """Compile one plan and walk the optimized HLO: no host custom-calls,
    plus the hlo_costs byte/flop accounting for the report."""
    from ...core import plan as plan_mod
    from .. import hlo_costs
    entry = plan_mod._fn_entry(plan.fn)
    # staticcheck: disable=REPRO003 -- the checker compiles one plan
    # off-cache on purpose to inspect its optimized HLO
    compiled = jax.jit(entry.build(plan)).lower(*entry.specs(plan)).compile()
    text = compiled.as_text()
    out: List[Finding] = []
    path = plan_path(plan)
    for i, line in enumerate(text.splitlines(), start=1):
        if "custom-call" in line and ("callback" in line
                                      or "host" in line.lower()):
            out.append(Finding(path, i, "REPRO101",
                               "host/callback custom-call in compiled HLO"))
    return out, hlo_costs.module_costs(text)


# ---------------------------------------------------------------------------
# plan matrices + the combined per-plan entry point
# ---------------------------------------------------------------------------

_CORPUS_FNS = ("count_corpus", "count_corpus_tail",
               "count_corpus_tail_grouped")
_TAIL_FNS = ("count_tail", "count_corpus_tail", "count_corpus_tail_grouped")


def _plan(fn: str, engine: str, *, level: int = 3, cap: int = 256,
          batch: int = 8, streams: int = 4, tail_cap: int = 64):
    from ...core import plan as plan_mod
    return plan_mod.plan_for(
        fn, level=level, n_types=8, cap=cap, batch=batch,
        streams=streams if fn in _CORPUS_FNS else 0,
        tail_cap=tail_cap if fn in _TAIL_FNS else 0,
        engine=engine, interpret=True)


def _registered_fns() -> Sequence[str]:
    from ...core import plan as plan_mod
    plan_mod._fn_entry("count_indexed")  # import counting -> register all
    return tuple(sorted(plan_mod._FNS))


def default_matrix() -> List:
    """Every fn × every engine at one representative bucket (CI tier)."""
    from ...core import tracking
    return [_plan(fn, eng)
            for fn in _registered_fns() for eng in tracking.engine_names()]


def full_matrix() -> List:
    """default_matrix + shape sweep on the two dense engines (nightly)."""
    from ...core import tracking
    plans = default_matrix()
    sweep_engines = [e for e in ("dense", "dense_pallas_fused")
                     if e in tracking.engine_names()]
    for fn in _registered_fns():
        for eng in sweep_engines:
            for level in (2, 4):
                for cap in (256, 1024):
                    for batch in (8, 32):
                        plans.append(_plan(fn, eng, level=level, cap=cap,
                                           batch=batch))
    return plans


def check_plan(plan) -> List[Finding]:
    """All layer-1 checks for one plan."""
    from ...core import plan as plan_mod
    entry = plan_mod._fn_entry(plan.fn)
    out = check_rounding(plan, entry.specs(plan))
    out.extend(check_plan_tiles(plan))
    try:
        closed, n_restrict = trace_plan(plan)
    except Exception as err:
        out.append(Finding(plan_path(plan), 0, "REPRO101",
                           f"plan builder failed to trace: {err}"))
        return out
    out.extend(check_callbacks(plan, closed))
    out.extend(check_tmin(plan, n_restrict))
    return out


def check_plans(plans: Iterable) -> List[Finding]:
    out: List[Finding] = []
    for p in plans:
        out.extend(check_plan(p))
    return out
