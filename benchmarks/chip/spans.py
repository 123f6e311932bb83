"""What the readers of the program's own spans and scopes share.

The miner marks its host phases with profiler spans (``repro.mine.index``,
``.join``, ``.count``, ``.sync``; ``src/repro/core/obs.py``). They lie on
the thread that drives the program, so ``trace.read`` keeps them in
``Trace.host``. Its count path's device work carries the named scopes
``table_gather`` and ``scan_table`` in the HLO metadata. A trace names a
device operation by its HLO instruction alone, so an operation's scope is
looked up in the compiled programs of the program's plan cache
(``plan.cached_executable(plan).as_text()``): a name counts for a scope
when every cached program that has an instruction of that name puts it
under the scope. The trace's device operations keep the program they ran
in (its ``XLA Modules`` line), so an operation counts for a scope only
where it ran in a program whose HloModule name is a cached plan's: an
eager operation that shares an instruction name with a plan's
(``fusion``) is not the plan's. The program id a TPU trace appends to
the name is dropped: the compiled executable exposes no id that equals
it. A trace without that line counts by name alone, as the run's log
says.

Span stats (the keyword arguments of a span, ``rows``/``padded`` of
``repro.mine.count``) are summed by ``span_stat_sum``; a later cell reads
its counters the same way.

A program without ``repro.core.obs`` (one older than its spans) reads as
nothing. A program that has it, and a trace that holds units of work but
not the span or scope, fails the run: a renamed span cannot drop its
metric silently.
"""
from __future__ import annotations

import importlib.util
import re
from typing import Dict, Iterable, List, Optional, Set

from benchmarks.chip import harness, trace as trace_lib

_INSTRUCTION = re.compile(r'^\s*(?:ROOT )?%([\w.\-]+) = ')
_OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')
_MODULE = re.compile(r'^HloModule ([^\s,]+)', re.MULTILINE)


def program_has_spans() -> bool:
    """Whether the program under test emits the spans and scopes."""
    return importlib.util.find_spec("repro.core.obs") is not None


def span_events(r, name: str) -> List[trace_lib.Event]:
    """The host spans named ``name``. Raises where units were traced and
    the span is not there."""
    events = [e for e in r.trace.host if e.name == name]
    if r.units and not events:
        raise LookupError(f"{r.units} units traced, but no host span is "
                          f"named {name!r}")
    return events


def span_stat_sum(r, name: str, stat: str) -> float:
    """The sum of the stat ``stat`` over the host spans ``name`` that start
    inside the traced window. Raises where units were traced and no such
    span carries the stat."""
    lo, hi = r.trace.window
    values = [dict(e.stats)[stat] for e in span_events(r, name)
              if lo <= e.start_ns < hi and stat in dict(e.stats)]
    if r.units and not values:
        raise LookupError(f"{r.units} units traced, but no host span "
                          f"{name!r} carries the stat {stat!r}")
    return sum(values)


def span_ms(r, name: str) -> Optional[float]:
    """Milliseconds inside the span ``name`` per unit of work."""
    if r.units == 0 or not program_has_spans():
        return None
    spans = [(e.start_ns, e.end_ns) for e in span_events(r, name)]
    return 1e-6 * trace_lib.total_ns(trace_lib.clip(spans, r.trace.window)) \
        / r.units


def program_hlo() -> List[str]:
    """The optimized HLO text of every program in the plan cache."""
    from repro.core import plan
    return [plan.cached_executable(p).as_text()
            for p in plan.cached_plans()]


def scoped_names(texts: Iterable[str], scope: str) -> Set[str]:
    """Instruction names that every program of ``texts`` having them puts
    under the named scope ``scope`` (a component of the op_name path)."""
    verdicts: Dict[str, Set[bool]] = {}
    for text in texts:
        for line in text.splitlines():
            m = _INSTRUCTION.match(line)
            if m:
                op = _OP_NAME.search(line)
                inside = bool(op) and scope in op.group(1).split("/")
                verdicts.setdefault(m.group(1), set()).add(inside)
    return {name for name, seen in verdicts.items() if seen == {True}}


def module_names(texts: Iterable[str]) -> Set[str]:
    """The HloModule names of the programs of ``texts``."""
    return {m.group(1) for text in texts for m in [_MODULE.search(text)] if m}


def scope_ms(r, scope: str) -> Optional[float]:
    """Device milliseconds per unit of work under the named scope
    ``scope``: the union of its operations' intervals in the plan cache's
    programs, less any time the count kernel runs, so two scopes together
    never exceed ``other_device_ms``. Raises where units were traced and
    no operation of the scope ran."""
    if r.units == 0 or not r.trace.device_ops or not program_has_spans():
        return None
    texts = program_hlo()
    names = scoped_names(texts, scope)
    kernel = trace_lib.kernel_matcher(harness.KERNEL)
    trace = r.trace
    if trace.modules:
        plans = module_names(texts)
        trace = trace.only(lambda e: kernel(e.name) or
                           trace_lib.module_name(e.module) in plans)
    seconds = (trace.busy_s(lambda n: n in names or kernel(n))
               - r.kernel_s())
    if seconds <= 0:
        raise LookupError(f"{r.units} units traced, but no device "
                          f"operation lies in the scope {scope!r}")
    return 1e3 * seconds / r.units
