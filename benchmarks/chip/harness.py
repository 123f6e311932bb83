"""What a run of the benchmark carries: the compile counter, the tracer,
the context handed to a driver, what its window returns, and what a
per-layer metric reader gets."""
from __future__ import annotations

import dataclasses
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

from benchmarks.chip import roofline, trace as trace_lib

TRACE_AFTER_S = 2.0                  # the trace starts this far into the window
TRACE_LEN_S = 3.0                    # ... and stops at the first unit after this
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
KERNEL = "count_batch"               # the Pallas call the kernel metrics read


def span(name: str):
    """A host span in the profiler's trace (a no-op when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(trace_lib.SPAN_PREFIX + name)


class Compiles:
    """Backend compiles, counted from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, _secs, **_kw):
        if name == COMPILE_EVENT:
            self.count += 1


class Tracer:
    """Starts the profiler ``TRACE_AFTER_S`` into the window and stops it at
    the first unit boundary ``TRACE_LEN_S`` later. Drivers call ``tick``
    between units of work (a recording, a flush), so a unit lies wholly
    inside the trace or wholly outside it; ``units`` lists the traced ones."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.log_dir: Optional[str] = None
        self.units: List[Any] = []
        self._t0 = self._started = None
        self._span = None
        self.done = False

    @property
    def active(self) -> bool:
        return self._started is not None and not self.done

    def begin(self, t0: float) -> None:
        self._t0 = t0

    def tick(self, now: float, unit: Any = None) -> None:
        if not self.enabled or self.done or self._t0 is None:
            return
        if self.active:
            if unit is not None:
                self.units.append(unit)
            if now - self._started >= TRACE_LEN_S:
                self.end()
        elif now - self._t0 >= TRACE_AFTER_S:
            import jax
            self.log_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            self._span = span("window")
            self._span.__enter__()
            self._started = time.perf_counter()

    def end(self) -> None:
        """Stop the trace (at the end of the window at the latest)."""
        if self.active:
            import jax
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        self.done = True


@dataclasses.dataclass
class Context:
    """What a driver gets: the cell's files, the run's arguments, the
    program (``core``) and the run's counters."""

    cell: Any                        # run.Cell
    seed: int
    seconds: float
    core: Any
    compiles: Compiles
    tracer: Tracer
    log: Callable[[str], None]

    @property
    def config(self) -> Dict[str, Any]:
        return self.cell.config

    @property
    def traffic(self) -> Dict[str, Any]:
        return self.cell.traffic


@dataclasses.dataclass
class Window:
    """What a driver's window returns."""

    metrics: Dict[str, float]        # end-to-end values, by metric name
    attempted: int
    failed: int
    compiles: int                    # backend compiles inside the window


@dataclasses.dataclass
class Reading:
    """What a per-layer metric reader gets."""

    trace: trace_lib.Trace
    units: int                       # units of work inside the trace
    unit_bytes: int                  # bytes the count kernel needed for them
    compiles: int                    # backend compiles inside the window
    device_kind: str

    def kernel_s(self) -> float:
        """The kernel's device time in the trace. Raises where units of
        work were traced and no operation names the kernel: a kernel that
        was renamed, or hidden from the matcher, fails the run instead of
        leaving its metrics silently out."""
        seconds = self.trace.busy_s(trace_lib.kernel_matcher(KERNEL))
        if self.units and seconds <= 0:
            raise LookupError(f"{self.units} units traced, but no device "
                              f"operation is named {KERNEL!r}")
        return seconds

    def peak(self, name: str) -> float:
        """A published peak of the chip (an unknown chip raises)."""
        return roofline.peaks(self.device_kind)[name]
