"""On-chip benchmark of the episode miner: one cell, one run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout. ``BENCHMARK.json`` there names the cell's
configuration (``benchmarks/chip/configs/<config>.json``), its traffic mix
(``benchmarks/chip/traffic/<traffic>.json``) and its metrics; the
configuration names the driver (``benchmarks/chip/drivers/<driver>.py``)
that builds the inputs from ``--seed``, warms up, drives the program's entry
point for ``--seconds`` and checks its answers against the plain reference
(``reference.py``). Each per-layer metric is read by
``benchmarks/chip/metrics/<metric>.py``. Adding a cell, a configuration, a
traffic mix or a metric adds files and ``BENCHMARK.json`` entries only.

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` records a
profiler trace of a few seconds inside the window and prints the per-layer
metrics instead. The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(``--trace 1``: also ``busy_s`` and ``window_s``), ``breakdown`` with
``--trace 1``, and last ``checks``: each compared number with its limit,
which also end standard error.

Exits 3, printing no result, when JAX finds no TPU or fewer chips than the
cell asks for, and 2 when the program (``src/repro``) is not in the
checkout. JAX's persistent compilation cache lives at ``.jax_cache/`` in
the checkout.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List

T_START = time.perf_counter()        # set-up is timed from process start

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE_DIR = ROOT / ".jax_cache"

if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import trace as trace_lib  # noqa: E402
from benchmarks.chip.harness import (TRACE_AFTER_S, Compiles, Context,  # noqa: E402
                                     Reading, Tracer, Window)


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_module(path: Path):
    """Import a file of the benchmark by path (its name may hold dots and
    dashes), once per process."""
    name = "chipbench_" + "".join(c if c.isalnum() else "_"
                                  for c in str(path.relative_to(HERE)))
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(spec)
        sys.modules[name] = module
        spec.loader.exec_module(module)
    return sys.modules[name]


# ---------------------------------------------------------------------------
# the cell
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def applies(metric: Dict[str, Any], cell: str, end_to_end) -> bool:
    """A metric is reported in the cells it lists, or, without a list, in
    every cell that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:
        moved = next(m for m in end_to_end if m["name"] == metric["moves"])
        return applies(moved, cell, end_to_end)
    return True


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    work = next((w for w in bench["workloads"] if w["name"] == name), None)
    if work is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == work["config"])
    e2e = bench["end_to_end"]
    return Cell(
        name=name, chips=int(work["chips"]),
        config=json.loads((root / conf["file"]).read_text()),
        traffic=json.loads(
            (HERE / "traffic" / f"{work['traffic']}.json").read_text()),
        end_to_end=[m for m in e2e if applies(m, name, e2e)],
        per_layer=[m for m in bench["per_layer"] if applies(m, name, e2e)])


def log_stderr(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def device_info(jax, chips: int, require_tpu: bool) -> Dict[str, Any]:
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak(jax, chips: int) -> int:
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True,
             log: Callable[[str], None] = log_stderr,
             t_start: float = T_START) -> Dict[str, Any]:
    """One run of ``cell``; returns the result object (not yet printed)."""
    import jax

    device = device_info(jax, cell.chips, require_tpu)
    src = ROOT / "src"
    if not (src / "repro" / "core").is_dir():
        raise FileNotFoundError(f"no repro package under {src}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    import repro.core as core

    driver = load_module(HERE / "drivers" / f"{cell.config['driver']}.py")
    ctx = Context(cell=cell, seed=seed, seconds=seconds, core=core,
                  compiles=Compiles(), tracer=Tracer(trace), log=log)
    state = driver.setup(ctx)
    setup_s = time.perf_counter() - t_start
    try:
        win = driver.window(ctx, state)
    finally:
        ctx.tracer.end()
    device["memory_peak_bytes"] = memory_peak(jax, cell.chips)
    checks = driver.check(ctx, state)
    correct = win.failed == 0 and all(v <= lim for v, lim in checks.values())

    result: Dict[str, Any] = {"correct": correct, "attempted": win.attempted,
                              "failed": win.failed}
    if trace:
        metrics, breakdown = read_trace(ctx, driver, state, device, win)
        result.update(metrics=metrics, device=device, breakdown=breakdown)
    else:
        values = dict(win.metrics, setup_s=setup_s)
        result.update(metrics={
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}, device=device)
    log(f"compiles inside the window: {win.compiles}")
    for name, (value, limit) in checks.items():
        log(f"check {name}: {value} (limit {limit})")
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, (v, lim) in checks.items()}
    return result


def read_trace(ctx: Context, driver, state, device, win: Window):
    """Per-layer metrics, the device's busy time and the breakdown."""
    tracer = ctx.tracer
    if tracer.log_dir is None:
        raise RuntimeError("the window ended before the trace started; "
                           f"--seconds must exceed {TRACE_AFTER_S}")
    try:
        tr = trace_lib.read(trace_lib.find_xplane(tracer.log_dir))
    finally:
        shutil.rmtree(tracer.log_dir, ignore_errors=True)
    reading = Reading(
        trace=tr, units=len(tracer.units),
        unit_bytes=driver.traced_bytes(ctx, state, tracer.units),
        compiles=win.compiles, device_kind=device["kind"])
    device["busy_s"] = tr.busy_s()
    device["window_s"] = tr.window_s
    ctx.log(f"trace: {reading.units} units, window {tr.window_s} s, busy "
            f"{device['busy_s']} s, kernel {reading.kernel_s()} s, "
            f"{len(tr.ops())} device operations")
    if not tr.modules:
        ctx.log(f"trace: no {trace_lib.MODULES_LINE!r} line on the device "
                "planes; scope metrics count operations by name in every "
                "program")
    metrics = {}
    for m in ctx.cell.per_layer:
        value = load_module(HERE / "metrics" / f"{m['name']}.py").read(reading)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics, {"device_ops": tr.top_ops(), "idle_gaps": tr.idle_gaps()}


def use_compile_cache() -> None:
    """Keep every compiled program in the checkout's ``.jax_cache/`` (a
    fixed path: it is part of the cache key), so that only a cell's first
    run in a checkout compiles. The cache is unbounded whatever the
    environment sets: a bounded one evicts by access-time files, and one
    missing file (a cache directory restored without them) makes every
    write fail, so every run compiles again."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    use_compile_cache()
    try:
        result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace))
    except NoChip as err:
        log_stderr(f"chipbench: {err}")
        return 3
    except FileNotFoundError as err:
        log_stderr(f"chipbench: {err}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
