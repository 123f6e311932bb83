"""The control that ``correct`` has to reject, at a cell's own size.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1 2 3 \
        [--seconds 10]

Runs the cell as ``run.py`` does, with the plain reference computed with
float32 window edges (``reference.py``, ``edges="float32"``) put in the
program's place: every ``mine_arrays`` answer comes from it. The
configuration states exact window edges on float64 bounds; the
float32 edge is the step below it that a faster implementation would be
tempted to take. Prints, per seed, the compared numbers with their limits
and whether the run came out correct (it must not). Not part of the
benchmark's runs.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import types as pytypes
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import run  # noqa: E402
from benchmarks.chip.drivers import common  # noqa: E402


def as_result(levels):
    return {level: pytypes.SimpleNamespace(symbols=rows, counts=counts,
                                           n_candidates=n)
            for level, (rows, counts, n) in levels.items()}


def install(core, conf) -> None:
    """Put the float32-edge reference in the program's place."""

    def mine_arrays(stream, cfg):
        return as_result(common.reference_mine(
            conf, cfg, np.asarray(stream.types), np.asarray(stream.times),
            edges="float32"))

    core.mine_arrays = mine_arrays


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    run.use_compile_cache()
    sys.path.insert(0, str(ROOT / "src"))
    import repro.core as core

    install(core, cell.config)
    for seed in args.seeds:
        result = run.run_cell(
            cell, seed=seed, seconds=args.seconds, trace=False,
            t_start=time.perf_counter())
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "correct": result["correct"],
                          "checks": result["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
