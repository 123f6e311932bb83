"""The paper's technique on the framework's own control plane: mine the
training runtime's telemetry event stream for chained-slowness episodes
(the straggler signature). See DESIGN.md §4 and distributed/fault_tolerance.

Scoring runs through the multi-tenant serving pool (core/serving.py): each
host is one session in a ``MiningSessionServer``, its SLOW events stream
in live as steps complete, and ``scores()`` absorbs every host's pending
events in ONE batched pool flush — the same counts the cold per-host
``telemetry.straggler_scores`` loop produces, at a fixed number of device
dispatches regardless of host count.

    PYTHONPATH=src python examples/telemetry_straggler.py
"""
import numpy as np

from repro.launch import compile_cache
from repro.core import telemetry
from repro.distributed.fault_tolerance import StragglerMonitor


def main():
    compile_cache.setup()
    rng = np.random.default_rng(3)
    hosts = [f"host{i}" for i in range(16)]
    mon = StragglerMonitor(window=30.0, repeat=3, min_count=2)

    # Simulate 200 training steps: host7 degrades persistently after step 60
    # (e.g. thermal throttling); host12 has two isolated blips (not a
    # straggler — the non-overlapped episode count is burst-insensitive).
    wall = 0.0
    for step in range(200):
        base = rng.normal(2.0, 0.05, len(hosts)).clip(1.8, None)
        durs = dict(zip(hosts, base))
        if step > 60:
            durs["host7"] = float(base[7] * rng.uniform(1.8, 2.6))
        if step in (30, 120):
            durs["host12"] = float(base[12] * 3.0)
        wall += max(durs.values())
        mon.record_step(durs, wall)

    scores = mon.scores()
    print("straggler scores (non-overlapped chained-SLOW episode count,")
    print(f"mined via a {len(mon._sessions.server)}-session serving pool):")
    for h, c in sorted(scores.items(), key=lambda kv: -kv[1]):
        print(f"  {h:8s} {c}")
    flagged = mon.flagged()
    print("flagged:", flagged)
    assert "host7" in flagged, "persistent straggler must be flagged"
    assert "host12" not in flagged, "isolated blips must not be flagged"

    # the serving path and the cold per-host counting loop are the same
    # count (the serving differential bar, checked here on real telemetry)
    cold = telemetry.straggler_scores(
        mon.log, window=mon.window, repeat=mon.repeat)
    assert scores == cold, (scores, cold)
    print("OK: persistent straggler isolated from benign blips; "
          "serving-pool scores == cold per-host counting loop")


if __name__ == "__main__":
    main()
