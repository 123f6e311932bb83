"""Live-feed mining demo: frequent episodes tracked as spikes arrive.

    PYTHONPATH=src python examples/streaming_live_feed.py

Simulates the recording loop the paper's neuroscientists sit in: a
multi-electrode stream arrives in small chunks, and after every chunk the
analysis must reflect the WHOLE recording so far. A cascade 0 -> 1 -> 2
(5-15 ms delays) is injected only in the second half of the session, so the
demo shows the miner's result *changing* mid-stream: the cascade is absent
from the early reports, then crosses the threshold and appears — the
moment a cold remine would have found it too, but at per-chunk incremental
cost (StreamingMiner recounts only the span-bounded tail and stitches onto
cached greedy state; see DESIGN.md §9).
"""
import time

import numpy as np

from repro.launch import compile_cache
from repro.core import (EventStream, MinerConfig, StreamingMiner,
                        cache_stats, mine_arrays, warm)


def make_session(rng, n_types=6, duration=40.0, cascade_after=20.0):
    """Poisson background; the 0->1->2 cascade only after ``cascade_after``."""
    t_noise = rng.uniform(0, duration, rng.poisson(30 * duration))
    e_noise = rng.integers(0, n_types, t_noise.size)
    t_inj, e_inj = [], []
    for t0 in rng.uniform(cascade_after, duration, 80):
        t = t0
        for sym in (0, 1, 2):
            t_inj.append(t)
            e_inj.append(sym)
            t += rng.uniform(0.005, 0.015)
    times = np.concatenate([t_noise, t_inj]).astype(np.float32)
    types = np.concatenate([e_noise, e_inj]).astype(np.int32)
    order = np.argsort(times, kind="stable")
    return types[order], times[order]


def main():
    compile_cache.setup()
    rng = np.random.default_rng(11)
    n_types = 6
    types, times = make_session(rng, n_types)
    cfg = MinerConfig(t_low=0.004, t_high=0.016, threshold=40, max_level=3)
    # Serving startup (DESIGN.md §11): size the index for the whole session
    # up front (no mid-session growth, hence no mid-session recompile) and
    # warm every executable the live loop can dispatch — plain per-level
    # counts, cold backfills, and tail recounts at the expected tail-view
    # widths (chunk size + event rate x constraint span bound them).
    per_type = int(np.bincount(types, minlength=n_types).max())
    miner = StreamingMiner(n_types, cfg, initial_cap=per_type)
    warmed = warm(miner.plans(tail_caps=(16, 32, 64)))
    print(f"plan cache warmed: {warmed['compiled']} executable(s) before "
          "the first chunk")

    chunk = max(1, types.size // 16)
    seen = set()
    print(f"session: {types.size} events, fed in {chunk}-event chunks")
    for start in range(0, types.size, chunk):
        ty, tm = types[start:start + chunk], times[start:start + chunk]
        t0 = time.perf_counter()
        results = miner.append(ty, tm)
        dt = (time.perf_counter() - t0) * 1e3
        top = results.get(3)
        found = ({tuple(int(x) for x in row) for row in top.symbols}
                 if top else set())
        fresh = found - seen
        line = (f"t={miner.last_time:6.2f}s  n={miner.n_events:5d}  "
                f"append={dt:6.1f}ms  3-node frequent={len(found)}")
        if fresh:
            line += "  NEW: " + ", ".join(
                "->".join(map(str, f)) for f in sorted(fresh))
        print(line)
        seen = found

    stats = cache_stats()
    print(f"plan cache after the session: {stats['hits']} hit(s), "
          f"{stats['misses']} miss(es) — every miss is one compile the "
          "warm() preload did not anticipate")
    assert (0, 1, 2) in seen, "injected cascade should be discovered"
    # the streaming state is bit-for-bit the cold answer on the full session
    cold = mine_arrays(EventStream(types, times, n_types), cfg)
    got = miner.results
    assert set(got) == set(cold)
    for lvl in cold:
        assert np.array_equal(got[lvl].symbols, cold[lvl].symbols)
        assert np.array_equal(got[lvl].counts, cold[lvl].counts)
    print("OK: cascade 0->1->2 discovered mid-session; final state matches "
          "a cold remine bit-for-bit")


if __name__ == "__main__":
    main()
