"""Quickstart: count and mine frequent episodes in an event stream.

    PYTHONPATH=src python examples/quickstart.py

Before sending a PR, run the static invariant checker (DESIGN.md §13) —
it lint-checks the tree and traces the registered plan matrix, and CI
runs it blocking:

    PYTHONPATH=src python scripts/staticcheck.py --all       # what CI runs
    PYTHONPATH=src python scripts/staticcheck.py --changed-only  # fast loop
"""
import numpy as np

from repro.launch import compile_cache
from repro.core import (EventStream, MinerConfig, MiningSessionServer,
                        StreamingMiner, cache_stats, count_fsm_numpy,
                        count_nonoverlapped, mine, plans_for_miner, serial,
                        warm)


def main():
    compile_cache.setup()
    rng = np.random.default_rng(7)

    # An event stream: 6 event types, Poisson noise + an embedded cascade
    # 0 -> 1 -> 2 with 5-15 ms delays (the paper's running example shape).
    n_types, duration = 6, 30.0
    t_noise = rng.uniform(0, duration, rng.poisson(40 * duration))
    e_noise = rng.integers(0, n_types, t_noise.size)
    t_inj, e_inj = [], []
    for t0 in rng.uniform(0, duration, 60):
        t = t0
        for sym in (0, 1, 2):
            t_inj.append(t)
            e_inj.append(sym)
            t += rng.uniform(0.005, 0.015)
    times = np.concatenate([t_noise, t_inj])
    types = np.concatenate([e_noise, e_inj]).astype(np.int32)
    order = np.argsort(times)
    stream = EventStream(types[order], times[order].astype(np.float32), n_types)

    # 1) Count one constrained episode, redesigned (paper) engine vs oracle
    ep = serial([0, 1, 2], 0.004, 0.016)
    res = count_nonoverlapped(stream, ep, engine="count_scan_write",
                              cap_occ=4 * stream.n_events)
    oracle = count_fsm_numpy(stream.types, stream.times, ep)
    print(f"episode {ep}: count={int(res.count)} (oracle {oracle}), "
          f"superset tracked={int(res.n_superset)}")

    # 2) Level-wise mining: discovers the embedded cascade automatically.
    # Preload the executable cache first (DESIGN.md §11): every
    # (level, batch-class) bucket this config can dispatch compiles here,
    # so the mining loop itself never stops to compile.
    cfg = MinerConfig(t_low=0.004, t_high=0.016, threshold=30, max_level=3)
    warmed = warm(plans_for_miner(cfg, n_types=n_types,
                                  n_events=stream.n_events))
    print(f"plan cache warmed: {warmed['compiled']} executable(s) compiled "
          f"ahead of mining")
    results = mine(stream, cfg)
    for level, lr in results.items():
        shown = ", ".join(f"{e}(n={c})" for e, c in
                          zip(lr.episodes[:4], lr.counts[:4]))
        print(f"level {level}: {len(lr.episodes)} frequent "
              f"of {lr.n_candidates} candidates: {shown}")
    top3 = results.get(3)
    assert top3 and any(e.symbols == (0, 1, 2) for e in top3.episodes), \
        "embedded cascade should be discovered"
    stats = cache_stats()
    print(f"plan cache: {stats['hits']} hit(s), {stats['misses']} miss(es) "
          "after warm (0 misses = every level ran a preloaded executable)")
    print("OK: embedded cascade 0->1->2 discovered")

    # 3) Multi-tenant serving (DESIGN.md §12): many live sessions, each an
    # incrementally-growing stream, mined in ONE batched pool pass per
    # flush. Each session's result is bit-for-bit a standalone
    # StreamingMiner fed the same chunks.
    srv = MiningSessionServer(n_types, cfg, max_sessions=4, initial_cap=256)
    srv.warm()                     # serving startup: preload every bucket
    half = stream.n_events // 2
    sessions = [srv.create_session() for _ in range(3)]
    for sid in sessions:           # first chunk for every session...
        srv.append(sid, stream.types[:half], stream.times[:half])
    srv.flush()                    # ...absorbed in one batched level loop
    for sid in sessions:           # streams keep growing
        srv.append(sid, stream.types[half:], stream.times[half:])
    got = srv.results(sessions[0])  # reads flush all pending sessions
    solo = StreamingMiner(n_types, cfg, initial_cap=256)
    solo.append(stream.types[:half], stream.times[:half])
    ref = solo.append(stream.types[half:], stream.times[half:])
    assert all(np.array_equal(got[lv].counts, ref[lv].counts) for lv in ref)
    print(f"serving pool: {len(sessions)} sessions mined per flush, "
          "each == its standalone StreamingMiner")


if __name__ == "__main__":
    main()
