"""What the drivers share: the network and the mining rule of a
configuration file, seeds, and the reference and byte counts of a result."""
from __future__ import annotations

from typing import Dict

import numpy as np

from benchmarks.chip import reference, roofline, spikes

SEED_SPACE = 2 ** 62


def catalog_order(seed: int, n: int):
    """The generator seeds of a run's ``n`` streams: the fixed catalog
    ``0 .. n-1`` in an order drawn from ``--seed`` (any whole number). The
    work of a realization (how many episodes are frequent at each level,
    so how many candidates each level counts) differs from one to the next
    by several per cent, so every seed gets the same streams: the seed
    changes the order, not the work."""
    return np.random.default_rng(seed % SEED_SPACE).permutation(n).tolist()


def fresh_seed(seed: int, n: int) -> int:
    """The generator seed of a run's fresh recording: drawn from ``--seed``,
    outside the catalog ``0 .. n-1``."""
    rng = np.random.default_rng((seed % SEED_SPACE, 1))
    return n + int(rng.integers(0, 2 ** 31))


def pow2_ceil(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def network(conf, seed: int) -> spikes.Network:
    return spikes.Network(**conf["network"], seed=seed)


def thresholds(conf, duration_s: float):
    """The configuration's rule for a ``duration_s``-second stream: the
    frequency threshold, and the per-level overrides."""
    rule, net = conf["mining"], spikes.Network(**conf["network"])
    threshold = max(rule["threshold_min"],
                    int(rule["threshold_per_trigger"] * net.trigger_hz
                        * duration_s))
    noise = spikes.noise_pair_estimate(net, duration_s, rule["t_high"])
    return threshold, {2: int(rule["level2_over_noise"] * noise)}


def miner_config(core, conf, duration_s: float, *, cap: int):
    rule = conf["mining"]
    threshold, per_level = thresholds(conf, duration_s)
    return core.MinerConfig(
        t_low=rule["t_low"], t_high=rule["t_high"], threshold=threshold,
        level_thresholds=per_level, max_level=rule["max_level"],
        engine=rule["engine"], max_candidates=rule["max_candidates"], cap=cap)


def reference_mine(conf, miner_cfg, types, times, *, edges: str = "exact"):
    """The plain reference's result for one stream under ``miner_cfg``'s
    thresholds."""
    return reference.mine(
        types, times, n_types=conf["network"]["n_neurons"],
        t_low=miner_cfg.t_low, t_high=miner_cfg.t_high,
        threshold=miner_cfg.threshold,
        level_thresholds=dict(miner_cfg.level_thresholds or {}),
        max_level=miner_cfg.max_level,
        max_candidates=miner_cfg.max_candidates, edges=edges)


def as_levels(result) -> Dict[int, reference.Level]:
    """The program's per-level result in the reference's form."""
    return {level: (np.asarray(la.symbols), np.asarray(la.counts),
                    int(la.n_candidates))
            for level, la in result.items()}


def level_bytes(result, type_counts, max_candidates: int) -> int:
    """Bytes the count kernel needs for every level >= 2 of ``result``: the
    candidates of a level are the join of the previous level's frequent
    episodes (in the program's order)."""
    total = 0
    for level in sorted(result):
        if level < 2 or level - 1 not in result:
            continue
        frontier = [tuple(int(x) for x in row)
                    for row in result[level - 1].symbols]
        total += roofline.count_bytes(
            reference.join(frontier, level, max_candidates), type_counts)
    return total
