"""The benchmark's own copy of the paper §V-A spike-train generator.

Inhomogeneous-Poisson network of [Patnaik et al. 2008] as used for Table II
of arXiv:0905.2203: ``n_neurons`` neurons fire at ``base_rate`` (noise) and
``n_embedded`` cascades of ``episode_len`` neurons are injected at
``trigger_hz``, each edge passing with probability ``conn_strength`` after a
delay in ``(delay_low, delay_high]``. This file is a frozen copy of the
program's generator, kept with the benchmark so that the traffic cannot
change with the program; it returns plain numpy arrays.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Network:
    n_neurons: int = 64
    base_rate: float = 20.0        # spontaneous spikes/s/neuron (noise)
    conn_strength: float = 0.9     # firing probability along a cascade edge
    delay_low: float = 0.001       # seconds
    delay_high: float = 0.005
    trigger_hz: float = 6.0        # cascade injection rate per episode
    n_embedded: int = 4
    episode_len: int = 9
    seed: int = 0


#: Table II of arXiv:0905.2203: dataset number -> recording length (s).
PAPER_DATASETS = {1: 4000.0, 2: 2000.0, 3: 1000.0, 4: 500.0,
                  5: 200.0, 6: 100.0, 7: 50.0, 8: 20.0}


def simulate(net: Network, duration_s: float) -> Tuple[np.ndarray, np.ndarray]:
    """A ``duration_s``-second spike train: ``(types i32[n], times f32[n])``,
    sorted by time (stable)."""
    perm = np.random.default_rng(net.seed).permutation(net.n_neurons)
    cascades = [perm[i * net.episode_len:(i + 1) * net.episode_len]
                for i in range(net.n_embedded)]
    rng = np.random.default_rng(net.seed + 1)

    n_noise = rng.poisson(net.base_rate * net.n_neurons * duration_s)
    t_noise = rng.uniform(0.0, duration_s, n_noise)
    e_noise = rng.integers(0, net.n_neurons, n_noise)

    t_extra, e_extra = [], []
    for symbols in cascades:
        triggers = rng.uniform(0.0, duration_s,
                               max(1, rng.poisson(net.trigger_hz * duration_s)))
        for t in triggers:
            for sym in symbols:
                t_extra.append(t)
                e_extra.append(int(sym))
                if rng.uniform() > net.conn_strength:
                    break
                t = t + rng.uniform(net.delay_low, net.delay_high)

    times = np.concatenate([t_noise, np.asarray(t_extra, np.float64)])
    types = np.concatenate([e_noise, np.asarray(e_extra, np.int64)])
    order = np.argsort(times, kind="stable")
    return types[order].astype(np.int32), times[order].astype(np.float32)


def noise_pair_estimate(net: Network, duration_s: float,
                        window_s: float) -> float:
    """Expected chance count of a 2-node episode under noise alone: events
    of the first type times P(the second type within the window)."""
    return (net.base_rate * duration_s) * (net.base_rate * window_s)
