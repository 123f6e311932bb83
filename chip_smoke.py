"""Chip smoke: mine a paper-scale spike train on a TPU through every entry point.

    python chip_smoke.py [--seed 0]            # one chip
    python chip_smoke.py --chips 4 [--seed 0]  # the sharded paths, four chips

One process, one chip (``--chips 4``: one process driving four). Exits
non-zero, and prints no result line, when JAX finds no TPU or the repo's
``src/`` is missing. On one chip it runs three phases, each checked:

* batch — paper Table II dataset 5 (64 neurons, 200 s; ``--seed`` sets the
  network and the spikes) mined to level 5 by ``mine_arrays`` with the
  fused Pallas engine, bit-for-bit against ``engine="dense"`` (levels,
  symbols, counts, candidate counts), a fixed sample of episodes per level
  against the FSM oracle, and the embedded cascades recovered;
* streaming — the same stream fed to a ``StreamingMiner`` (head, then the
  tail in chunks) ends equal to the batch result;
* serving — a ``MiningSessionServer`` of a few hundred 16-channel sessions
  warmed, then served a few flush rounds with zero plan-cache misses; a
  handful of sessions equal standalone ``StreamingMiner``s.

Across phases: no plan-cache fallback, and every cached fused executable
holds a compiled Mosaic kernel (``tpu_custom_call``), so nothing ran in
interpret mode. ``--chips 4`` runs only ``mine_sharded`` on a 4-device
mesh against ``mine_arrays`` on one device, and a stream-sharded
``mine_corpus`` against the unsharded one, on Table II dataset 8 (20 s)
mined to level 3: the sharded path holds per-shard tables as wide as the
shard and all-gathers every shard's intervals, and each level compiles a
sharded program.

Phase lines report wall time and XLA compile count: smoke timings, not
metrics. The last line of standard output is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from importlib import metadata
from pathlib import Path

ROOT = Path(__file__).resolve().parent
FUSED = "dense_pallas_fused"
PAPER_DATASET = 5          # Table II: 64 neurons x 200 s
MAX_LEVEL = 5
SHARDED_MAX_LEVEL = 3      # four-chip phases: each level compiles a sharded
                           # program per mesh, so depth is cut to bound time
ORACLE_SAMPLE = 4          # episodes per level checked against the FSM


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def log(msg):
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# phase bookkeeping
# ---------------------------------------------------------------------------


class Phases:
    """Wall time and XLA compile count per phase (smoke timings)."""

    def __init__(self, monitoring):
        self.compiles = 0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, _secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def run(self, name, fn, *args):
        c0, t0 = self.compiles, time.perf_counter()
        out = fn(*args)
        log(f"phase {name}: {time.perf_counter() - t0:.2f} s wall, "
            f"{self.compiles - c0} XLA compiles (smoke timing, not a metric)")
        return out


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------


def spike_config(np, spikes, duration, seed, *, engine=FUSED, mesh=None,
                 cap=None, max_level=MAX_LEVEL):
    """The neuroscience example's thresholds for a ``duration``-second
    recording of the paper's 64-neuron network."""
    from repro.core import MinerConfig
    net = spikes.NetworkConfig(seed=seed)
    noise = spikes.noise_pair_estimate(net, duration)
    return MinerConfig(
        t_low=0.0, t_high=2 * net.delay_high,
        threshold=max(5, int(0.35 * net.trigger_hz * duration)),
        level_thresholds={2: int(1.4 * noise)}, max_level=max_level,
        engine=engine, max_candidates=net.n_neurons ** 2, cap=cap, mesh=mesh)


def max_type_count(np, stream):
    return int(np.bincount(np.asarray(stream.types),
                           minlength=stream.n_types).max())


def assert_levels_equal(np, got, want, what):
    check(sorted(got) == sorted(want),
          f"{what}: levels {sorted(got)} != {sorted(want)}")
    for level in sorted(want):
        g, w = got[level], want[level]
        check(np.array_equal(g.symbols, w.symbols),
              f"{what}: level {level} symbols differ")
        check(np.array_equal(g.counts, w.counts),
              f"{what}: level {level} counts differ")
        check(g.n_candidates == w.n_candidates,
              f"{what}: level {level} n_candidates {g.n_candidates} != "
              f"{w.n_candidates}")


def describe(results):
    return ", ".join(f"L{lv}:{len(la.counts)}/{la.n_candidates}"
                     for lv, la in sorted(results.items()))


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------


def phase_batch(np, spikes, core, stream, cfg, net):
    fused = core.mine_arrays(stream, cfg)
    dense = core.mine_arrays(stream, _replace(cfg, engine="dense"))
    assert_levels_equal(np, fused, dense, "fused vs dense")
    log(f"batch: levels (frequent/candidates) {describe(fused)}")
    check(max(fused) >= 3, f"mining stopped at level {max(fused)}")

    # FSM oracle on a fixed sample: first, last and evenly spaced episodes
    types, times = np.asarray(stream.types), np.asarray(stream.times)
    checked = 0
    for level in sorted(fused):
        if level < 2:
            continue
        la = fused[level]
        k = len(la.counts)
        for i in sorted({int(round(j * (k - 1) / max(ORACLE_SAMPLE - 1, 1)))
                         for j in range(min(ORACLE_SAMPLE, k))}):
            syms = tuple(int(s) for s in la.symbols[i])
            ep = core.Episode(syms, (cfg.t_low,) * (level - 1),
                              (cfg.t_high,) * (level - 1))
            # events of other types never touch the FSM: drop them first
            keep = np.isin(types, syms)
            want = core.count_fsm_numpy(types[keep], times[keep], ep)
            check(int(la.counts[i]) == want,
                  f"FSM oracle: {syms} counted {int(la.counts[i])}, "
                  f"oracle {want}")
            checked += 1
    log(f"batch: {checked} sampled episodes equal the FSM oracle")

    truth = {ep.symbols[:lv] for ep in spikes.embedded_episodes(net)
             for lv in range(2, MAX_LEVEL + 1)}
    hits = {lv: sum(tuple(int(s) for s in row) in truth
                    for row in fused[lv].symbols)
            for lv in fused if lv >= 2}
    log(f"batch: embedded-cascade prefixes recovered per level {hits}")
    check(all(hits.get(lv, 0) > 0 for lv in range(2, max(fused) + 1)),
          f"embedded cascades not recovered at every level: {hits}")
    return fused


def phase_streaming(np, core, stream, cfg, cap, want, tail_s=2.0, chunks=4):
    miner = core.StreamingMiner(stream.n_types, cfg, initial_cap=cap)
    types, times = np.asarray(stream.types), np.asarray(stream.times)
    edges = np.searchsorted(
        times, times[-1] - tail_s * (1 - np.arange(chunks) / chunks))
    bounds = [0, *edges.tolist(), stream.n_events]
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        got = miner.append(types[lo:hi], times[lo:hi])
    log(f"streaming: {len(bounds) - 1} appends (head + {chunks}-chunk "
        f"{tail_s:.1f} s tail)")
    assert_levels_equal(np, got, want, "streaming vs batch")


def phase_serving(np, spikes, core, seed, sessions=256, rounds=3,
                  chunk_s=0.5, n_types=16, checked=4):
    """A pool of 16-channel rigs, each streaming its own recording."""
    from repro.core import plan
    net = spikes.NetworkConfig(n_neurons=n_types, n_embedded=1,
                               episode_len=4)
    feeds = []
    for i in range(sessions):
        s = spikes.simulate(_replace(net, seed=seed + 1000 + i),
                            rounds * chunk_s)
        types, times = np.asarray(s.types), np.asarray(s.times)
        cut = np.searchsorted(times, chunk_s * np.arange(1, rounds))
        bounds = [0, *cut.tolist(), s.n_events]
        feeds.append([(types[a:b], times[a:b])
                      for a, b in zip(bounds[:-1], bounds[1:])])
    cap = plan.capacity_class(max(
        int(np.bincount(np.concatenate([c[0] for c in f]),
                        minlength=n_types).max()) for f in feeds))
    cfg = core.MinerConfig(t_low=0.0, t_high=2 * net.delay_high, threshold=8,
                           max_level=4, engine=FUSED, cap=cap,
                           max_candidates=n_types * n_types)
    srv = core.MiningSessionServer(n_types, cfg, max_sessions=sessions,
                                   initial_cap=cap)
    warmed = srv.warm()
    sids = [srv.create_session() for _ in range(sessions)]
    misses0 = plan.cache_stats()["misses"]
    for r in range(rounds):
        for sid, feed in zip(sids, feeds):
            srv.append(sid, *feed[r])
        srv.flush()
    misses = plan.cache_stats()["misses"] - misses0
    log(f"serving: {sessions} sessions x {rounds} flush rounds, warm() "
        f"compiled {warmed['compiled']} plans, {misses} plan-cache misses "
        "after warm-up")
    check(misses == 0, f"{misses} plan-cache misses after warm()")
    for i in sorted({0, sessions // 3, 2 * sessions // 3, sessions - 1})[
            :checked]:
        solo = core.StreamingMiner(n_types, cfg, initial_cap=cap)
        for c in feeds[i]:
            want = solo.append(*c)
        assert_levels_equal(np, srv.results(sids[i]), want,
                            f"session {i} vs standalone StreamingMiner")
    log(f"serving: sampled sessions equal standalone StreamingMiners "
        f"(e.g. session 0: {describe(srv.results(sids[0]))})")


# ---------------------------------------------------------------------------
# four-chip phases
# ---------------------------------------------------------------------------


def phase_sharded(np, spikes, core, mesh, seed):
    """mine_sharded over the mesh == mine_arrays on one device."""
    duration = dict(spikes.PAPER_DATASETS)[8]
    stream = spikes.paper_dataset(8, cfg=spikes.NetworkConfig(seed=seed))
    cap = max_type_count(np, stream)
    cfg = spike_config(np, spikes, duration, seed, cap=cap,
                       max_level=SHARDED_MAX_LEVEL)
    want = core.mine_arrays(stream, cfg)
    got = core.mine_sharded(stream, _replace(cfg, mesh=mesh))
    assert_levels_equal(np, got, want, "mine_sharded vs mine_arrays")
    log(f"sharded: dataset 8 ({stream.n_events} events) over "
        f"{mesh.devices.size} devices == one device: {describe(got)}")


def phase_corpus(np, spikes, core, mesh, seed, n_streams=8):
    """Stream-sharded mine_corpus == the unsharded corpus miner."""
    duration = dict(spikes.PAPER_DATASETS)[8]
    streams = [spikes.paper_dataset(8, cfg=spikes.NetworkConfig(seed=seed + i))
               for i in range(n_streams)]
    cap = max(max_type_count(np, s) for s in streams)
    cfg = spike_config(np, spikes, duration, seed, cap=cap,
                       max_level=SHARDED_MAX_LEVEL)
    want = core.mine_corpus(streams, cfg)
    got = core.mine_corpus(streams, _replace(cfg, mesh=mesh))
    for i in range(n_streams):
        assert_levels_equal(np, got.per_stream[i], want.per_stream[i],
                            f"sharded corpus stream {i}")
    log(f"corpus: {n_streams} recordings sharded over {mesh.devices.size} "
        f"devices == unsharded (stream 0: {describe(got.per_stream[0])})")


def _replace(obj, **kw):
    import dataclasses
    return dataclasses.replace(obj, **kw)


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def fused_kernels_compiled(plan):
    fused = [p for p in plan.cached_plans() if p.engine == FUSED]
    check(fused, "no fused-engine executable was cached")
    for p in fused:
        check("tpu_custom_call" in plan.cached_executable(p).as_text(),
              f"{p.fn} L{p.level} N{p.cap} B{p.batch}: no Mosaic kernel in "
              "the executable (interpret mode?)")
    return len(fused)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    src = ROOT / "src"
    if not (src / "repro" / "core").is_dir():
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))

    import jax
    import jaxlib
    import numpy as np
    from jax import monitoring

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    log(f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, "
        f"libtpu {libtpu}")
    devices = jax.devices()
    log(f"devices: {devices}")
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform!r}",
              file=sys.stderr)
        return 3
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 3

    from repro.core import plan
    from repro.launch import compile_cache
    log(f"compile cache: {compile_cache.setup()}")
    import repro.core as core
    from repro.data import spikes

    phases = Phases(monitoring)
    try:
        if args.chips == 4:
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((4,), ("data",))
            phases.run("sharded", phase_sharded, np, spikes, core, mesh,
                       args.seed)
            phases.run("corpus", phase_corpus, np, spikes, core, mesh,
                       args.seed)
        else:
            net = spikes.NetworkConfig(seed=args.seed)
            duration = dict(spikes.PAPER_DATASETS)[PAPER_DATASET]
            stream = spikes.paper_dataset(PAPER_DATASET, cfg=net)
            cap = plan.capacity_class(max_type_count(np, stream))
            log(f"dataset: paper Table II #{PAPER_DATASET} "
                f"({net.n_neurons} neurons, {duration:.0f} s, seed "
                f"{args.seed}): {stream.n_events} events, per-type cap {cap}")
            cfg = spike_config(np, spikes, duration, args.seed, cap=cap)
            want = phases.run("batch", phase_batch, np, spikes, core,
                              stream, cfg, net)
            phases.run("streaming", phase_streaming, np, core, stream, cfg,
                       cap, want)
            phases.run("serving", phase_serving, np, spikes, core, args.seed)
            log(f"fused executables with a compiled Mosaic kernel: "
                f"{fused_kernels_compiled(plan)}")
        stats = plan.cache_stats()
        log(f"plan cache: {stats}")
        check(stats["fallbacks"] == 0,
              f"{stats['fallbacks']} plan-cache fallbacks")
    except SmokeFailure as err:
        print(f"chip_smoke: FAILED: {err}", file=sys.stderr)
        return 1

    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
