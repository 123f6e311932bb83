"""Tests of the on-chip benchmark's yardstick (``benchmarks/chip``).

They run on the CPU: the trace reduction on a small trace recorded here,
the roofline byte count against a hand count, the generator copy pinned to
the paper's dataset sizes, the plain reference against the program's FSM
oracle, the control that the comparison must reject, and whole runs of
small cells with the chip check skipped: sound, and with the timed path
broken underneath, where ``correct`` must come out false.
"""
from __future__ import annotations

import copy
import json
import os
import re
import subprocess
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks.chip import reference, roofline, run, spikes, trace as tl
from benchmarks.chip.drivers import common

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


# ---------------------------------------------------------------------------
# the generator copy
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dataset,events", [(5, 284_868), (8, 28_506)])
def test_generator_copy_pins_table2_sizes(dataset, events):
    types, times = spikes.simulate(spikes.Network(seed=0),
                                   spikes.PAPER_DATASETS[dataset])
    assert types.size == times.size == events
    assert np.all(np.diff(times) >= 0)


def test_generator_copy_equals_program_generator():
    from repro.data import spikes as program

    types, times = spikes.simulate(spikes.Network(seed=3), 20.0)
    want = program.paper_dataset(8, cfg=program.NetworkConfig(seed=3))
    assert np.array_equal(types, np.asarray(want.types))
    assert np.array_equal(times, np.asarray(want.times))


# ---------------------------------------------------------------------------
# the trace reduction
# ---------------------------------------------------------------------------


def test_union_gaps_and_kernel_names_hand_counted():
    ops = [(0, 10), (5, 15), (20, 30), (29, 31), (40, 40)]
    assert tl.union(ops) == [(0, 15), (20, 31)]
    assert tl.total_ns(ops) == 26
    assert tl.gaps(ops, (-5, 50)) == [(-5, 0), (15, 20), (31, 50)]
    assert tl.total_ns(tl.clip(ops, (12, 25))) == 3 + 5
    match = tl.kernel_matcher("count_batch")
    assert match("count_batch") and match("count_batch.12")
    assert not match("count_batch_fusion") and not match("fusion.count_batch")
    # a TPU trace names an operation by its HLO line
    hlo = ('%count_batch.1 = (s32[128,1,32]{2,1,0}) custom-call(f32[4096,2,'
           '8,128]{3,2,1,0} %reshape.27), custom_call_target="tpu_custom_call"')
    assert tl.op_name(hlo) == "count_batch.1" and match(tl.op_name(hlo))
    assert tl.op_name("%fusion.57 = f32[32768]{0} fusion(%gte.2)") == "fusion.57"
    assert tl.op_name("sort.0") == "sort.0"

    trace = tl.Trace(window=(0, 100), device_ops={
        "/device:TPU:0": [tl.Event("count_batch.1", 10, 40),
                          tl.Event("fusion.3", 30, 50),
                          tl.Event("count_batch.1", 60, 70)]},
        spans=[tl.Event("bench.flush", 0, 55), tl.Event("bench.wait", 55, 100)])
    assert trace.busy_s() == pytest.approx(50e-9)
    assert trace.busy_s(match) == pytest.approx(40e-9)
    assert trace.top_ops(1) == [["count_batch.1", pytest.approx(40e-9)]]
    assert trace.idle_gaps() == [["bench.wait", pytest.approx(40e-9)],
                                 ["bench.flush", pytest.approx(10e-9)]]


def test_cpu_recorded_trace_busy_union_and_kernel_names(tmp_path):
    import jax
    import jax.numpy as jnp

    sort_twice = jax.jit(lambda x: jnp.sort(x) * 2.0)
    x = jnp.arange(100_000.0)[::-1]
    sort_twice(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(tl.WINDOW_SPAN):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                sort_twice(x).block_until_ready()
    jax.profiler.stop_trace()

    # on the CPU the operations run on the host plane's XLA threads
    trace = tl.read(tl.find_xplane(str(tmp_path)),
                    device_plane=re.compile(r"^/host:CPU$"), ops_line="tf_XLA")
    assert [s.name for s in trace.spans] == ["bench.step"] * 3
    ops = [(e.start_ns, e.end_ns) for e in trace.ops()]
    assert ops, "no XLA operation in the CPU trace"
    # busy is the union: brute force over the nanoseconds it spans
    lo = min(a for a, _ in ops)
    covered = np.zeros(max(b for _, b in ops) - lo, bool)
    for a, b in tl.clip(ops, trace.window):
        covered[a - lo:b - lo] = True
    assert trace.busy_s() * 1e9 == pytest.approx(covered.sum())
    assert 0 < trace.busy_s() <= trace.window_s
    sort_s = trace.busy_s(tl.kernel_matcher("sort"))
    assert 0 < sort_s <= trace.busy_s()
    idle = trace.idle_gaps(1000)
    assert sum(secs for _, secs in idle) == pytest.approx(
        trace.window_s - trace.busy_s())
    assert all(label.startswith(("bench.step", "unannotated"))
               for label, _ in idle)
    assert any(re.match(r"^sort(\.\d+)?$", e.name) for e in trace.ops())


def test_device_ops_keep_the_module_that_covers_their_start():
    mods = [tl.Event("jit_fn(53)", 100, 200),
            tl.Event("jit_scatter_add(12)", 0, 50)]
    ops = [tl.Event("fusion", 10, 20), tl.Event("fusion", 100, 150),
           tl.Event("copy.1", 190, 230), tl.Event("fusion", 60, 70),
           tl.Event("fusion", 200, 210)]
    assert [e.module for e in tl.in_modules(ops, mods)] == [
        "jit_scatter_add(12)", "jit_fn(53)", "jit_fn(53)", "", ""]
    assert [e.module for e in tl.in_modules(ops, [])] == [""] * 5
    assert tl.module_name("jit_fn(10399301410868591309)") == "jit_fn"
    assert tl.module_name("jit_scatter-add") == "jit_scatter-add"


def test_cpu_recorded_trace_keeps_span_stats(tmp_path):
    import jax
    import jax.numpy as jnp

    x = jnp.arange(1000.0)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(tl.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("bench.step"):
            with jax.profiler.TraceAnnotation("repro.mine.count", rows=12,
                                              padded=16):
                jnp.cumsum(x).block_until_ready()
    jax.profiler.stop_trace()

    trace = tl.read(tl.find_xplane(str(tmp_path)),
                    device_plane=re.compile(r"^/host:CPU$"), ops_line="tf_XLA")
    counts = [e for e in trace.host if e.name == "repro.mine.count"]
    assert [list(e.stats) for e in counts] == [[("rows", 12), ("padded", 16)]]
    assert [s.stats for s in trace.spans] == [()]
    hash(counts[0])                         # an Event stays hashable
    assert not trace.modules                # the CPU has no modules line


# ---------------------------------------------------------------------------
# the roofline's numerator
# ---------------------------------------------------------------------------


def test_roofline_bytes_hand_count():
    counts = [3, 5, 0, 7]
    # (0, 1): 4 * (3 + 5) + 4 ; (1, 1): 4 * (5 + 5) + 4 ; (3, 2, 0): 4 * 10 + 4
    assert roofline.count_bytes([(0, 1), (1, 1)], counts) == 36 + 44
    assert roofline.count_bytes([(3, 2, 0)], counts) == 44
    assert roofline.count_bytes([], counts) == 0
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


def test_level_bytes_counts_the_join_of_each_frontier():
    class La:
        def __init__(self, rows):
            self.symbols = np.asarray(rows, np.int32)

    result = {1: La([[0], [2]]), 2: La([[0, 2], [2, 2]]), 3: La([])}
    counts = [1, 100, 10]
    # level 2 joins {0, 2}: (0,0) (0,2) (2,0) (2,2); level 3 joins the two
    # frequent pairs: (0,2,2) and (2,2,2)
    want = (4 * (2 + 11 + 11 + 20) + 4 * 4) + (4 * (21 + 30) + 4 * 2)
    assert common.level_bytes(result, counts, 4096) == want


# ---------------------------------------------------------------------------
# the plain reference and its control
# ---------------------------------------------------------------------------


def test_reference_counts_equal_fsm_oracle():
    from repro.core import Episode, count_fsm_numpy

    types, times = spikes.simulate(
        spikes.Network(n_neurons=6, n_embedded=1, episode_len=4, seed=5), 3.0)
    rng = np.random.default_rng(1)
    rows = [tuple(int(s) for s in rng.integers(0, 6, n))
            for n in (2, 2, 3, 3, 4) for _ in range(6)] + [(1, 1), (2, 1, 2)]
    for lo, hi in ((0.0, 0.01), (0.001, 0.02), (0.0, 0.0035)):
        got = reference.count(types, times, 6, rows, lo, hi)
        want = [count_fsm_numpy(types, times, Episode(
            r, (lo,) * (len(r) - 1), (hi,) * (len(r) - 1))) for r in rows]
        assert got.tolist() == want


@pytest.mark.parametrize("engine", ["dense", "dense_pallas_fused"])
def test_reference_mine_equals_program(engine):
    from repro.core import EventStream, MinerConfig, mine_arrays

    net = spikes.Network(n_neurons=12, n_embedded=1, episode_len=5, seed=9)
    types, times = spikes.simulate(net, 6.0)
    cfg = MinerConfig(t_low=0.0, t_high=0.01, threshold=12,
                      level_thresholds={2: 20}, max_level=4, engine=engine,
                      max_candidates=64,
                      cap=common.pow2_ceil(np.bincount(types).max()))
    got = common.as_levels(mine_arrays(EventStream(types, times, 12), cfg))
    want = reference.mine(types, times, n_types=12, t_low=0.0, t_high=0.01,
                          threshold=12, level_thresholds={2: 20}, max_level=4,
                          max_candidates=64)
    assert reference.mismatches(got, want) == 0
    assert max(want) >= 3


def test_control_float32_edges_is_rejected():
    """The reference with float32 window edges, in the program's place, on
    the recordings of ``batch-ds8-20s``: the comparison must not pass it."""
    cell = run.load_cell("batch-ds8-20s")
    duration = cell.traffic["recording_s"]
    threshold, per_level = common.thresholds(cell.config, duration)
    kw = dict(n_types=cell.config["network"]["n_neurons"],
              t_low=cell.config["mining"]["t_low"],
              t_high=cell.config["mining"]["t_high"], threshold=threshold,
              level_thresholds=per_level,
              max_level=cell.config["mining"]["max_level"],
              max_candidates=cell.config["mining"]["max_candidates"])
    bad = 0
    for k in common.catalog_order(1, cell.traffic["recordings"]):
        types, times = spikes.simulate(common.network(cell.config, k),
                                       duration)
        bad += reference.mismatches(
            reference.mine(types, times, edges="float32", **kw),
            reference.mine(types, times, **kw))
    assert bad > cell.config["limits"]["mismatched_entries"]


# ---------------------------------------------------------------------------
# whole runs of small cells, chip check skipped
# ---------------------------------------------------------------------------


def small_cell(name):
    cell = run.load_cell(name)
    cell.config = copy.deepcopy(cell.config)
    cell.config["network"].update(n_neurons=8, n_embedded=1, episode_len=4)
    cell.config["mining"].update(max_level=3, max_candidates=64)
    cell.traffic = dict(cell.traffic, recording_s=3.0, recordings=2)
    if "sessions" in cell.traffic:
        cell.traffic["sessions"] = 2
    return cell


def run_small(name, seed=2 ** 31 + 17):
    return run.run_cell(small_cell(name), seed=seed, seconds=1.5,
                        trace=False, require_tpu=False, log=lambda _: None,
                        t_start=time.perf_counter())


CELLS = [w["name"] for w in BENCH["workloads"]]
CHECKS = ["mismatched_entries", "fresh_recording_mismatched_entries"]


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    result = run_small(name)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] > 0
    assert list(result)[-1] == "checks"
    assert result["checks"] == {c: {"value": 0, "limit": 0} for c in CHECKS}
    cell = small_cell(name)
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}


def test_fresh_recording_is_drawn_from_the_seed():
    n = 4
    seeds = {common.fresh_seed(s, n) for s in (1, 2, 2 ** 31 + 17, 2 ** 40)}
    assert len(seeds) == 4 and min(seeds) >= n
    assert common.fresh_seed(7, n) == common.fresh_seed(7, n)


def _alter_answer(counts, *rest):
    return (counts + 1, *rest)


def _drop_half(counts, *rest):
    half = counts.shape[-1] // 2
    return (counts.at[..., half:].set(0), *rest)


# The program's count entries: every entry point (mining.py, streaming.py,
# corpus.py, distributed.py, serving.py) calls one of them as
# ``counting.<name>``, and each returns the counts first.
COUNT_ENTRIES = ("count_batch_indexed", "count_batch_indexed_stateful",
                 "count_tail_batch_indexed", "count_corpus_indexed",
                 "count_corpus_tail_grouped")


def break_counts(monkeypatch, fault, when=lambda: True):
    """Wrap every entry of ``COUNT_ENTRIES`` so that ``fault`` alters its
    counts (while ``when()`` holds); returns the calls made, by entry."""
    from repro.core import counting

    calls = dict.fromkeys(COUNT_ENTRIES, 0)

    def wrap(name):
        real = getattr(counting, name)

        def entry(*a, **k):
            calls[name] += 1
            out = real(*a, **k)
            return fault(*out) if when() else out
        return entry

    for name in COUNT_ENTRIES:
        monkeypatch.setattr(counting, name, wrap(name))
    return calls


def assert_reached_an_entry(name, calls):
    """A cell whose timed path calls none of the wrapped entries would
    pass the fault tests without their fault: name the gap instead."""
    if not any(calls.values()):
        raise AssertionError(
            f"the small run of {name} reached none of COUNT_ENTRIES "
            f"{COUNT_ENTRIES}: add the count entry its path calls")


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", [_alter_answer, _drop_half])
def test_broken_timed_path_is_not_correct(monkeypatch, name, fault):
    calls = break_counts(monkeypatch, fault)
    result = run_small(name)
    assert_reached_an_entry(name, calls)
    assert not result["correct"]
    assert all(result["checks"][c]["value"] > 0 for c in CHECKS)


def test_path_that_reaches_no_count_entry_is_named(monkeypatch):
    """A driver whose run calls none of ``COUNT_ENTRIES`` fails the fault
    tests' precondition with a message naming them."""
    from benchmarks.chip.harness import Window

    fake = SimpleNamespace(
        setup=lambda ctx: None,
        window=lambda ctx, st: Window(metrics={"mine_events_per_s": 1.0},
                                      attempted=1, failed=0, compiles=0),
        check=lambda ctx, st: {c: (0, 0) for c in CHECKS})
    real_load = run.load_module
    monkeypatch.setattr(run, "load_module", lambda path: fake
                        if path.name == "fake.py" else real_load(path))
    cell = small_cell("batch-ds8-20s")
    cell.config["driver"] = "fake"
    calls = break_counts(monkeypatch, _alter_answer)
    result = run.run_cell(cell, seed=5, seconds=0.1, trace=False,
                          require_tpu=False, log=lambda _: None,
                          t_start=time.perf_counter())
    assert result["correct"]
    with pytest.raises(AssertionError, match="COUNT_ENTRIES"):
        assert_reached_an_entry(cell.name, calls)


@pytest.mark.parametrize("name", CELLS)
def test_fault_after_the_window_fails_the_fresh_recording(monkeypatch, name):
    """A count that goes wrong only after the window (as a program that
    breaks on a stream length it has not seen would) is caught by the
    fresh recording alone."""
    driver = run.load_module(
        run.HERE / "drivers" / f"{small_cell(name).config['driver']}.py")
    broken = {"on": False}
    calls = break_counts(monkeypatch, _alter_answer, lambda: broken["on"])
    real_window = driver.window

    def window(*a, **k):
        win = real_window(*a, **k)
        broken["on"] = True
        return win

    monkeypatch.setattr(driver, "window", window)
    result = run_small(name)
    assert_reached_an_entry(name, calls)
    assert not result["correct"]
    assert result["checks"]["mismatched_entries"]["value"] == 0
    assert result["checks"]["fresh_recording_mismatched_entries"]["value"] > 0


@pytest.mark.parametrize("reader", ["count_kernel_ms.batch",
                                    "count_batch_roofline.batch",
                                    "other_device_ms.batch"])
def test_kernel_readers_fail_when_the_kernel_is_not_in_the_trace(reader):
    from benchmarks.chip.harness import Reading

    trace = tl.Trace(window=(0, 100), device_ops={
        "/device:TPU:0": [tl.Event("fusion.3", 10, 50)]}, spans=[])
    read = run.load_module(run.HERE / "metrics" / f"{reader}.py").read
    kw = dict(trace=trace, unit_bytes=4096, compiles=0,
              device_kind="TPU v5 lite")
    assert read(Reading(units=0, **kw)) is None
    with pytest.raises(LookupError, match="count_batch"):
        read(Reading(units=2, **kw))


# ---------------------------------------------------------------------------
# the command and BENCHMARK.json
# ---------------------------------------------------------------------------


def test_no_tpu_exits_nonzero_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", "batch-ds8-20s",
         "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_every_cell_finds_its_files_by_name():
    here = ROOT / "benchmarks" / "chip"
    for work in BENCH["workloads"]:
        cell = run.load_cell(work["name"])
        assert (here / "drivers" / f"{cell.config['driver']}.py").is_file()
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert (here / "metrics" / f"{m['name']}.py").is_file()
            assert any(e["name"] == m["moves"] for e in cell.end_to_end)
    for conf in BENCH["configs"]:
        assert json.loads((ROOT / conf["file"]).read_text())["name"] == \
            conf["name"]
