"""Tests of the readers of the program's own spans and scopes
(``benchmarks/chip/spans.py`` and its eight metrics).

Each reader is checked on a hand-built trace against a hand count, made to
fail where units were traced and its span or scope is absent, and made to
read nothing on a program older than its spans. A CPU trace recorded here
of ``mine_arrays`` between the benchmark's own spans checks the span
readers against the result they describe.
"""
from __future__ import annotations

import dataclasses
import re

import numpy as np
import pytest

from benchmarks.chip import run, spans, spikes, trace as tl
from benchmarks.chip.drivers import common
from benchmarks.chip.harness import Reading

SPAN_READERS = {               # metric -> its value on the hand-built trace
    "index_host_ms.batch": 10e-6 / 2,
    "join_host_ms.batch": (6 + 4) * 1e-6 / 2,
    "count_host_ms.batch": (3 + 2) * 1e-6 / 2,
    "sync_wait_ms.batch": (5 + 7 + 5) * 1e-6 / 2,
    "syncs_per_recording.batch": 3 / 2,
    # rows / padded of the two count spans: (60 + 3) / (64 + 16)
    "count_rows_useful_pct.batch": 100.0 * 63 / 80,
}
SCOPE_READERS = {
    # fusion.2 [100, 130) and gather.1 [125, 140): union 40 ns; the kernel
    # [130, 160) takes 10 of it
    "table_gather_ms.batch": 30e-6 / 2,
    # while.15 [160, 200) holds fusion.57 [170, 180): 40 ns
    "scan_table_ms.batch": 40e-6 / 2,
}
READERS = {**SPAN_READERS, **SCOPE_READERS}

HLO = """
ENTRY %main.1 (table.1: f32[8,16]) -> s32[4] {
  %fusion.2 = f32[4,2,16]{2,1,0} fusion(%table.1), kind=kCustom, metadata={op_name="jit(fn)/table_gather/gather" stack_frame_id=1}
  %gather.1 = f32[4,2,16]{2,1,0} gather(%table.1), metadata={op_name="jit(fn)/table_gather/gather"}
  %while.15 = (s32[]) while(%tuple.1), body=%body.1, metadata={op_name="jit(fn)/scan_table/vmap(jit(searchsorted))/while"}
  %fusion.57 = f32[64]{0} fusion(%x.1), kind=kCustom, metadata={op_name="jit(fn)/scan_table/vmap(jit(searchsorted))/while/body/gather"}
  %copy.50 = f32[4,2,16]{2,1,0} copy(%fusion.2)
  ROOT %count_batch.1 = s32[4] custom-call(%copy.50), custom_call_target="tpu_custom_call", metadata={op_name="jit(fn)/count_batch/pallas_call"}
}
"""


def hand_trace(drop=(), count_stats=("rows", "padded")):
    """Two recordings' worth of host spans and device operations (ns)."""
    def count(a, b, rows, padded):
        stats = {"level": 2, "rows": rows, "padded": padded}
        return tl.Event("repro.mine.count", a, b, tuple(
            (k, v) for k, v in stats.items()
            if k == "level" or k in count_stats))

    host = [e for e in [
        tl.Event("repro.mine", 0, 60), tl.Event("repro.mine.index", 1, 11),
        tl.Event("DevicePut", 2, 4),                  # a JAX event inside
        tl.Event("repro.mine.sync", 11, 16), tl.Event("repro.mine.join", 17, 23),
        count(23, 26, 60, 64), tl.Event("repro.mine.sync", 26, 33),
        tl.Event("repro.mine.join", 40, 44), count(44, 46, 3, 16),
        tl.Event("repro.mine.sync", 46, 51)] if e.name not in drop]
    ops = [tl.Event(n, a, b) for n, a, b in [
        ("fusion.2", 100, 130), ("gather.1", 125, 140),
        ("count_batch.1", 130, 160), ("while.15", 160, 200),
        ("fusion.57", 170, 180), ("copy.50", 200, 220)] if n not in drop]
    return tl.Trace(window=(0, 300), device_ops={"/device:TPU:0": ops},
                    spans=[tl.Event("bench.mine_arrays", 0, 60)], host=host)


def reading(trace, units=2):
    return Reading(trace=trace, units=units, unit_bytes=4096, compiles=0,
                   device_kind="TPU v5 lite")


def reader(name):
    return run.load_module(run.HERE / "metrics" / f"{name}.py").read


@pytest.fixture
def hlo(monkeypatch):
    monkeypatch.setattr(spans, "program_hlo", lambda: [HLO])


@pytest.mark.parametrize("cell", ["batch-ds5-200s", "batch-ds8-20s"])
def test_every_reader_is_in_benchmark_json(cell):
    metrics = {m["name"]: m for m in run.load_cell(cell).per_layer}
    assert set(READERS) <= set(metrics)
    assert all(metrics[n]["moves"] == "mine_events_per_s" for n in READERS)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_hand_counted(hlo, name):
    assert reader(name)(reading(hand_trace())) == pytest.approx(READERS[name])


def test_scopes_stay_within_other_device_time(hlo):
    r = reading(hand_trace())
    other = reader("other_device_ms.batch")(r)
    assert sum(reader(n)(r) for n in SCOPE_READERS) <= other
    # copy.50 is in no scope: the 20 ns left over
    assert other - sum(reader(n)(r) for n in SCOPE_READERS) == \
        pytest.approx(20e-6 / 2)


@pytest.mark.parametrize("name,absent", [
    ("index_host_ms.batch", "repro.mine.index"),
    ("join_host_ms.batch", "repro.mine.join"),
    ("count_host_ms.batch", "repro.mine.count"),
    ("sync_wait_ms.batch", "repro.mine.sync"),
    ("syncs_per_recording.batch", "repro.mine.sync"),
    ("count_rows_useful_pct.batch", "repro.mine.count"),
])
def test_span_reader_fails_when_its_span_is_absent(hlo, name, absent):
    read = reader(name)
    assert read(reading(hand_trace(drop=[absent]), units=0)) is None
    with pytest.raises(LookupError, match=re.escape(absent)):
        read(reading(hand_trace(drop=[absent])))


@pytest.mark.parametrize("stat", ["rows", "padded"])
def test_span_stat_sum_hand_counted_and_fails_without_the_stat(stat):
    r = reading(hand_trace())
    want = {"rows": 60 + 3, "padded": 64 + 16}[stat]
    assert spans.span_stat_sum(r, "repro.mine.count", stat) == want
    # only the spans that start inside the traced window count
    late = dataclasses.replace(r.trace, window=(30, 300))
    assert spans.span_stat_sum(reading(late), "repro.mine.count", stat) == \
        {"rows": 3, "padded": 16}[stat]
    lacking = hand_trace(count_stats=[s for s in ("rows", "padded")
                                      if s != stat])
    with pytest.raises(LookupError, match=stat):
        spans.span_stat_sum(reading(lacking), "repro.mine.count", stat)
    with pytest.raises(LookupError, match=stat):
        reader("count_rows_useful_pct.batch")(reading(lacking))
    assert spans.span_stat_sum(reading(lacking, units=0),
                               "repro.mine.count", stat) == 0


PLAN_HLO = """HloModule jit_fn, is_scheduled=true, entry_computation_layout={(f32[8,16]{1,0})->s32[4]{0}}

ENTRY %main.1 (table.1: f32[8,16]) -> s32[4] {
  %fusion = f32[4,2,16]{2,1,0} fusion(%table.1), kind=kLoop, metadata={op_name="jit(fn)/table_gather/gather"}
  ROOT %count_batch.1 = s32[4] custom-call(%fusion), custom_call_target="tpu_custom_call", metadata={op_name="jit(fn)/count_batch/pallas_call"}
}
"""


@pytest.mark.parametrize("modules,want_ns", [(True, 30), (False, 30 + 40 + 10)])
def test_scope_counts_only_the_plan_programs_operations(
        monkeypatch, modules, want_ns):
    """An eager module and a plan's both run an operation named
    ``fusion``: with the modules line only the plan's counts; without it
    the reader counts by name, as before."""
    monkeypatch.setattr(spans, "program_hlo", lambda: [PLAN_HLO])
    ops = [tl.Event("fusion", 100, 130, module="jit_fn(53)"),
           tl.Event("count_batch.1", 130, 160, module="jit_fn(53)"),
           tl.Event("fusion", 200, 240, module="jit_scatter_add(12)"),
           tl.Event("fusion", 250, 260)]          # under no module event
    if not modules:
        ops = [dataclasses.replace(e, module="") for e in ops]
    trace = tl.Trace(window=(0, 300), device_ops={"/device:TPU:0": ops},
                     spans=[], host=hand_trace().host, modules=modules)
    assert reader("table_gather_ms.batch")(reading(trace)) == \
        pytest.approx(want_ns * 1e-6 / 2)


def test_module_names_of_the_plan_programs():
    assert spans.module_names([PLAN_HLO, HLO, PLAN_HLO]) == {"jit_fn"}


@pytest.mark.parametrize("name,absent", [
    ("table_gather_ms.batch", ["fusion.2", "gather.1"]),
    ("scan_table_ms.batch", ["while.15", "fusion.57"]),
])
def test_scope_reader_fails_when_its_scope_is_absent(hlo, name, absent):
    read = reader(name)
    with pytest.raises(LookupError, match=name.split("_ms")[0]):
        read(reading(hand_trace(drop=absent)))


@pytest.mark.parametrize("name", sorted(SCOPE_READERS))
def test_scope_reader_fails_when_no_program_carries_the_scope(
        monkeypatch, name):
    unscoped = re.sub(r"/(table_gather|scan_table)/", "/", HLO)
    monkeypatch.setattr(spans, "program_hlo", lambda: [unscoped])
    with pytest.raises(LookupError):
        reader(name)(reading(hand_trace()))


@pytest.mark.parametrize("name", sorted(READERS))
def test_program_without_spans_reads_nothing(monkeypatch, hlo, name):
    """An older program (no ``repro.core.obs``) leaves the metric out
    instead of failing the run."""
    monkeypatch.setattr(spans, "program_has_spans", lambda: False)
    older = hand_trace(drop=["repro.mine", "repro.mine.index",
                             "repro.mine.join", "repro.mine.count",
                             "repro.mine.sync"])
    assert reader(name)(reading(older)) is None


def test_scoped_names_need_every_program_to_agree():
    a = ('  %fusion.2 = f32[4] fusion(%x), metadata={op_name="f/table_gather/g"}\n'
         '  %copy.50 = f32[4] copy(%x), metadata={op_name="f/table_gather/g"}\n'
         '  %add.1 = f32[4] add(%x), metadata={op_name="f/table_gatherer/add"}\n')
    b = ('  %fusion.2 = f32[4] fusion(%x), metadata={op_name="f/table_gather/g"}\n'
         '  %copy.50 = f32[4] copy(%x)\n')
    assert spans.scoped_names([a], "table_gather") == {"fusion.2", "copy.50"}
    assert spans.scoped_names([a, b], "table_gather") == {"fusion.2"}
    assert spans.scoped_names([a, b], "scan_table") == set()


def test_cpu_recorded_trace_span_readers_describe_the_result(tmp_path):
    import jax
    from repro.core import EventStream, MinerConfig, mine_arrays

    types, times = spikes.simulate(
        spikes.Network(n_neurons=8, n_embedded=1, episode_len=4, seed=3), 3.0)
    cfg = MinerConfig(t_low=0.0, t_high=0.01, threshold=5,
                      level_thresholds={2: 8}, max_level=4,
                      engine="dense_pallas_fused", max_candidates=64,
                      cap=common.pow2_ceil(np.bincount(types).max()))
    result = mine_arrays(EventStream(types, times, 8), cfg)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(tl.WINDOW_SPAN):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.mine_arrays"):
                mine_arrays(EventStream(types, times, 8), cfg)
    jax.profiler.stop_trace()

    trace = tl.read(tl.find_xplane(str(tmp_path)),
                    device_plane=re.compile(r"^/host:CPU$"), ops_line="tf_XLA")
    r = reading(trace)
    counted = sum(1 for lv, la in result.items() if lv > 1 and la.n_candidates)
    assert reader("syncs_per_recording.batch")(r) == 1 + counted
    leaves = [reader(n)(r) for n in ("index_host_ms.batch",
                                     "join_host_ms.batch",
                                     "count_host_ms.batch",
                                     "sync_wait_ms.batch")]
    assert all(v > 0 for v in leaves)
    call_ms = 1e-6 * sum(s.end_ns - s.start_ns for s in trace.spans) / 2
    assert sum(leaves) <= call_ms
    # the benchmark's own spans and the device busy time are as before
    assert [s.name for s in trace.spans] == ["bench.mine_arrays"] * 2
    assert 0 < trace.busy_s() <= trace.window_s
