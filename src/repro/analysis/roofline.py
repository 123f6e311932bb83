"""Three-term roofline model from compiled dry-run artifacts.

    T_compute    = FLOPs_total   / (chips * peak FLOP/s)
    T_memory     = HBM_bytes     / (chips * peak HBM bytes/s)
    T_collective = coll_bytes    / (chips * peak ICI bytes/s)

Peaks come from one table, :data:`PEAKS`, keyed by the device kind JAX
reports; an unknown device raises instead of borrowing another's peaks.

`cost_analysis()` on a GSPMD-partitioned module is **per-device** (verified
by calibration in EXPERIMENTS.md §Roofline-notes: a 4.4 TFLOP global matmul
on 512 devices reports 8.6 GFLOP), so per-device numbers are used directly
against per-chip peaks; *_total in the report = per_device * chips.

collective_bytes is not in cost_analysis: we parse the optimized HLO and
sum operand bytes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute (sync and async -start forms).

This model is LOAD-BEARING for the hot path, not just reporting:
``kernels.autotune.model_time`` routes its analytic per-launch cost dicts
through :func:`analyze` to pre-rank tile candidates, and the winners land
in ``kernels/tuned_configs.json`` — the table every counting entry point
resolves ``None`` block knobs against (``benchmarks/run.py --autotune``
regenerates it). Changing the peaks or the t_compute/t_memory terms here
reshapes the candidate ranking, so recheck the tuned table after edits.
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, Optional


@dataclasses.dataclass(frozen=True)
class Peaks:
    """Published per-chip peaks of one device kind."""
    flops: float        # bf16 FLOP/s
    hbm_bw: float       # HBM bytes/s
    ici_bw: float       # chip-to-chip interconnect bytes/s per link
    source: str


#: Peaks keyed by ``jax.devices()[0].device_kind``. A device that is not
#: here is an error wherever a time is divided by a peak, never a default.
PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(
        flops=197e12, hbm_bw=819e9, ici_bw=50e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "819 GB/s HBM, 1,600 Gbit/s ICI per chip (4 links)"),
}
V5E = "TPU v5 lite"


def peaks(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; unknown devices raise ``KeyError``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add it to "
            f"analysis.roofline.PEAKS (known: {sorted(PEAKS)})") from None

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*(?:\([^)]*\)\s*)?"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"(pred|s4|u4|s8|u8|s16|u16|s32|u32|s64|u64|"
                       r"f8e4m3fn|f8e5m2|bf16|f16|f32|f64|c64|c128)"
                       r"\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-op-kind operand bytes of every collective in an HLO module."""
    out: Dict[str, float] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        kind = m.group(1)
        # operand shapes: every dtype[shape] token AFTER the op name
        tail = line[m.end():]
        # stop at metadata junk: operands live before `)` + attributes;
        # attribute regions (replica_groups etc.) contain no dtype[...] tokens
        total = sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(tail))
        out[kind] = out.get(kind, 0.0) + float(total)
    out["total"] = float(sum(v for k, v in out.items() if k != "total"))
    return out


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_device: float
    bytes_per_device: float
    coll_bytes_per_device: float
    t_compute: float
    t_memory: float
    t_collective: float
    bottleneck: str
    model_flops: float            # 6*N*D (active) per step, global
    useful_ratio: float           # model_flops / (flops_per_device*chips)
    peak_fraction: float          # t_compute / max(t_*) — roofline fraction
    argument_bytes: int = 0
    temp_bytes: int = 0
    output_bytes: int = 0

    def asdict(self):
        return dataclasses.asdict(self)


def analyze(
    *, arch: str, shape: str, mesh_name: str, chips: int,
    cost: Dict[str, float], coll: Dict[str, float],
    model_flops: float, device_kind: str,
    memstats: Optional[dict] = None,
) -> Roofline:
    peak = peaks(device_kind)
    flops = float(cost.get("flops", 0.0))
    mem_bytes = float(cost.get("bytes accessed", 0.0))
    cb = float(coll.get("total", 0.0))
    t_c = flops / peak.flops            # per-device flops / per-chip peak
    t_m = mem_bytes / peak.hbm_bw
    t_x = cb / peak.ici_bw
    terms = {"compute": t_c, "memory": t_m, "collective": t_x}
    bottleneck = max(terms, key=terms.get)
    t_max = max(t_c, t_m, t_x, 1e-30)
    global_flops = flops * chips
    r = Roofline(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        flops_per_device=flops, bytes_per_device=mem_bytes,
        coll_bytes_per_device=cb,
        t_compute=t_c, t_memory=t_m, t_collective=t_x,
        bottleneck=bottleneck,
        model_flops=model_flops,
        useful_ratio=(model_flops / global_flops) if global_flops else 0.0,
        peak_fraction=t_c / t_max,
    )
    if memstats:
        r.argument_bytes = int(memstats.get("argument_size_in_bytes", 0))
        r.temp_bytes = int(memstats.get("temp_size_in_bytes", 0))
        r.output_bytes = int(memstats.get("output_size_in_bytes", 0))
    return r


def model_flops_for(cfg, shape, n_active: int) -> float:
    """6*N_active*D per optimizer step (train) or per token batch (serve)."""
    if shape.kind == "train":
        tokens = shape.seq_len * shape.global_batch
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.seq_len * shape.global_batch
        return 2.0 * n_active * tokens       # forward only
    return 2.0 * n_active * shape.global_batch  # decode: 1 token per seq


def load_reports(path_glob: str):
    import glob
    rows = []
    for p in sorted(glob.glob(path_glob)):
        with open(p) as f:
            rows.append(json.load(f))
    return rows
