"""Compile the Pallas kernels and plan builders for a described TPU v5e.

Nothing runs: each test lowers and compiles for one chip of a ``v5e:2x2``
topology that JAX describes without the hardware, so Mosaic and XLA:TPU
refuse here what they would refuse on the chip (unaligned blocks,
unsupported primitives, VMEM overruns). Shapes: the widths
``chip_smoke.py`` runs, every bucket of ``kernels/tuned_configs.json`` at
the tiles the chip would be served, and one ``count_indexed`` plan per
engine.

The topology is described inside a module fixture (never at import), and
only the compile tests depend on it, so the TPU library is loaded only by
the worker that runs this file.
"""
import json

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import plan as plan_mod
from repro.core import tracking
from repro.kernels import autotune, ops

SMOKE_CAP = 8192                 # dataset 5: ~4.4k spikes per neuron
#: (batch, episode length) of the smoke's fused launches: the 64x64 level-2
#: join, then the narrow deeper frontiers
SMOKE_COUNT = [(4096, 2), (16, 3), (16, 4), (32, 5)]


def _tuned_buckets():
    with open(autotune._CONFIG_PATH) as f:
        keys = json.load(f)["configs"]
    out = []
    for key in sorted(keys):
        kind, lv, n, b = key.split(":")
        out.append((kind, int(lv[1:]), int(n[1:]), int(b[1:])))
    return out


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    # compiles for a described chip are written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_count(sharding, batch, n, cap, tiles):
    def f(times, lo, hi, pe, pc):
        return ops.count_batch(
            times, lo, hi, pe, pc, block_next=tiles.block_next,
            block_prev=tiles.block_prev, window_tiles=tiles.window_tiles,
            chunk=tiles.chunk, interpret=False)
    return jax.jit(f).lower(
        _spec(sharding, (batch, n, cap)), _spec(sharding, (batch, n - 1)),
        _spec(sharding, (batch, n - 1)), _spec(sharding, (batch,)),
        _spec(sharding, (batch,), jnp.int32)).compile()


def _compile_track(sharding, batch, n, cap, tiles):
    def f(times, lo, hi):
        return ops.track_batch(
            times, lo, hi, block_next=tiles.block_next,
            block_prev=tiles.block_prev, window_tiles=tiles.window_tiles,
            interpret=False)
    return jax.jit(f).lower(
        _spec(sharding, (batch, n, cap)), _spec(sharding, (batch, n - 1)),
        _spec(sharding, (batch, n - 1))).compile()


def _chip_tiles(kind, levels, cap, batch):
    return autotune.resolve(kind, levels, cap, batch, backend="tpu")


def _assert_mosaic(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("batch,n", SMOKE_COUNT)
def test_count_kernel_compiles_at_smoke_widths(one_chip, batch, n):
    tiles = _chip_tiles("count", n - 1, SMOKE_CAP, batch)
    _assert_mosaic(_compile_count(one_chip, batch, n, SMOKE_CAP, tiles))


@pytest.mark.parametrize("batch,n,cap", [(4096, 2, 7424), (16, 5, 7424),
                                         (64, 3, 300)])
def test_track_kernel_compiles(one_chip, batch, n, cap):
    """The sharded miner's per-shard tracking (cap = shard + halo, not a
    tile multiple: the wrapper pads it)."""
    tiles = _chip_tiles("track", n - 1, cap, batch)
    _assert_mosaic(_compile_track(one_chip, batch, n, cap, tiles))


def test_track_level_kernel_compiles(one_chip):
    def f(tp, vp, tn):
        return ops.track_level(tp, vp, tn, 0.0, 0.01, interpret=False)
    specs = [_spec(one_chip, (SMOKE_CAP,))] * 3
    _assert_mosaic(jax.jit(f).lower(*specs).compile())


@pytest.mark.parametrize("kind,levels,cap,batch", _tuned_buckets())
def test_tuned_bucket_compiles_with_chip_tiles(one_chip, kind, levels, cap,
                                               batch):
    tiles = _chip_tiles(kind, levels, cap, batch)
    compile_fn = _compile_count if kind == "count" else _compile_track
    _assert_mosaic(compile_fn(one_chip, batch, levels + 1, cap, tiles))


@pytest.mark.parametrize("engine", tracking.engine_names())
def test_count_indexed_plan_compiles_per_engine(one_chip, engine):
    p = plan_mod.plan_for("count_indexed", level=3, n_types=64,
                          cap=SMOKE_CAP, batch=64, engine=engine,
                          interpret=False)
    entry = plan_mod._fn_entry(p.fn)
    specs = [_spec(one_chip, s.shape, s.dtype) for s in entry.specs(p)]
    compiled = jax.jit(entry.build(p)).lower(*specs).compile()
    if engine.startswith("dense_pallas"):
        _assert_mosaic(compiled)


def test_unaligned_tiles_raise_before_mosaic():
    with pytest.raises(ValueError, match="multiples of 128"):
        ops.tile_geometry(1024, 8, 16, interpret=False)
    assert ops.tile_geometry(1000, 8, 16, interpret=True) == (8, 16, 1008)


def test_vmem_over_budget_raises_before_mosaic():
    with pytest.raises(ValueError, match="VMEM"):
        ops._vmem_limit(ops.VMEM_BUDGET_BYTES + 1, interpret=False)
