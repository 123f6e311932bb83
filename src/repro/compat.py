"""One spelling for the shard_map calls the miner makes (JAX 0.9)."""
from __future__ import annotations

import jax

shard_map = jax.shard_map


def shard_map_unchecked(f, *, mesh, in_specs, out_specs):
    """``shard_map`` with the varying-manual-axes checker off.

    Needed when the body contains a ``pallas_call``: the checker has no
    rule for it.
    """
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def pcast_varying(x, axes):
    """Mark ``x`` as varying over manual ``axes`` inside shard_map (scan
    carries must be typed varying)."""
    return jax.lax.pcast(x, axes, to="varying")


__all__ = ["shard_map", "shard_map_unchecked", "pcast_varying"]
