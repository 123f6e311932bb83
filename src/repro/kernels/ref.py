"""Pure-jnp oracles for every Pallas kernel in this package."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.episodes import in_window

NEG = -jnp.inf


def track_level_ref(t_prev, v_prev, t_next, t_low, t_high) -> jax.Array:
    """Quadratic masked max — independent oracle for episode_track.

    v_next[a] = max over {b : t_low < t_next[a] - t_prev[b] <= t_high}
    (exact, ``core.episodes.in_window``) of v_prev[b]; -inf when empty.
    """
    ok = in_window(t_next[:, None], t_prev[None, :], t_low, t_high)
    return jnp.max(jnp.where(ok, v_prev[None, :], NEG), axis=1)


def track_episode_ref(times_by_sym, t_low, t_high):
    """Full multi-level tracking using the quadratic oracle per level.

    Returns (starts, ends) with -inf/+inf padding, matching
    core.tracking.track_dense semantics.
    """
    n = times_by_sym.shape[0]
    t0 = times_by_sym[0]
    v = jnp.where(jnp.isfinite(t0), t0, NEG)
    for i in range(n - 1):
        v = track_level_ref(times_by_sym[i], v, times_by_sym[i + 1],
                            t_low[i], t_high[i])
        v = jnp.where(jnp.isfinite(times_by_sym[i + 1]), v, NEG)
    ends = times_by_sym[n - 1]
    valid = (v > NEG) & jnp.isfinite(ends)
    return jnp.where(valid, v, NEG), jnp.where(valid, ends, jnp.inf)


def flash_attention_ref(q, k, v, *, causal: bool = True, scale=None):
    """Plain softmax attention oracle. q,k,v: [seq, heads, dim] (q heads may
    be a multiple of kv heads — GQA)."""
    sq, hq, d = q.shape
    sk, hk, _ = k.shape
    group = hq // hk
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    kk = jnp.repeat(k, group, axis=1)
    vv = jnp.repeat(v, group, axis=1)
    logits = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32),
                        kk.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(mask[None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("hqk,khd->qhd", p, vv.astype(jnp.float32))
    return out.astype(q.dtype)


def wkv_sequential_ref(r, k, v, logw, u):
    """Sequential oracle for the WKV recurrence (kernel contract):
        S_t = diag(w_t) S_{t-1} + k_t v_t^T
        o_t = r_t^T (S_{t-1} + diag(u) k_t v_t^T)
    r/k/v/logw: [b, T, h, hd]; u: [h, hd]. Returns o [b, T, h, hd]."""
    b, t, h, hd = r.shape
    rf = r.astype(jnp.float32); kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32); w = jnp.exp(logw.astype(jnp.float32))
    uf = u.astype(jnp.float32)
    s = jnp.zeros((b, h, hd, hd), jnp.float32)
    outs = []
    for i in range(t):
        cur = s + (uf[None] * kf[:, i])[..., None] * vf[:, i][:, :, None, :]
        outs.append(jnp.einsum("bhi,bhiv->bhv", rf[:, i], cur))
        s = w[:, i][..., None] * s + kf[:, i][..., None] * vf[:, i][:, :, None, :]
    return jnp.stack(outs, axis=1)
