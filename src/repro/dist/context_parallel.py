"""Context-parallel decode attention (shard_map over the KV-sequence axis).

At long contexts the decode step is KV-cache-bandwidth-bound, so the cache is
sharded along its *sequence* dimension across the ``model`` axis; each device
attends over its local KV slice with flash-style partial-softmax statistics
(m, l, o) that are combined with one pmax + psum across the axis.  The new
token's K/V is written only by the shard whose slice contains ``cache_len``
(out-of-range writes are dropped), so the returned cache keeps the same
sharded layout it arrived with.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map

from repro.models.attention import NEG_INF, _repeat_kv, out_proj, project_qkv


def cp_decode_self_attention(params, x, k_cache, v_cache, cache_len, *,
                             cfg, mesh, axis="model", dp_spec="data"):
    """Sequence-sharded decode attention.

    x: [B,1,D]; caches: [B,Smax,Hk,hd] sharded P(dp_spec, axis, None, None);
    ``cache_len`` scalar or [B].  Returns (out [B,1,D], new_k, new_v) with the
    caches still sequence-sharded.
    """
    b, s_max = x.shape[0], k_cache.shape[1]
    lens = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (b,))
    # global key positions, sharded like the cache's sequence dim: each shard
    # sees its own slice, which sidesteps axis_index math for tuple axes.
    pos = jnp.arange(s_max, dtype=jnp.int32)
    axes = axis if isinstance(axis, tuple) else (axis,)

    kv_spec = P(dp_spec, axis, None, None)
    bat_spec = P(dp_spec)

    def body(params, x, kc, vc, lens, pos):
        b_l, s_l = kc.shape[0], kc.shape[1]
        q, k_new, v_new = project_qkv(params, x, cfg=cfg, positions=lens[:, None])
        # scatter the new K/V into whichever shard owns position ``lens``
        local = lens - pos[0]
        safe = jnp.where((local >= 0) & (local < s_l), local, s_l)  # s_l -> dropped
        bidx = jnp.arange(b_l)
        kc = kc.at[bidx, safe].set(k_new[:, 0].astype(kc.dtype), mode="drop")
        vc = vc.at[bidx, safe].set(v_new[:, 0].astype(vc.dtype), mode="drop")

        k_valid = pos[None, :] <= lens[:, None]
        if cfg.sliding_window:
            k_valid = k_valid & (lens[:, None] - pos[None, :] < cfg.sliding_window)

        h = q.shape[2]
        k_full = _repeat_kv(kc, h)
        v_full = _repeat_kv(vc, h)
        scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
        scores = jnp.einsum("bqhd,bshd->bhqs", q, k_full,
                            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(k_valid[:, None, None, :], scores, NEG_INF)

        m_loc = jnp.max(scores, axis=-1)                       # [b,h,1]
        m = jax.lax.pmax(m_loc, axes)
        p = jnp.exp(scores - m[..., None])
        l = jax.lax.psum(jnp.sum(p, axis=-1), axes)            # [b,h,1]
        o = jax.lax.psum(jnp.einsum("bhqs,bshd->bqhd", p.astype(v_full.dtype),
                                    v_full), axes)             # [b,1,h,hd]
        out = o / jnp.maximum(l.transpose(0, 2, 1)[..., None], 1e-30)
        return out.astype(x.dtype), kc, vc

    attn, kc, vc = shard_map(
        body, mesh=mesh,
        in_specs=(P(), bat_spec, kv_spec, kv_spec, bat_spec, P(axis)),
        out_specs=(bat_spec, kv_spec, kv_spec),
        check_vma=False)(params, x, k_cache, v_cache, lens, pos)
    return out_proj(params, attn), kc, vc
