"""Unified decoder-only transformer LM (dense / MoE / VLM families).

Parameters are *stacked over layers* and iterated with ``jax.lax.scan`` so the
HLO (and compile time) is O(1) in depth.  Heterogeneous depth patterns are
expressed as *grouped* scans:

  * MoE with ``moe_interval=k``: scan over groups of (k-1 dense + 1 MoE) layers
  * VLM with ``cross_attn_interval=k``: scan over groups of (1 gated
    cross-attention block + k self-attention layers)

Three entry points share the layer body:
  forward      (train / scoring: full sequence -> logits, aux losses)
  prefill      (full sequence -> logits + filled KV cache)
  decode_step  (1 token + cache -> logits + updated cache)
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.common import ParamSpec, SpecTree
from repro.configs.base import ModelConfig
from repro.dist.sharding import shard_activation
from repro.models import attention as attn
from repro.models import layers as L
from repro.models import moe as moe_mod


# ---------------------------------------------------------------------------
# Param specs
# ---------------------------------------------------------------------------


def _stack(specs: dict, n: int, prefix: str) -> SpecTree:
    out = {}
    for path, s in specs.items():
        out[(prefix,) + path] = ParamSpec((n,) + s.shape, ("layers",) + s.axes,
                                          dtype=s.dtype, init=s.init, init_scale=s.init_scale)
    return out


def _decoder_layer_specs(cfg: ModelConfig, *, use_moe: bool) -> dict:
    specs: dict = {}
    for p, s in attn.attention_spec(cfg).items():
        specs[("attn",) + p] = s
    for p, s in L.rmsnorm_spec(cfg.d_model).items():
        specs[("attn_norm",) + p] = s
        specs[("ffn_norm",) + p] = s
    if use_moe:
        for p, s in moe_mod.moe_spec(cfg).items():
            specs[("moe",) + p] = s
        if cfg.moe_shared_expert:
            for p, s in L.swiglu_spec(cfg.d_model, cfg.d_ff).items():
                specs[("shared",) + p] = s
    else:
        for p, s in L.swiglu_spec(cfg.d_model, cfg.d_ff).items():
            specs[("ffn",) + p] = s
    return specs


def _cross_layer_specs(cfg: ModelConfig) -> dict:
    specs: dict = {}
    for p, s in attn.attention_spec(cfg, cross=True).items():
        specs[("xattn",) + p] = s
    for p, s in L.rmsnorm_spec(cfg.d_model).items():
        specs[("xattn_norm",) + p] = s
        specs[("xffn_norm",) + p] = s
    for p, s in L.swiglu_spec(cfg.d_model, cfg.d_ff).items():
        specs[("xffn",) + p] = s
    specs[("attn_gate",)] = ParamSpec((), (), init="zeros", dtype=jnp.float32)
    specs[("ffn_gate",)] = ParamSpec((), (), init="zeros", dtype=jnp.float32)
    return specs


def layer_layout(cfg: ModelConfig) -> dict:
    """How the depth dimension is organized into scanned stacks."""
    if cfg.family == "vlm" and cfg.cross_attn_interval:
        n_groups = cfg.num_layers // cfg.cross_attn_interval
        return {"kind": "vlm", "groups": n_groups, "per_group": cfg.cross_attn_interval,
                "dense": cfg.num_layers, "cross": n_groups}
    if cfg.is_moe and cfg.moe_interval > 1:
        n_groups = cfg.num_layers // cfg.moe_interval
        return {"kind": "moe_interleave", "groups": n_groups,
                "dense_per_group": cfg.moe_interval - 1,
                "dense": n_groups * (cfg.moe_interval - 1), "moe": n_groups}
    if cfg.is_moe:
        return {"kind": "moe", "moe": cfg.num_layers, "dense": 0}
    return {"kind": "dense", "dense": cfg.num_layers}


def param_specs(cfg: ModelConfig) -> SpecTree:
    lay = layer_layout(cfg)
    specs: SpecTree = {}
    specs.update({("embed",) + p: s for p, s in L.embed_spec(cfg.vocab_size, cfg.d_model).items()})
    if lay["kind"] == "moe":
        specs.update(_stack(_decoder_layer_specs(cfg, use_moe=True), lay["moe"], "layers"))
    else:
        if lay.get("dense"):
            specs.update(_stack(_decoder_layer_specs(cfg, use_moe=False), lay["dense"], "layers"))
        if lay["kind"] == "moe_interleave":
            specs.update(_stack(_decoder_layer_specs(cfg, use_moe=True), lay["moe"], "moe_layers"))
        if lay["kind"] == "vlm":
            specs.update(_stack(_cross_layer_specs(cfg), lay["cross"], "cross_layers"))
    specs.update({("final_norm",) + p: s for p, s in L.rmsnorm_spec(cfg.d_model).items()})
    specs.update({("out",) + p: s for p, s in L.unembed_spec(cfg.vocab_size, cfg.d_model, tied=cfg.tie_embeddings).items()})
    return specs


# ---------------------------------------------------------------------------
# Layer bodies
# ---------------------------------------------------------------------------


def _decoder_layer_seq(lp, x, *, cfg: ModelConfig, use_moe: bool):
    """Full-sequence decoder layer. Returns (x, (k, v), aux)."""
    x = shard_activation(x, ("batch", "seq_act", "embed_act"))
    h = L.rmsnorm(lp["attn_norm"], x, cfg.norm_eps)
    a, kv = attn.self_attention(lp["attn"], h, cfg=cfg)
    x = x + a
    h = L.rmsnorm(lp["ffn_norm"], x, cfg.norm_eps)
    aux = {}
    if use_moe:
        f, aux = moe_mod.moe_ffn(lp["moe"], h, cfg=cfg)
        if cfg.moe_shared_expert:
            f = f + L.swiglu(lp["shared"], h)
    else:
        f = L.swiglu(lp["ffn"], h)
    return x + f, kv, aux


def _decoder_layer_decode(lp, x, k_cache, v_cache, cache_len, *, cfg: ModelConfig, use_moe: bool):
    h = L.rmsnorm(lp["attn_norm"], x, cfg.norm_eps)
    a, k_cache, v_cache = attn.decode_self_attention(lp["attn"], h, k_cache, v_cache, cache_len, cfg=cfg)
    x = x + a
    h = L.rmsnorm(lp["ffn_norm"], x, cfg.norm_eps)
    if use_moe:
        f, _ = moe_mod.moe_ffn(lp["moe"], h, cfg=cfg)
        if cfg.moe_shared_expert:
            f = f + L.swiglu(lp["shared"], h)
    else:
        f = L.swiglu(lp["ffn"], h)
    return x + f, k_cache, v_cache


def _cross_block_seq(cp, x, mem, *, cfg: ModelConfig):
    h = L.rmsnorm(cp["xattn_norm"], x, cfg.norm_eps)
    a = attn.cross_attention(cp["xattn"], h, mem, cfg=cfg)
    x = x + jnp.tanh(cp["attn_gate"]).astype(x.dtype) * a
    h = L.rmsnorm(cp["xffn_norm"], x, cfg.norm_eps)
    f = L.swiglu(cp["xffn"], h)
    return x + jnp.tanh(cp["ffn_gate"]).astype(x.dtype) * f


def _cross_block_decode(cp, x, k_mem, v_mem, *, cfg: ModelConfig):
    h = L.rmsnorm(cp["xattn_norm"], x, cfg.norm_eps)
    a = attn.decode_cross_attention(cp["xattn"], h, k_mem, v_mem, cfg=cfg)
    x = x + jnp.tanh(cp["attn_gate"]).astype(x.dtype) * a
    h = L.rmsnorm(cp["xffn_norm"], x, cfg.norm_eps)
    f = L.swiglu(cp["xffn"], h)
    return x + jnp.tanh(cp["ffn_gate"]).astype(x.dtype) * f


def _maybe_remat(fn, cfg: ModelConfig, enable: bool):
    if enable and cfg.remat:
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
    return fn


def _add_aux(acc, aux):
    return {k: acc.get(k, 0.0) + v for k, v in aux.items()} if aux else acc


def _group_tree(tree, n_groups: int):
    return jax.tree.map(lambda a: a.reshape((n_groups, a.shape[0] // n_groups) + a.shape[1:]), tree)


# ---------------------------------------------------------------------------
# Full-sequence pass (train / prefill)
# ---------------------------------------------------------------------------


def _run_layers_seq(params, x, *, cfg: ModelConfig, extra, remat: bool, collect_kv: bool):
    """Returns (x, kv_stacks: dict[str, (k, v)] or None, aux)."""
    lay = layer_layout(cfg)
    aux0 = {"moe_lb": 0.0, "moe_z": 0.0} if cfg.is_moe else {}
    kv_out: dict[str, Any] = {}

    if lay["kind"] in ("dense", "moe"):
        use_moe = lay["kind"] == "moe"
        body_fn = _maybe_remat(
            functools.partial(_decoder_layer_seq, cfg=cfg, use_moe=use_moe), cfg, remat)

        def body(carry, lp):
            x, aux = carry
            x, kv, a = body_fn(lp, x)
            return (x, _add_aux(aux, a)), kv if collect_kv else None

        (x, aux), kvs = jax.lax.scan(body, (x, aux0), params["layers"])
        if collect_kv:
            kv_out["self"] = kvs

    elif lay["kind"] == "moe_interleave":
        dense_fn = _maybe_remat(functools.partial(_decoder_layer_seq, cfg=cfg, use_moe=False), cfg, remat)
        moe_fn = _maybe_remat(functools.partial(_decoder_layer_seq, cfg=cfg, use_moe=True), cfg, remat)
        dense_groups = _group_tree(params["layers"], lay["groups"])

        def group(carry, gp):
            x, aux = carry
            dense_p, moe_p = gp

            def inner(c, lp):
                x, aux = c
                x, kv, a = dense_fn(lp, x)
                return (x, _add_aux(aux, a)), kv if collect_kv else None

            (x, aux), d_kvs = jax.lax.scan(inner, (x, aux), dense_p)
            x, m_kv, a = moe_fn(moe_p, x)
            return (x, _add_aux(aux, a)), ((d_kvs, m_kv) if collect_kv else None)

        (x, aux), kvs = jax.lax.scan(group, (x, aux0), (dense_groups, params["moe_layers"]))
        if collect_kv:
            kv_out["dense"], kv_out["moe"] = kvs

    else:  # vlm
        mem = extra["image_embeds"]
        self_fn = _maybe_remat(functools.partial(_decoder_layer_seq, cfg=cfg, use_moe=False), cfg, remat)
        cross_fn = _maybe_remat(functools.partial(_cross_block_seq, cfg=cfg), cfg, remat)
        groups = _group_tree(params["layers"], lay["groups"])

        def group(carry, gp):
            x, aux = carry
            cross_p, self_p = gp
            x = cross_fn(cross_p, x, mem)

            def inner(c, lp):
                x, aux = c
                x, kv, a = self_fn(lp, x)
                return (x, _add_aux(aux, a)), kv if collect_kv else None

            (x, aux), kvs = jax.lax.scan(inner, (x, aux), self_p)
            return (x, aux), kvs

        (x, aux), kvs = jax.lax.scan(group, (x, aux0), (params["cross_layers"], groups))
        if collect_kv:
            kv_out["self"] = jax.tree.map(
                lambda a: a.reshape((lay["dense"],) + a.shape[2:]), kvs)
            # precompute cross K/V once per cross layer for decode
            def xkv(cp):
                k = jnp.einsum("bsd,dhk->bshk", mem, cp["xattn"]["wk"])
                v = jnp.einsum("bsd,dhk->bshk", mem, cp["xattn"]["wv"])
                return k, v
            kv_out["cross"] = jax.vmap(xkv)(params["cross_layers"])
        aux = dict(aux)

    return x, (kv_out if collect_kv else None), aux


def forward(params, tokens, *, cfg: ModelConfig, extra=None, remat=False, last=None):
    """tokens [B,S] -> (logits [B,S,V] f32, aux dict); with ``last`` [B]
    only those positions are unembedded -> logits [B,1,V]."""
    x = L.embed(params["embed"], tokens).astype(cfg.activation_dtype)
    x, _, aux = _run_layers_seq(params, x, cfg=cfg, extra=extra, remat=remat, collect_kv=False)
    if last is not None:
        x = L.take_positions(x, last)
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.unembed({**params.get("out", {}), **params["embed"]}, x, tied=cfg.tie_embeddings)
    return logits, aux


# ---------------------------------------------------------------------------
# KV cache structure + prefill / decode
# ---------------------------------------------------------------------------


def cache_specs(cfg: ModelConfig, batch: int, max_seq: int) -> SpecTree:
    lay = layer_layout(cfg)
    hk, hd = cfg.num_kv_heads, cfg.hd
    dt = jnp.dtype(cfg.dtype)
    kv_axes = ("layers", "batch", "kv_seq", "kv_heads", "qkv")

    def kv(n_layers, seq):
        return ParamSpec((n_layers, batch, seq, hk, hd), kv_axes, dtype=dt, init="zeros")

    specs: SpecTree = {}
    if lay["kind"] in ("dense", "moe"):
        n = lay.get("dense") or lay.get("moe")
        specs[("self", "k")] = kv(n, max_seq)
        specs[("self", "v")] = kv(n, max_seq)
    elif lay["kind"] == "moe_interleave":
        specs[("dense", "k")] = kv(lay["groups"] * lay["dense_per_group"], max_seq)
        specs[("dense", "v")] = kv(lay["groups"] * lay["dense_per_group"], max_seq)
        specs[("moe", "k")] = kv(lay["groups"], max_seq)
        specs[("moe", "v")] = kv(lay["groups"], max_seq)
    else:  # vlm
        specs[("self", "k")] = kv(lay["dense"], max_seq)
        specs[("self", "v")] = kv(lay["dense"], max_seq)
        specs[("cross", "k")] = kv(lay["cross"], cfg.num_image_tokens)
        specs[("cross", "v")] = kv(lay["cross"], cfg.num_image_tokens)
    return specs


def _write_prefill(cache_buf, kv_new):
    """Place freshly computed [L,B,S,hk,hd] K/V at the head of a [L,B,Smax,...] buffer."""
    return jax.lax.dynamic_update_slice_in_dim(cache_buf, kv_new.astype(cache_buf.dtype), 0, axis=2)


def prefill(params, tokens, cache, *, cfg: ModelConfig, extra=None, last_only=False):
    """tokens [B,S] + zeroed cache -> (logits, filled cache).

    ``last_only`` computes the unembedding for the final position only (the
    serving path — avoids materializing a [B,S,V] logits tensor at 32k)."""
    x = L.embed(params["embed"], tokens).astype(cfg.activation_dtype)
    x, kvs, _ = _run_layers_seq(params, x, cfg=cfg, extra=extra, remat=False, collect_kv=True)
    lay = layer_layout(cfg)
    new_cache = dict(cache)
    if lay["kind"] == "moe_interleave":
        d_kvs, m_kv = kvs["dense"], kvs["moe"]
        dk = d_kvs[0].reshape((-1,) + d_kvs[0].shape[2:])
        dv = d_kvs[1].reshape((-1,) + d_kvs[1].shape[2:])
        new_cache["dense"] = {"k": _write_prefill(cache["dense"]["k"], dk),
                              "v": _write_prefill(cache["dense"]["v"], dv)}
        new_cache["moe"] = {"k": _write_prefill(cache["moe"]["k"], m_kv[0]),
                            "v": _write_prefill(cache["moe"]["v"], m_kv[1])}
    else:
        k, v = kvs["self"]
        new_cache["self"] = {"k": _write_prefill(cache["self"]["k"], k),
                             "v": _write_prefill(cache["self"]["v"], v)}
        if lay["kind"] == "vlm":
            xk, xv = kvs["cross"]
            new_cache["cross"] = {"k": xk.astype(cache["cross"]["k"].dtype),
                                  "v": xv.astype(cache["cross"]["v"].dtype)}
    if last_only:
        x = x[:, -1:]
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.unembed({**params.get("out", {}), **params["embed"]}, x, tied=cfg.tie_embeddings)
    return logits, new_cache


def decode_step(params, tokens, cache, cache_len, *, cfg: ModelConfig, extra=None):
    """tokens [B,1] + cache + cache_len -> (logits [B,1,V], updated cache)."""
    lay = layer_layout(cfg)
    x = L.embed(params["embed"], tokens).astype(cfg.activation_dtype)
    new_cache = dict(cache)

    if lay["kind"] in ("dense", "moe"):
        use_moe = lay["kind"] == "moe"

        def body(x, inp):
            lp, kc, vc = inp
            x, kc, vc = _decoder_layer_decode(lp, x, kc, vc, cache_len, cfg=cfg, use_moe=use_moe)
            return x, (kc, vc)

        x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], cache["self"]["k"], cache["self"]["v"]))
        new_cache["self"] = {"k": ks, "v": vs}

    elif lay["kind"] == "moe_interleave":
        g = lay["groups"]
        dense_groups = _group_tree(params["layers"], g)
        dkc = _group_tree(cache["dense"]["k"], g)
        dvc = _group_tree(cache["dense"]["v"], g)

        def group(x, inp):
            dense_p, moe_p, dkc, dvc, mkc, mvc = inp

            def inner(x, i):
                lp, kc, vc = i
                x, kc, vc = _decoder_layer_decode(lp, x, kc, vc, cache_len, cfg=cfg, use_moe=False)
                return x, (kc, vc)

            x, (dks, dvs) = jax.lax.scan(inner, x, (dense_p, dkc, dvc))
            x, mks, mvs = _decoder_layer_decode(moe_p, x, mkc, mvc, cache_len, cfg=cfg, use_moe=True)
            return x, (dks, dvs, mks, mvs)

        x, (dks, dvs, mks, mvs) = jax.lax.scan(
            group, x, (dense_groups, params["moe_layers"], dkc, dvc, cache["moe"]["k"], cache["moe"]["v"]))
        new_cache["dense"] = {"k": dks.reshape(cache["dense"]["k"].shape),
                              "v": dvs.reshape(cache["dense"]["v"].shape)}
        new_cache["moe"] = {"k": mks, "v": mvs}

    else:  # vlm
        g = lay["groups"]
        groups = _group_tree(params["layers"], g)
        kc = _group_tree(cache["self"]["k"], g)
        vc = _group_tree(cache["self"]["v"], g)

        def group(x, inp):
            cross_p, self_p, kc, vc, xk, xv = inp
            x = _cross_block_decode(cross_p, x, xk, xv, cfg=cfg)

            def inner(x, i):
                lp, k1, v1 = i
                x, k1, v1 = _decoder_layer_decode(lp, x, k1, v1, cache_len, cfg=cfg, use_moe=False)
                return x, (k1, v1)

            x, (ks, vs) = jax.lax.scan(inner, x, (self_p, kc, vc))
            return x, (ks, vs)

        x, (ks, vs) = jax.lax.scan(
            group, x, (params["cross_layers"], groups, kc, vc, cache["cross"]["k"], cache["cross"]["v"]))
        new_cache["self"] = {"k": ks.reshape(cache["self"]["k"].shape),
                             "v": vs.reshape(cache["self"]["v"].shape)}

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.unembed({**params.get("out", {}), **params["embed"]}, x, tied=cfg.tie_embeddings)
    return logits, new_cache
