"""Zamba2-style hybrid: Mamba2 backbone + one *shared* attention block applied
every ``cfg.attn_every`` layers (arXiv:2411.15242).

Simplifications vs the released checkpoints (noted in DESIGN.md):
  * the shared block's "concatenated original embedding" skip is realized as a
    learned projection of the token embedding added to the block input
    (keeps width d instead of 2d),
  * per-application LoRA deltas on the shared block are omitted (pure sharing).

Depth layout for L layers, every=k:  G = L // k groups of (k mamba layers +
1 shared-attn application), then L - G*k trailing mamba layers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.common import ParamSpec, SpecTree
from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import layers as L
from repro.models import ssm
from repro.models.transformer import _group_tree, _maybe_remat


def _layout(cfg: ModelConfig):
    g = cfg.num_layers // cfg.attn_every
    return {"groups": g, "per_group": cfg.attn_every,
            "tail": cfg.num_layers - g * cfg.attn_every}


def _mamba_block_specs(cfg: ModelConfig) -> dict:
    specs = {("norm",) + p: s for p, s in L.rmsnorm_spec(cfg.d_model).items()}
    specs.update({("mixer",) + p: s for p, s in ssm.mamba2_spec(cfg).items()})
    return specs


def _shared_attn_specs(cfg: ModelConfig) -> dict:
    specs: dict = {}
    specs.update({("attn",) + p: s for p, s in attn.attention_spec(cfg).items()})
    specs.update({("attn_norm",) + p: s for p, s in L.rmsnorm_spec(cfg.d_model).items()})
    specs.update({("ffn_norm",) + p: s for p, s in L.rmsnorm_spec(cfg.d_model).items()})
    specs.update({("ffn",) + p: s for p, s in L.swiglu_spec(cfg.d_model, cfg.d_ff).items()})
    specs[("skip_proj",)] = ParamSpec((cfg.d_model, cfg.d_model), ("embed_in", "embed_out"), init="scaled")
    return specs


def param_specs(cfg: ModelConfig) -> SpecTree:
    lay = _layout(cfg)
    specs: SpecTree = {}
    specs.update({("embed",) + p: s for p, s in L.embed_spec(cfg.vocab_size, cfg.d_model).items()})
    from repro.models.transformer import _stack
    specs.update(_stack(_mamba_block_specs(cfg), lay["groups"] * lay["per_group"], "mamba_layers"))
    if lay["tail"]:
        specs.update(_stack(_mamba_block_specs(cfg), lay["tail"], "tail_layers"))
    specs.update({("shared",) + p: s for p, s in _shared_attn_specs(cfg).items()})
    specs.update({("final_norm",) + p: s for p, s in L.rmsnorm_spec(cfg.d_model).items()})
    specs.update({("out",) + p: s
                  for p, s in L.unembed_spec(cfg.vocab_size, cfg.d_model, tied=cfg.tie_embeddings).items()})
    return specs


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def _mamba_block_seq(lp, x, *, cfg, state=None, return_state=False):
    from repro.dist.sharding import shard_activation
    x = shard_activation(x, ("batch", None, None))  # keep batch on dp axes
    h = L.rmsnorm(lp["norm"], x, cfg.norm_eps)
    if return_state:
        y, st = ssm.mamba2_forward(lp["mixer"], h, cfg=cfg, state=state, return_state=True)
        return x + y, st
    return x + ssm.mamba2_forward(lp["mixer"], h, cfg=cfg), None


def _shared_attn_seq(sp, x, x0, *, cfg):
    """Shared transformer block; x0 is the original token embedding (skip)."""
    from repro.dist.sharding import shard_activation
    x = shard_activation(x, ("batch", None, None))
    h_in = x + jnp.einsum("bsd,de->bse", x0, sp["skip_proj"])
    h = L.rmsnorm(sp["attn_norm"], h_in, cfg.norm_eps)
    a, kv = attn.self_attention(sp["attn"], h, cfg=cfg)
    x = x + a
    h = L.rmsnorm(sp["ffn_norm"], x, cfg.norm_eps)
    return x + L.swiglu(sp["ffn"], h), kv


def _shared_attn_decode(sp, x, x0, k_cache, v_cache, cache_len, *, cfg):
    h_in = x + jnp.einsum("bsd,de->bse", x0, sp["skip_proj"])
    h = L.rmsnorm(sp["attn_norm"], h_in, cfg.norm_eps)
    a, k_cache, v_cache = attn.decode_self_attention(sp["attn"], h, k_cache, v_cache, cache_len, cfg=cfg)
    x = x + a
    h = L.rmsnorm(sp["ffn_norm"], x, cfg.norm_eps)
    return x + L.swiglu(sp["ffn"], h), k_cache, v_cache


# ---------------------------------------------------------------------------
# Forward / prefill / decode
# ---------------------------------------------------------------------------


def _run_seq(params, x, *, cfg: ModelConfig, remat: bool, collect_state: bool):
    lay = _layout(cfg)
    x0 = x
    mb = _maybe_remat(functools.partial(_mamba_block_seq, cfg=cfg, return_state=collect_state), cfg, remat)
    groups = _group_tree(params["mamba_layers"], lay["groups"])
    kv_caches = []
    states: dict = {}

    def inner(x, lp):
        x, st = mb(lp, x)
        return x, st

    def group(x, gp):
        x, sts = jax.lax.scan(inner, x, gp)
        x, kv = _shared_attn_seq(params["shared"], x, x0, cfg=cfg)
        # only stack ys that are consumed — unused scan outputs still
        # materialize [G, ...] buffers in the compiled loop
        return x, ((sts, kv) if collect_state else None)

    x, ys = jax.lax.scan(group, x, groups)
    if collect_state:
        sts, kvs = ys
        states["mamba"] = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), sts)
        kv_caches = kvs  # stacked over groups: [G,B,S,hk,hd]
    if lay["tail"]:
        x, tail_sts = jax.lax.scan(inner, x, params["tail_layers"])
        if collect_state:
            states["tail"] = tail_sts
    return x, states, kv_caches


def forward(params, tokens, *, cfg: ModelConfig, extra=None, remat=False, last=None):
    x = L.embed(params["embed"], tokens).astype(cfg.activation_dtype)
    x, _, _ = _run_seq(params, x, cfg=cfg, remat=remat, collect_state=False)
    if last is not None:
        x = L.take_positions(x, last)
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.unembed({**params.get("out", {}), **params["embed"]}, x, tied=cfg.tie_embeddings)
    return logits, {}


def cache_specs(cfg: ModelConfig, batch: int, max_seq: int) -> SpecTree:
    lay = _layout(cfg)
    specs: SpecTree = {}
    for path, s in ssm.mamba2_state_specs(cfg, batch).items():
        n = lay["groups"] * lay["per_group"]
        specs[("mamba",) + path] = ParamSpec((n,) + s.shape, ("layers",) + s.axes, dtype=s.dtype, init="zeros")
        if lay["tail"]:
            specs[("tail",) + path] = ParamSpec((lay["tail"],) + s.shape, ("layers",) + s.axes,
                                                dtype=s.dtype, init="zeros")
    kv_axes = ("layers", "batch", "kv_seq", "kv_heads", "qkv")
    shp = (lay["groups"], batch, max_seq, cfg.num_kv_heads, cfg.hd)
    specs[("attn", "k")] = ParamSpec(shp, kv_axes, dtype=jnp.dtype(cfg.dtype), init="zeros")
    specs[("attn", "v")] = ParamSpec(shp, kv_axes, dtype=jnp.dtype(cfg.dtype), init="zeros")
    return specs


def prefill(params, tokens, cache, *, cfg: ModelConfig, extra=None, last_only=False):
    x = L.embed(params["embed"], tokens).astype(cfg.activation_dtype)
    x, states, kvs = _run_seq(params, x, cfg=cfg, remat=False, collect_state=True)
    from repro.models.transformer import _write_prefill
    new_cache = {
        "mamba": states["mamba"],
        "attn": {"k": _write_prefill(cache["attn"]["k"], kvs[0]),
                 "v": _write_prefill(cache["attn"]["v"], kvs[1])},
    }
    if "tail" in states:
        new_cache["tail"] = states["tail"]
    if last_only:
        x = x[:, -1:]
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.unembed({**params.get("out", {}), **params["embed"]}, x, tied=cfg.tie_embeddings)
    return logits, new_cache


def decode_step(params, tokens, cache, cache_len, *, cfg: ModelConfig, extra=None):
    lay = _layout(cfg)
    x = L.embed(params["embed"], tokens).astype(cfg.activation_dtype)
    x0 = x
    groups = _group_tree(params["mamba_layers"], lay["groups"])
    mstate = _group_tree(cache["mamba"], lay["groups"])

    def inner(x, inp):
        lp, st = inp
        h = L.rmsnorm(lp["norm"], x, cfg.norm_eps)
        st, y = ssm.mamba2_decode(lp["mixer"], st, h, cfg=cfg)
        return x + y, st

    def group(x, inp):
        gp, gst, kc, vc = inp
        x, sts = jax.lax.scan(inner, x, (gp, gst))
        x, kc, vc = _shared_attn_decode(params["shared"], x, x0, kc, vc, cache_len, cfg=cfg)
        return x, (sts, kc, vc)

    x, (msts, ks, vs) = jax.lax.scan(group, x, (groups, mstate, cache["attn"]["k"], cache["attn"]["v"]))
    new_cache = {
        "mamba": jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), msts),
        "attn": {"k": ks, "v": vs},
    }
    if lay["tail"]:
        x, tsts = jax.lax.scan(inner, x, (params["tail_layers"], cache["tail"]))
        new_cache["tail"] = tsts
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.unembed({**params.get("out", {}), **params["embed"]}, x, tied=cfg.tie_embeddings)
    return logits, new_cache
