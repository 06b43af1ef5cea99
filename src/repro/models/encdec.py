"""Whisper-style encoder-decoder (arXiv:2212.04356).

The conv/mel frontend is a STUB per the assignment: ``input_specs`` provides
precomputed frame embeddings [B, T_frames, d].  Encoder = bidirectional
pre-LN transformer with sinusoidal positions; decoder = causal pre-LN
transformer with learned positions, cross-attending to the encoder output.
Embeddings are tied to the LM head (whisper convention).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.common import ParamSpec, SpecTree
from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import layers as L
from repro.models.transformer import _maybe_remat, _stack, _write_prefill


def _enc_layer_specs(cfg: ModelConfig) -> dict:
    specs: dict = {}
    specs.update({("attn",) + p: s for p, s in attn.attention_spec(cfg).items()})
    specs.update({("attn_norm",) + p: s for p, s in L.layernorm_spec(cfg.d_model).items()})
    specs.update({("ffn_norm",) + p: s for p, s in L.layernorm_spec(cfg.d_model).items()})
    specs.update({("ffn",) + p: s for p, s in L.gelu_ffn_spec(cfg.d_model, cfg.d_ff).items()})
    return specs


def _dec_layer_specs(cfg: ModelConfig) -> dict:
    specs = _enc_layer_specs(cfg)
    specs.update({("xattn",) + p: s for p, s in attn.attention_spec(cfg, cross=True).items()})
    specs.update({("xattn_norm",) + p: s for p, s in L.layernorm_spec(cfg.d_model).items()})
    return specs


def param_specs(cfg: ModelConfig) -> SpecTree:
    specs: SpecTree = {}
    specs.update({("embed",) + p: s for p, s in L.embed_spec(cfg.vocab_size, cfg.d_model).items()})
    specs[("pos_embed",)] = ParamSpec((cfg.max_position, cfg.d_model), ("seq", "embed"), init="normal")
    specs.update(_stack(_enc_layer_specs(cfg), cfg.encoder_layers, "enc_layers"))
    specs.update(_stack(_dec_layer_specs(cfg), cfg.num_layers, "dec_layers"))
    specs.update({("enc_norm",) + p: s for p, s in L.layernorm_spec(cfg.d_model).items()})
    specs.update({("final_norm",) + p: s for p, s in L.layernorm_spec(cfg.d_model).items()})
    return specs  # tied embeddings: no separate head


def _sinusoidal(t: int, d: int) -> jax.Array:
    pos = jnp.arange(t, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10_000.0, 2 * dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# Encoder
# ---------------------------------------------------------------------------


def encode(params, frames, *, cfg: ModelConfig, remat=False):
    """frames: [B, T, d] (stub frontend output) -> [B, T, d]."""
    x = frames + _sinusoidal(frames.shape[1], cfg.d_model).astype(frames.dtype)

    def layer(lp, x):
        from repro.dist.sharding import shard_activation
        x = shard_activation(x, ("batch", None, None))
        h = L.layernorm(lp["attn_norm"], x, cfg.norm_eps)
        a, _ = attn.self_attention(lp["attn"], h, cfg=cfg, causal=False)
        x = x + a
        h = L.layernorm(lp["ffn_norm"], x, cfg.norm_eps)
        return x + L.gelu_ffn(lp["ffn"], h)

    body = _maybe_remat(layer, cfg, remat)
    x, _ = jax.lax.scan(lambda x, lp: (body(lp, x), None), x, params["enc_layers"])
    return L.layernorm(params["enc_norm"], x, cfg.norm_eps)


# ---------------------------------------------------------------------------
# Decoder
# ---------------------------------------------------------------------------


def _dec_layer_seq(lp, x, enc_out, *, cfg: ModelConfig):
    from repro.dist.sharding import shard_activation
    x = shard_activation(x, ("batch", "seq_act", None))
    h = L.layernorm(lp["attn_norm"], x, cfg.norm_eps)
    a, kv = attn.self_attention(lp["attn"], h, cfg=cfg, causal=True)
    x = x + a
    h = L.layernorm(lp["xattn_norm"], x, cfg.norm_eps)
    x = x + attn.cross_attention(lp["xattn"], h, enc_out, cfg=cfg)
    h = L.layernorm(lp["ffn_norm"], x, cfg.norm_eps)
    return x + L.gelu_ffn(lp["ffn"], h), kv


def _decode_logits(params, x, cfg):
    x = L.layernorm(params["final_norm"], x, cfg.norm_eps)
    return L.unembed(params["embed"], x, tied=True)


def forward(params, tokens, *, cfg: ModelConfig, extra=None, remat=False, last=None):
    """Teacher-forced decoder pass. tokens [B,S]; extra['audio_frames'] [B,T,d];
    ``last`` [B] keeps only those positions' logits."""
    enc_out = encode(params, extra["audio_frames"], cfg=cfg, remat=remat)
    s = tokens.shape[1]
    x = L.embed(params["embed"], tokens).astype(cfg.activation_dtype)
    x = x + params["pos_embed"][:s].astype(x.dtype)
    body = _maybe_remat(functools.partial(_dec_layer_seq, cfg=cfg), cfg, remat)
    x, _ = jax.lax.scan(lambda x, lp: (body(lp, x, enc_out)[0], None), x, params["dec_layers"])
    if last is not None:
        x = L.take_positions(x, last)
    return _decode_logits(params, x, cfg), {}


def cache_specs(cfg: ModelConfig, batch: int, max_seq: int) -> SpecTree:
    hk, hd, n = cfg.num_kv_heads, cfg.hd, cfg.num_layers
    dt = jnp.dtype(cfg.dtype)
    kv_axes = ("layers", "batch", "kv_seq", "kv_heads", "qkv")
    x_axes = ("layers", "batch", "frames", "kv_heads", "qkv")
    return {
        ("self", "k"): ParamSpec((n, batch, max_seq, hk, hd), kv_axes, dtype=dt, init="zeros"),
        ("self", "v"): ParamSpec((n, batch, max_seq, hk, hd), kv_axes, dtype=dt, init="zeros"),
        ("cross", "k"): ParamSpec((n, batch, cfg.num_audio_frames, hk, hd), x_axes, dtype=dt, init="zeros"),
        ("cross", "v"): ParamSpec((n, batch, cfg.num_audio_frames, hk, hd), x_axes, dtype=dt, init="zeros"),
    }


def prefill(params, tokens, cache, *, cfg: ModelConfig, extra=None, last_only=False):
    enc_out = encode(params, extra["audio_frames"], cfg=cfg)
    s = tokens.shape[1]
    x = L.embed(params["embed"], tokens).astype(cfg.activation_dtype)
    x = x + params["pos_embed"][:s].astype(x.dtype)

    def body(x, lp):
        x, kv = _dec_layer_seq(lp, x, enc_out, cfg=cfg)
        return x, kv

    x, (ks, vs) = jax.lax.scan(body, x, params["dec_layers"])

    def xkv(lp):
        k = jnp.einsum("bsd,dhk->bshk", enc_out, lp["xattn"]["wk"])
        v = jnp.einsum("bsd,dhk->bshk", enc_out, lp["xattn"]["wv"])
        return k, v

    xk, xv = jax.vmap(xkv)(params["dec_layers"])
    new_cache = {
        "self": {"k": _write_prefill(cache["self"]["k"], ks),
                 "v": _write_prefill(cache["self"]["v"], vs)},
        "cross": {"k": xk.astype(cache["cross"]["k"].dtype),
                  "v": xv.astype(cache["cross"]["v"].dtype)},
    }
    if last_only:
        x = x[:, -1:]
    return _decode_logits(params, x, cfg), new_cache


def decode_step(params, tokens, cache, cache_len, *, cfg: ModelConfig, extra=None):
    x = L.embed(params["embed"], tokens).astype(cfg.activation_dtype)
    lens = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (tokens.shape[0],))
    x = x + params["pos_embed"][lens][:, None].astype(x.dtype)

    def body(x, inp):
        lp, kc, vc, xk, xv = inp
        h = L.layernorm(lp["attn_norm"], x, cfg.norm_eps)
        a, kc, vc = attn.decode_self_attention(lp["attn"], h, kc, vc, cache_len, cfg=cfg)
        x = x + a
        h = L.layernorm(lp["xattn_norm"], x, cfg.norm_eps)
        x = x + attn.decode_cross_attention(lp["xattn"], h, xk, xv, cfg=cfg)
        h = L.layernorm(lp["ffn_norm"], x, cfg.norm_eps)
        x = x + L.gelu_ffn(lp["ffn"], h)
        return x, (kc, vc)

    x, (ks, vs) = jax.lax.scan(
        body, x, (params["dec_layers"], cache["self"]["k"], cache["self"]["v"],
                  cache["cross"]["k"], cache["cross"]["v"]))
    new_cache = {"self": {"k": ks, "v": vs}, "cross": cache["cross"]}
    return _decode_logits(params, x, cfg), new_cache
