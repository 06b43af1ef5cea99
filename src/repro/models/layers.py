"""Shared neural-net building blocks (pure functions over param dicts)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.common import ParamSpec


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm_spec(d: int) -> dict:
    return {("scale",): ParamSpec((d,), ("embed",), init="ones", dtype=jnp.float32)}


def rmsnorm(params, x, eps: float = 1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * params["scale"]).astype(dtype)


def layernorm_spec(d: int) -> dict:
    return {
        ("scale",): ParamSpec((d,), ("embed",), init="ones", dtype=jnp.float32),
        ("bias",): ParamSpec((d,), ("embed",), init="zeros", dtype=jnp.float32),
    }


def layernorm(params, x, eps: float = 1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * params["scale"] + params["bias"]).astype(dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., seq, heads, head_dim]; positions: broadcastable to [..., seq]."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # [hd/2]
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., seq, hd/2]
    cos = jnp.cos(angles)[..., :, None, :]  # [..., seq, 1, hd/2]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# FFN (SwiGLU for llama-family, GELU for whisper)
# ---------------------------------------------------------------------------


def swiglu_spec(d: int, d_ff: int) -> dict:
    return {
        ("w_gate",): ParamSpec((d, d_ff), ("embed_in", "mlp_out"), init="scaled"),
        ("w_up",): ParamSpec((d, d_ff), ("embed_in", "mlp_out"), init="scaled"),
        ("w_down",): ParamSpec((d_ff, d), ("mlp", "embed_out"), init="scaled"),
    }


def swiglu(params, x):
    g = jnp.einsum("bsd,df->bsf", x, params["w_gate"])
    u = jnp.einsum("bsd,df->bsf", x, params["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("bsf,fd->bsd", h, params["w_down"])


def gelu_ffn_spec(d: int, d_ff: int) -> dict:
    return {
        ("w_in",): ParamSpec((d, d_ff), ("embed_in", "mlp_out"), init="scaled"),
        ("b_in",): ParamSpec((d_ff,), ("mlp",), init="zeros", dtype=jnp.float32),
        ("w_out",): ParamSpec((d_ff, d), ("mlp", "embed_out"), init="scaled"),
        ("b_out",): ParamSpec((d,), ("embed",), init="zeros", dtype=jnp.float32),
    }


def gelu_ffn(params, x):
    h = jnp.einsum("bsd,df->bsf", x, params["w_in"]) + params["b_in"].astype(x.dtype)
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    return jnp.einsum("bsf,fd->bsd", h, params["w_out"]) + params["b_out"].astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------


def embed_spec(vocab: int, d: int) -> dict:
    return {("embedding",): ParamSpec((vocab, d), ("vocab", "embed"), init="normal")}


def embed(params, tokens):
    return jnp.take(params["embedding"], tokens, axis=0)


def take_positions(x, pos):
    """x [B,S,d], pos [B] int -> [B,1,d]: each row's state at ``pos[b]``
    (the scoring path unembeds only these rows, not all S positions)."""
    return jnp.take_along_axis(x, pos[:, None, None], axis=1)


def unembed(params, x, *, tied: bool):
    w = params["embedding"] if tied else params["head"]
    if tied:
        return jnp.einsum("bsd,vd->bsv", x, w, preferred_element_type=jnp.float32)
    return jnp.einsum("bsd,dv->bsv", x, w, preferred_element_type=jnp.float32)


def unembed_spec(vocab: int, d: int, *, tied: bool) -> dict:
    if tied:
        return {}
    return {("head",): ParamSpec((d, vocab), ("embed_in", "vocab"), init="scaled")}
