"""Plain float32 reference of a dense decoder (Qwen2 / Llama architecture).

The forward pass as the published Hugging Face models define it: token
embedding, then per layer RMSNorm -> rotary self-attention (q/k/v biases
where the config has them, half-split rotation) -> residual -> RMSNorm ->
SwiGLU -> residual, then a final RMSNorm and the unembedding.  Everything is
computed in float32 at "highest" matmul precision, one layer at a time, from
the weights the benchmark drew (bfloat16 leaves are widened per layer inside
the scan, so the float32 copy of one layer is all that is ever live).

``control=True`` is the same pass computed in float8 e4m3, the precision one
step below the bfloat16 the configuration states: both operands of every
weight matmul (projections, feed-forward, unembedding) are rounded to e4m3,
the weight with one scale per output channel and the activation with one
per token, and the products are summed in float32.  It is never run by the
benchmark's own runs.

Nothing here imports the system under test.  The weight tree uses the key
layout the benchmark draws (``bench.weights``)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8_e4m3fn


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"d": d, "h": h, "hk": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim") or d // h, "f": cfg["intermediate_size"],
            "V": cfg["vocab_size"], "L": cfg["num_hidden_layers"],
            "eps": float(cfg["rms_norm_eps"]), "theta": float(cfg["rope_theta"]),
            "tied": bool(cfg["tie_word_embeddings"]), "bias": bool(cfg["qkv_bias"])}


def _f8(w, axis):
    """Round ``w`` to float8 e4m3 with one scale per slice along ``axis``
    (the contraction axis), and widen back to float32."""
    w = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [B, T, H, hd]; rotate_half convention."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _layer(x, lw, dm, control):
    q8 = (lambda w, ax: _f8(w, ax)) if control else (lambda w, ax: w.astype(jnp.float32))
    a8 = (lambda v: _f8(v, -1)) if control else (lambda v: v)
    b, t, d = x.shape
    h, hk, hd = dm["h"], dm["hk"], dm["hd"]
    a = lw["attn"]
    y = a8(_rms(x, lw["attn_norm"]["scale"].astype(jnp.float32), dm["eps"]))
    q = jnp.einsum("btd,dhk->bthk", y, q8(a["wq"], 0), precision=HI)
    k = jnp.einsum("btd,dhk->bthk", y, q8(a["wk"], 0), precision=HI)
    v = jnp.einsum("btd,dhk->bthk", y, q8(a["wv"], 0), precision=HI)
    if dm["bias"]:
        q = q + a["bq"].astype(jnp.float32)
        k = k + a["bk"].astype(jnp.float32)
        v = v + a["bv"].astype(jnp.float32)
    q, k = _rope(q, dm["theta"]), _rope(k, dm["theta"])
    if hk != h:
        k = jnp.repeat(k, h // hk, axis=2)
        v = jnp.repeat(v, h // hk, axis=2)
    s = jnp.einsum("bqhk,bshk->bhqs", q, k, precision=HI) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqs,bshk->bqhk", p, v, precision=HI)
    o = a8(o.reshape(b, t, h * hd)).reshape(b, t, h, hd)
    x = x + jnp.einsum("bqhk,hkd->bqd", o, q8(a["wo"], (0, 1)), precision=HI)
    y = a8(_rms(x, lw["ffn_norm"]["scale"].astype(jnp.float32), dm["eps"]))
    f = lw["ffn"]
    g = jnp.einsum("btd,df->btf", y, q8(f["w_gate"], 0), precision=HI)
    u = jnp.einsum("btd,df->btf", y, q8(f["w_up"], 0), precision=HI)
    x = x + jnp.einsum("btf,fd->btd", a8(jax.nn.silu(g) * u), q8(f["w_down"], 0),
                       precision=HI)
    return x


def _final(weights, tokens, positions, dm, control):
    """The final-normed hidden state [B, P, d] at each of ``positions``."""
    x = jnp.take(weights["embed"]["embedding"], tokens, axis=0).astype(jnp.float32)

    def body(x, lw):
        return _layer(x, lw, dm, control), None

    x, _ = jax.lax.scan(body, x, weights["layers"])
    x = jnp.take_along_axis(x, positions[:, :, None], axis=1)       # [B, P, d]
    return _rms(x, weights["final_norm"]["scale"].astype(jnp.float32), dm["eps"])


@functools.partial(jax.jit, static_argnames=("cfg_items", "control"))
def _logits_at(weights, tokens, positions, *, cfg_items, control):
    dm = dict(cfg_items)
    x = _final(weights, tokens, positions, dm, control)
    if control:
        x = _f8(x, -1)
    if dm["tied"]:
        w = weights["embed"]["embedding"]                              # [V, d]
        w = _f8(w, 1) if control else w.astype(jnp.float32)
        return jnp.einsum("bpd,vd->bpv", x, w, precision=HI)
    w = weights["out"]["head"]                                         # [d, V]
    w = _f8(w, 0) if control else w.astype(jnp.float32)
    return jnp.einsum("bpd,dv->bpv", x, w, precision=HI)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _hidden_at(weights, tokens, positions, *, cfg_items):
    return _final(weights, tokens, positions, dict(cfg_items), False)


def logits_at(weights, cfg: dict, tokens, positions, *, control: bool = False):
    """tokens [B, T] int32 (right-padded; causal attention keeps the padding
    out of every real position), positions [B, P] int32 -> float32 logits
    [B, P, V] of the token that follows each of those positions."""
    with jax.default_matmul_precision("highest"):
        return _logits_at(weights, jnp.asarray(tokens, jnp.int32),
                          jnp.asarray(positions, jnp.int32),
                          cfg_items=tuple(sorted(dims(cfg).items())),
                          control=control)


def hidden_at(weights, cfg: dict, tokens, positions):
    """The final-normed float32 hidden state [B, P, d] that the unembedding
    reads at each of ``positions`` (same inputs as ``logits_at``)."""
    with jax.default_matmul_precision("highest"):
        return _hidden_at(weights, jnp.asarray(tokens, jnp.int32),
                          jnp.asarray(positions, jnp.int32),
                          cfg_items=tuple(sorted(dims(cfg).items())))
