"""Operations a dense decoder's forward pass needs, from a configuration's
sizes, and the chips' peaks by ``device_kind`` (``peaks.json``).

Only the work the answer needs is counted: real (unpadded) tokens, causal
attention over the positions before each token, and the unembedding of the
positions whose logits are read (the last one for scoring and prefill, every
step for decode).  Padding, recomputation and unembedding of unread
positions are waste and do not count."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peak(device_kind: str) -> dict:
    """The peak table's row for this chip; an unknown chip is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def _sizes(cfg: dict):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // h
    return (d, h, cfg["num_key_value_heads"], hd, cfg["intermediate_size"],
            cfg["vocab_size"], cfg["num_hidden_layers"])


def layer_matmul_flops(cfg: dict) -> float:
    """Multiply-adds x 2 of one layer's projections for one token."""
    d, h, hk, hd, f, _, _ = _sizes(cfg)
    attn = d * h * hd + 2 * d * hk * hd + h * hd * d
    return 2.0 * (attn + 3 * d * f)


def attention_flops(cfg: dict, ctx: float) -> float:
    """Scores and weighted sum of one token against ``ctx`` positions, one layer."""
    _, h, _, hd, _, _, _ = _sizes(cfg)
    return 4.0 * h * hd * ctx


def unembed_flops(cfg: dict) -> float:
    d, _, _, _, _, V, _ = _sizes(cfg)
    return 2.0 * d * V


def sequence_flops(cfg: dict, n: int, unembedded: int = 1) -> float:
    """One causal pass over ``n`` real tokens, unembedding ``unembedded``
    positions (the scoring step and prefill read only the last)."""
    L = cfg["num_hidden_layers"]
    return (L * (n * layer_matmul_flops(cfg) + attention_flops(cfg, n * (n + 1) / 2))
            + unembedded * unembed_flops(cfg))


def decode_flops(cfg: dict, ctx: int) -> float:
    """One generated token whose cache holds ``ctx`` positions (itself included)."""
    L = cfg["num_hidden_layers"]
    return L * (layer_matmul_flops(cfg) + attention_flops(cfg, ctx)) + unembed_flops(cfg)
