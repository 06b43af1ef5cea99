"""The dense decoder (Qwen2 / Llama) on the system's side: the ``ModelConfig``
the engine builds, the weight layout the benchmark draws, and the operations
a forward pass needs.  A configuration selects it with
``"architecture": "dense"``; its reference is ``dense_reference``.

An architecture module is what the harness knows of a model family.  A new
family adds one beside this file, and it exports:

* ``program_config(cfg) -> repro.configs.base.ModelConfig``: the system's
  configuration for the file's sizes, in bfloat16.
* ``leaf_plan(cfg) -> {path: (shape, dtype, init, std)}``: every parameter
  of the system's tree (``weights.draw`` draws it leaf by leaf from the
  seed, and ``weights.check_layout`` holds it against the system's own
  table).  The tree has ``embed.embedding`` [V, d], and ``out.head``
  [d, V] unless the head is tied: ``weights.set_label_margin`` sets the
  <false> column of ``out.head``, so an oracle under a filter mix has an
  untied head.
* ``sequence_flops(cfg, n, unembedded=1)``: the operations of one causal
  pass over ``n`` real tokens that unembeds ``unembedded`` positions (the
  scoring step and prefill).
* ``decode_flops(cfg, ctx)``: the operations of one generated token whose
  cache holds ``ctx`` positions.  Both count what the answer needs on this
  chip: for sparse experts, the active experts' share and the router.

Its paired reference module (the configuration's ``reference``) exports
``logits_at(weights, cfg, tokens, positions, *, control)`` and
``hidden_at(weights, cfg, tokens, positions)`` as ``dense_reference`` does,
over the same weight tree, and imports nothing of the system."""
from __future__ import annotations

from bench.configs.dense_reference import dims
from bench.flops import decode_flops, sequence_flops

__all__ = ["program_config", "leaf_plan", "sequence_flops", "decode_flops"]


def program_config(cfg: dict):
    """The system's ``ModelConfig`` for a configuration file's sizes."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=cfg["name"], family="dense", num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], head_dim=cfg.get("head_dim", 0),
        qkv_bias=bool(cfg["qkv_bias"]), rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]), dtype="bfloat16")


def leaf_plan(cfg: dict) -> dict:
    """path -> (shape, dtype, init, std).  ``init`` is ``normal`` (std * N),
    ``one_plus`` (1 + std * N) or ``zeros``."""
    dm = dims(cfg)
    d, h, hk, hd, f, V, L = (dm[k] for k in ("d", "h", "hk", "hd", "f", "V", "L"))
    bf, f32 = "bfloat16", "float32"
    plan = {
        # a tied table is also the head: scale it like one, so logits stay
        # near unit size (std 1 here gave logits of std sqrt(d) in the proxy)
        ("embed", "embedding"): ((V, d), bf, "normal", d ** -0.5 if dm["tied"] else 1.0),
        ("layers", "attn", "wq"): ((L, d, h, hd), bf, "normal", d ** -0.5),
        ("layers", "attn", "wk"): ((L, d, hk, hd), bf, "normal", d ** -0.5),
        ("layers", "attn", "wv"): ((L, d, hk, hd), bf, "normal", d ** -0.5),
        ("layers", "attn", "wo"): ((L, h, hd, d), bf, "normal", (h * hd) ** -0.5),
        ("layers", "attn_norm", "scale"): ((L, d), f32, "one_plus", 0.1),
        ("layers", "ffn_norm", "scale"): ((L, d), f32, "one_plus", 0.1),
        ("layers", "ffn", "w_gate"): ((L, d, f), bf, "normal", d ** -0.5),
        ("layers", "ffn", "w_up"): ((L, d, f), bf, "normal", d ** -0.5),
        ("layers", "ffn", "w_down"): ((L, f, d), bf, "normal", f ** -0.5),
        ("final_norm", "scale"): ((d,), f32, "one_plus", 0.1),
    }
    if dm["bias"]:
        plan[("layers", "attn", "bq")] = ((L, h, hd), f32, "normal", 0.1)
        plan[("layers", "attn", "bk")] = ((L, hk, hd), f32, "normal", 0.1)
        plan[("layers", "attn", "bv")] = ((L, hk, hd), f32, "normal", 0.1)
    if not dm["tied"]:
        plan[("out", "head")] = ((d, V), bf, "normal", d ** -0.5)
    return plan
