"""Family dispatch: one uniform interface over all model families.

    param_specs(cfg)                          -> SpecTree
    forward(cfg, params, tokens, last=None)   -> (logits, aux)
    cache_specs(cfg, batch, max_seq)          -> SpecTree
    prefill(cfg, params, tokens, cache, ...)  -> (logits, cache)
    decode_step(cfg, params, tokens, cache, cache_len, ...) -> (logits, cache)
"""
from __future__ import annotations

from repro.common import SpecTree, init_params as _init, param_structs, unflatten
from repro.configs.base import ModelConfig

from repro.models import encdec, hybrid, transformer, xlstm_lm

_FAMILY = {
    "dense": transformer,
    "moe": transformer,
    "vlm": transformer,
    "audio": encdec,
    "ssm": xlstm_lm,
    "hybrid": hybrid,
}


def module_for(cfg: ModelConfig):
    return _FAMILY[cfg.family]


def param_specs(cfg: ModelConfig) -> SpecTree:
    return module_for(cfg).param_specs(cfg)


def init_params(cfg: ModelConfig, key) -> dict:
    return _init(param_specs(cfg), key)


def param_structs_tree(cfg: ModelConfig) -> dict:
    return param_structs(param_specs(cfg))


def forward(cfg: ModelConfig, params, tokens, *, extra=None, remat=False, last=None):
    return module_for(cfg).forward(params, tokens, cfg=cfg, extra=extra, remat=remat,
                                   last=last)


def cache_specs(cfg: ModelConfig, batch: int, max_seq: int) -> SpecTree:
    return module_for(cfg).cache_specs(cfg, batch, max_seq)


def init_cache(cfg: ModelConfig, batch: int, max_seq: int) -> dict:
    import jax.numpy as jnp
    specs = cache_specs(cfg, batch, max_seq)
    return unflatten({p: jnp.zeros(s.shape, s.dtype) for p, s in specs.items()})


def cache_structs(cfg: ModelConfig, batch: int, max_seq: int) -> dict:
    return param_structs(cache_specs(cfg, batch, max_seq))


def prefill(cfg: ModelConfig, params, tokens, cache, *, extra=None, last_only=False):
    return module_for(cfg).prefill(params, tokens, cache, cfg=cfg, extra=extra,
                                   last_only=last_only)


def decode_step(cfg: ModelConfig, params, tokens, cache, cache_len, *, extra=None):
    return module_for(cfg).decode_step(params, tokens, cache, cache_len, cfg=cfg, extra=extra)
