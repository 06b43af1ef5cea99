"""xLSTM language model: grouped stacks of mLSTM blocks with an sLSTM block
every ``cfg.slstm_every`` layers (xLSTM[m:s] notation of arXiv:2405.04517).
"""
from __future__ import annotations

import functools

import jax

from repro.common import ParamSpec, SpecTree
from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import xlstm as X
from repro.models.transformer import _group_tree, _maybe_remat, _stack


def _layout(cfg: ModelConfig):
    if cfg.slstm_every:
        g = cfg.num_layers // cfg.slstm_every
        return {"groups": g, "m_per_group": cfg.slstm_every - 1,
                "n_m": g * (cfg.slstm_every - 1), "n_s": g}
    return {"groups": 0, "m_per_group": 0, "n_m": cfg.num_layers, "n_s": 0}


def _m_block_specs(cfg):
    specs = {("norm",) + p: s for p, s in L.rmsnorm_spec(cfg.d_model).items()}
    specs.update({("mixer",) + p: s for p, s in X.mlstm_spec(cfg).items()})
    return specs


def _s_block_specs(cfg):
    specs = {("norm",) + p: s for p, s in L.rmsnorm_spec(cfg.d_model).items()}
    specs.update({("mixer",) + p: s for p, s in X.slstm_spec(cfg).items()})
    return specs


def param_specs(cfg: ModelConfig) -> SpecTree:
    lay = _layout(cfg)
    specs: SpecTree = {}
    specs.update({("embed",) + p: s for p, s in L.embed_spec(cfg.vocab_size, cfg.d_model).items()})
    specs.update(_stack(_m_block_specs(cfg), lay["n_m"], "m_layers"))
    if lay["n_s"]:
        specs.update(_stack(_s_block_specs(cfg), lay["n_s"], "s_layers"))
    specs.update({("final_norm",) + p: s for p, s in L.rmsnorm_spec(cfg.d_model).items()})
    specs.update({("out",) + p: s
                  for p, s in L.unembed_spec(cfg.vocab_size, cfg.d_model, tied=cfg.tie_embeddings).items()})
    return specs


def _m_block(lp, x, *, cfg, state=None, return_state=False):
    from repro.dist.sharding import shard_activation
    x = shard_activation(x, ("batch", None, None))
    h = L.rmsnorm(lp["norm"], x, cfg.norm_eps)
    if return_state:
        y, st = X.mlstm_forward(lp["mixer"], h, cfg=cfg, state=state, return_state=True)
        return x + y, st
    return x + X.mlstm_forward(lp["mixer"], h, cfg=cfg), None


def _s_block(lp, x, *, cfg, state=None, return_state=False):
    h = L.rmsnorm(lp["norm"], x, cfg.norm_eps)
    if return_state:
        y, st = X.slstm_forward(lp["mixer"], h, cfg=cfg, state=state, return_state=True)
        return x + y, st
    return x + X.slstm_forward(lp["mixer"], h, cfg=cfg), None


def _run_seq(params, x, *, cfg: ModelConfig, remat: bool, collect_state: bool):
    lay = _layout(cfg)
    mb = _maybe_remat(functools.partial(_m_block, cfg=cfg, return_state=collect_state), cfg, remat)
    sb = _maybe_remat(functools.partial(_s_block, cfg=cfg, return_state=collect_state), cfg, remat)
    states = {}
    if lay["n_s"] == 0:
        def body(x, lp):
            x, st = mb(lp, x)
            return x, st
        x, sts = jax.lax.scan(body, x, params["m_layers"])
        if collect_state:
            states["m"] = sts
    else:
        m_groups = _group_tree(params["m_layers"], lay["groups"])

        def group(x, gp):
            mp, sp = gp

            def inner(x, lp):
                x, st = mb(lp, x)
                return x, st

            x, msts = jax.lax.scan(inner, x, mp)
            x, sst = sb(sp, x)
            return x, (msts, sst)

        x, (msts, ssts) = jax.lax.scan(group, x, (m_groups, params["s_layers"]))
        if collect_state:
            states["m"] = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), msts)
            states["s"] = ssts
    return x, states


def _logits(params, x, cfg):
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return L.unembed({**params.get("out", {}), **params["embed"]}, x, tied=cfg.tie_embeddings)


def forward(params, tokens, *, cfg: ModelConfig, extra=None, remat=False, last=None):
    x = L.embed(params["embed"], tokens).astype(cfg.activation_dtype)
    x, _ = _run_seq(params, x, cfg=cfg, remat=remat, collect_state=False)
    if last is not None:
        x = L.take_positions(x, last)
    return _logits(params, x, cfg), {}


def cache_specs(cfg: ModelConfig, batch: int, max_seq: int) -> SpecTree:
    lay = _layout(cfg)
    specs: SpecTree = {}
    for p, s in X.mlstm_state_specs(cfg, batch).items():
        specs[("m",) + p] = ParamSpec((lay["n_m"],) + s.shape, ("layers",) + s.axes, dtype=s.dtype, init="zeros")
    for p, s in X.slstm_state_specs(cfg, batch).items():
        if lay["n_s"]:
            specs[("s",) + p] = ParamSpec((lay["n_s"],) + s.shape, ("layers",) + s.axes, dtype=s.dtype, init="zeros")
    return specs


def prefill(params, tokens, cache, *, cfg: ModelConfig, extra=None, last_only=False):
    x = L.embed(params["embed"], tokens).astype(cfg.activation_dtype)
    x, states = _run_seq(params, x, cfg=cfg, remat=False, collect_state=True)
    if last_only:
        x = x[:, -1:]
    return _logits(params, x, cfg), states


def decode_step(params, tokens, cache, cache_len, *, cfg: ModelConfig, extra=None):
    lay = _layout(cfg)
    x = L.embed(params["embed"], tokens).astype(cfg.activation_dtype)

    def m_step(x, inp):
        lp, st = inp
        h = L.rmsnorm(lp["norm"], x, cfg.norm_eps)
        st, y = X.mlstm_decode(lp["mixer"], st, h, cfg=cfg)
        return x + y, st

    new_cache: dict = {}
    if lay["n_s"] == 0:
        x, msts = jax.lax.scan(m_step, x, (params["m_layers"], cache["m"]))
        new_cache["m"] = msts
    else:
        m_groups = _group_tree(params["m_layers"], lay["groups"])
        m_states = _group_tree(cache["m"], lay["groups"])

        def group(x, inp):
            mp, mst, sp, sst = inp
            x, msts = jax.lax.scan(m_step, x, (mp, mst))
            h = L.rmsnorm(sp["norm"], x, cfg.norm_eps)
            sst, y = X.slstm_decode(sp["mixer"], sst, h, cfg=cfg)
            return x + y, (msts, sst)

        x, (msts, ssts) = jax.lax.scan(group, x, (m_groups, m_states, params["s_layers"], cache["s"]))
        new_cache["m"] = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), msts)
        new_cache["s"] = ssts
    return _logits(params, x, cfg), new_cache
