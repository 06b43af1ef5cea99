"""Pallas TPU batched similarity: fused L2-normalize + MXU-tiled inner
products — the vector-search hot loop behind sem_search / sem_sim_join /
sem_join's sim-filter proxy (the FAISS-GPU analogue, TPU-native).

Grid (q-blocks, c-blocks); the full feature dim d rides inside the block
(embedding dims are <= a few thousand — one VMEM tile).  Norms are fused so
raw (un-normalized) embeddings never round-trip through HBM twice.

``sharded_similarity_topk`` is the device-parallel wrapper: the corpus is
row-sharded across a 1-D mesh with ``shard_map``, each device scores its
local tile (this kernel on TPU, its jnp math elsewhere) and keeps a local
top-k, and the per-shard candidate lists are merged on host
(`repro.kernels.ref.shard_topk_merge`).  jnp contract:
``ref.sharded_search_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from repro.kernels.ref import MASKED_SCORE, _unitize, pad_corpus_shards


def _kernel(q_ref, c_ref, o_ref, *, normalize: bool):
    q = q_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)
    if normalize:
        q = q * jax.lax.rsqrt(jnp.maximum(jnp.sum(q * q, -1, keepdims=True), 1e-18))
        c = c * jax.lax.rsqrt(jnp.maximum(jnp.sum(c * c, -1, keepdims=True), 1e-18))
    o_ref[...] = jax.lax.dot_general(
        q, c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ).astype(o_ref.dtype)


def similarity(queries, corpus, *, normalize: bool = True,
               block_q: int = 256, block_c: int = 256, interpret: bool = False):
    """queries:[nq,d], corpus:[nc,d] -> [nq,nc] f32 scores."""
    nq, d = queries.shape
    nc = corpus.shape[0]
    bq = min(block_q, nq)
    bc = min(block_c, nc)
    pq = (-nq) % bq
    pc = (-nc) % bc
    q = jnp.pad(jnp.asarray(queries), ((0, pq), (0, 0))) if pq else jnp.asarray(queries)
    c = jnp.pad(jnp.asarray(corpus), ((0, pc), (0, 0))) if pc else jnp.asarray(corpus)

    out = pl.pallas_call(
        functools.partial(_kernel, normalize=normalize),
        grid=((nq + pq) // bq, (nc + pc) // bc),
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bc, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bq, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nq + pq, nc + pc), jnp.float32),
        interpret=interpret,
    )(q, c)
    return out[:nq, :nc]


def shard_mesh(n_shards: int, *, devices=None) -> Mesh:
    """1-D retrieval mesh over the first ``n_shards`` devices."""
    devices = list(devices if devices is not None else jax.devices())[:n_shards]
    return Mesh(np.asarray(devices), ("shard",))


def place_shards(x, mesh: Mesh):
    """Place ``x`` on ``mesh`` split along its leading axis: device ``i`` of
    the ``shard`` axis holds the ``i``-th equal block of rows."""
    spec = P("shard", *([None] * (x.ndim - 1)))
    return jax.device_put(x, NamedSharding(mesh, spec))


def sharded_similarity_topk(queries, corpus, k: int, *, n_shards: int,
                            mesh: Mesh | None = None, normalize: bool = True,
                            interpret: bool = False, use_pallas: bool = False):
    """Device-sharded exact top-k: corpus rows split across ``n_shards``
    devices; each shard scores its tile and keeps ``min(k, local)`` local
    winners (global row ids reconstructed from ``axis_index``); the caller
    merges the [nq, n_shards*k_l] candidates (``ref.shard_topk_merge``).

    ``use_pallas`` runs the MXU similarity kernel per shard (TPU);
    otherwise the shard body is the kernel's jnp math (CPU multi-device).
    -> (scores [nq, n_shards*k_l], global idx [nq, n_shards*k_l]).
    """
    mesh = mesh if mesh is not None else shard_mesh(n_shards)
    q = jnp.asarray(queries, jnp.float32)
    c = jnp.asarray(corpus, jnp.float32)
    if normalize:  # normalize outside: rows are independent, shards agree
        q = _unitize(q)  # the reference's normalization, by definition
        c = _unitize(c)
    c, valid, local = pad_corpus_shards(c, n_shards)
    c, valid = place_shards(c, mesh), place_shards(valid, mesh)
    k_l = min(k, local)

    def body(q, c_local, v_local):
        if use_pallas:
            s = similarity(q, c_local, normalize=False, interpret=interpret)
        else:
            s = jax.lax.dot_general(q, c_local, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        s = jnp.where(v_local[None, :] > 0, s, MASKED_SCORE)
        vals, loc = jax.lax.top_k(s, k_l)
        offset = jax.lax.axis_index("shard") * c_local.shape[0]
        return vals, (loc + offset).astype(jnp.int32)

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(), P("shard", None), P("shard")),
        out_specs=(P(None, "shard"), P(None, "shard")),
        check_vma=False)(q, c, valid)
