"""Pallas TPU quantized IVF cluster scan: fused dequantize+score.

The int8 sibling of `repro.kernels.ivf_scan`: the same scalar-prefetched
masked gather-scan over padded per-cluster tiles — same static MXU grid
(query blocks x probe slots), same probe selection, same ``MASKED_SCORE``
padding discipline — but the tiles ride in as symmetric per-vector int8
(``store_q [kc, L, d]`` int8 + ``scales [kc, L]`` f32;
`repro.index.quant`), cutting the HBM bytes the hot loop streams per
vector from ``4*d`` to ``d + 4``.

Dequantization fuses into the scan: the per-vector scale factors out of the
inner product, so the kernel upcasts the int8 tile for one MXU pass and
multiplies the *score plane* by the tile's scale row — d multiplies per
vector become 1, and no f32 copy of the tile ever materializes.

`repro.kernels.ref.ivf_search_q_ref` is the pure-jnp contract (CPU CI);
``interpret=True`` runs this kernel body under the Pallas interpreter.
The recall story lives a layer up: `IVFIndex(quantize="int8")` exact-reranks
the top ``rerank_factor*k`` quantized candidates in fp32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import MASKED_SCORE, _unitize, ivf_probes, pad_queries


def _scan_kernel_q(p_ref, q_ref, v_ref, s_ref, m_ref, o_ref, *,
                   normalize: bool):
    del p_ref  # probe ids are consumed by the index_maps, not the body
    q = q_ref[...].astype(jnp.float32)                      # [bq, d]
    if normalize:
        q = q * jax.lax.rsqrt(jnp.maximum(jnp.sum(q * q, -1, keepdims=True), 1e-18))
    v = v_ref[0].astype(jnp.float32)                        # [L, d] int8 -> f32
    s = jax.lax.dot_general(q, v, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [bq, L]
    s = s * s_ref[0]                    # fused dequantize: per-vector scale
    o_ref[...] = jnp.where(m_ref[0] > 0, s, MASKED_SCORE)  # rows: [1, L]


def cluster_scan_q(queries, store_q, scales, mask, probe_blocks, *,
                   block_q: int = 8, normalize: bool = True,
                   interpret: bool = False):
    """queries [nb*bq, d], store_q [kc, L, d] int8, scales [kc, L] f32,
    mask [kc, L], probe_blocks [nb, slots] int32 -> scores [nb*bq, slots*L]
    f32 (padding slots = MASKED_SCORE)."""
    nq, d = queries.shape
    _, L, _ = store_q.shape
    nb, slots = probe_blocks.shape
    assert nq == nb * block_q, "queries must be pre-padded to full blocks"
    # scale and mask rows ride as [kc, 1, L]: a (1, 1, L) block spans the
    # array's last two dims, the layout the TPU lowering accepts
    scales = jnp.asarray(scales, jnp.float32).reshape(-1, 1, L)
    mask = jnp.asarray(mask).reshape(-1, 1, L)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, slots),
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j, p: (i, 0)),
            pl.BlockSpec((1, L, d), lambda i, j, p: (p[i, j], 0, 0)),
            pl.BlockSpec((1, 1, L), lambda i, j, p: (p[i, j], 0, 0)),
            pl.BlockSpec((1, 1, L), lambda i, j, p: (p[i, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, L), lambda i, j, p: (i, j)),
    )
    return pl.pallas_call(
        functools.partial(_scan_kernel_q, normalize=normalize),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nq, slots * L), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(probe_blocks, jnp.int32), jnp.asarray(queries),
      jnp.asarray(store_q, jnp.int8), scales, mask)


def ivf_search_q(queries, centroids, store_q, scales, mask, *, nprobe: int,
                 block_q: int = 8, interpret: bool = False):
    """Fused quantized IVF search: centroid scoring + per-query top-``nprobe``
    probe selection (both fp32 — centroids are tiny) + quantized cluster
    scan, no host round trip between stages.

    -> (scores [nq, bq*nprobe*L], probe_blocks [nb, bq*nprobe]); row i's
    candidate j came from cluster probe_blocks[i // bq, j // L], slot j % L.
    """
    q, nb = pad_queries(jnp.asarray(queries, jnp.float32), block_q)
    q = _unitize(q)  # same normalization as the jnp reference, by definition
    probe_blocks = ivf_probes(q, jnp.asarray(centroids), nprobe, block_q)
    scores = cluster_scan_q(q, store_q, scales, mask, probe_blocks,
                            block_q=block_q, normalize=False,
                            interpret=interpret)
    return scores[: len(queries)], probe_blocks


# ---------------------------------------------------------------------------
# Device-sharded quantized scan (shard_map over the cluster axis)
# ---------------------------------------------------------------------------


def sharded_ivf_search_q(queries, centroids, store_q, scales, mask, *,
                         nprobe: int, n_shards: int, block_q: int = 8,
                         mesh=None, interpret: bool = False,
                         use_pallas: bool = False):
    """Device-sharded quantized IVF search: identical sharding discipline to
    ``ivf_scan.sharded_ivf_search`` (int8 tiles + their scale rows
    partitioned across ``n_shards`` devices along the cluster axis, global
    probe selection, each device scans only the probed clusters it owns,
    per-device planes combine with one ``pmax``) — the combined plane is
    identical to the unsharded :func:`ivf_search_q` while per-device *bytes*
    drop to the local probed clusters' int8 tiles.  jnp contract:
    ``repro.kernels.ref.sharded_ivf_search_q_ref``."""
    from jax.sharding import PartitionSpec as P

    from repro.kernels.ref import ivf_scan_q_ref
    from repro.kernels.similarity import place_shards, shard_map, shard_mesh

    q, nb = pad_queries(jnp.asarray(queries, jnp.float32), block_q)
    q = _unitize(q)
    probe_blocks = ivf_probes(q, jnp.asarray(centroids), nprobe, block_q)
    kc, L, d = store_q.shape
    mesh = mesh if mesh is not None else shard_mesh(n_shards)
    local = max(1, -(-kc // n_shards))
    pad = n_shards * local - kc
    st = jnp.asarray(store_q, jnp.int8)
    sc = jnp.asarray(scales, jnp.float32)
    mk = jnp.asarray(mask)
    if pad:
        # equal tiles per device; padded clusters are never probed (probe
        # ids are < kc) and their mask is zero anyway
        st = jnp.concatenate([st, jnp.zeros((pad, L, d), st.dtype)])
        sc = jnp.concatenate([sc, jnp.ones((pad, L), sc.dtype)])
        mk = jnp.concatenate([mk, jnp.zeros((pad, L), mk.dtype)])
    st, sc, mk = (place_shards(a, mesh) for a in (st, sc, mk))

    def body(q, p, st_local, sc_local, mk_local):
        offset = jax.lax.axis_index("shard") * st_local.shape[0]
        local_p = p - offset
        in_range = (local_p >= 0) & (local_p < st_local.shape[0])
        safe = jnp.where(in_range, local_p, 0).astype(jnp.int32)
        if use_pallas:
            s = cluster_scan_q(q, st_local, sc_local, mk_local, safe,
                               block_q=block_q, normalize=False,
                               interpret=interpret)
        else:
            s = ivf_scan_q_ref(q, st_local, sc_local, mk_local, safe,
                               block_q=block_q, normalize=False)
        keep = jnp.repeat(jnp.repeat(in_range, L, axis=1), block_q, axis=0)
        s = jnp.where(keep, s, MASKED_SCORE)
        return jax.lax.pmax(s, "shard")

    scores = shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), P("shard", None, None), P("shard", None),
                  P("shard", None)),
        out_specs=P(),
        check_vma=False)(q, probe_blocks, st, sc, mk)
    return scores[: len(queries)], probe_blocks
