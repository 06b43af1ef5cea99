"""Pallas TPU IVF cluster scan: the ANN hot loop behind `IVFIndex.search`.

One fused pipeline per search:

  1. centroid scoring  — queries x coarse-quantizer centroids (one MXU pass);
  2. probe selection   — per-query top-``nprobe`` clusters (`jax.lax.top_k`);
  3. cluster scan      — the hand-written kernel below: a masked gather-scan
     over *only the probed clusters'* vectors.

The inverted file is laid out as padded per-cluster tiles ``store [kc, L, d]``
(`L` = max cluster size rounded up to the lane width) with a validity mask
``mask [kc, L]``, so the MXU grid stays static: grid = (query-blocks, probe
slots), and the probed cluster id rides in as a *scalar-prefetched* index —
the BlockSpec index_map gathers exactly that cluster's tile from HBM, scores
it against the query block on the MXU, and masks the padding lanes to -inf.
Work is O(sum of probed cluster sizes), not O(corpus).

Probe slots are per-query: a block of ``block_q`` queries scans the
concatenation of its queries' top-``nprobe`` lists (every query is
guaranteed its own best clusters; blockmates' clusters come along free since
the MXU scores the whole query block per tile anyway).

`repro.kernels.ref.ivf_search_ref` is the pure-jnp reference (CPU CI), and
`interpret=True` runs this kernel body under the Pallas interpreter.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import MASKED_SCORE, _unitize, ivf_probes, pad_queries


def _scan_kernel(p_ref, q_ref, v_ref, m_ref, o_ref, *, normalize: bool):
    del p_ref  # probe ids are consumed by the index_maps, not the body
    q = q_ref[...].astype(jnp.float32)                      # [bq, d]
    if normalize:
        q = q * jax.lax.rsqrt(jnp.maximum(jnp.sum(q * q, -1, keepdims=True), 1e-18))
    v = v_ref[0].astype(jnp.float32)                        # [L, d]
    s = jax.lax.dot_general(q, v, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [bq, L]
    o_ref[...] = jnp.where(m_ref[0] > 0, s, MASKED_SCORE)  # m_ref[0]: [1, L]


def cluster_scan(queries, store, mask, probe_blocks, *, block_q: int = 8,
                 normalize: bool = True, interpret: bool = False):
    """queries [nb*bq, d], store [kc, L, d], mask [kc, L],
    probe_blocks [nb, slots] int32 -> scores [nb*bq, slots*L] f32
    (padding slots = MASKED_SCORE)."""
    nq, d = queries.shape
    _, L, _ = store.shape
    nb, slots = probe_blocks.shape
    assert nq == nb * block_q, "queries must be pre-padded to full blocks"
    # the mask rides as [kc, 1, L]: a (1, 1, L) block spans the array's last
    # two dims, the layout the TPU lowering accepts for a one-row block
    mask = jnp.asarray(mask).reshape(-1, 1, L)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, slots),
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j, p: (i, 0)),
            pl.BlockSpec((1, L, d), lambda i, j, p: (p[i, j], 0, 0)),
            pl.BlockSpec((1, 1, L), lambda i, j, p: (p[i, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, L), lambda i, j, p: (i, j)),
    )
    return pl.pallas_call(
        functools.partial(_scan_kernel, normalize=normalize),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nq, slots * L), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(probe_blocks, jnp.int32), jnp.asarray(queries),
      jnp.asarray(store), mask)


def ivf_search(queries, centroids, store, mask, *, nprobe: int,
               block_q: int = 8, interpret: bool = False):
    """Fused IVF search (stages 1-3 above, no host round trip between them).

    -> (scores [nq, bq*nprobe*L], probe_blocks [nb, bq*nprobe]); row i's
    candidate j came from cluster probe_blocks[i // bq, j // L], slot j % L.
    """
    q, nb = pad_queries(jnp.asarray(queries, jnp.float32), block_q)
    q = _unitize(q)  # same normalization as the jnp reference, by definition
    probe_blocks = ivf_probes(q, jnp.asarray(centroids), nprobe, block_q)
    scores = cluster_scan(q, store, mask, probe_blocks, block_q=block_q,
                          normalize=False, interpret=interpret)
    return scores[: len(queries)], probe_blocks


# ---------------------------------------------------------------------------
# Device-sharded cluster scan (shard_map over the cluster axis)
# ---------------------------------------------------------------------------


def sharded_ivf_search(queries, centroids, store, mask, *, nprobe: int,
                       n_shards: int, block_q: int = 8, mesh=None,
                       interpret: bool = False, use_pallas: bool = False):
    """Device-sharded IVF search: the inverted file's per-cluster tiles are
    partitioned across ``n_shards`` devices along the cluster axis; probe
    selection stays global (centroids are tiny and replicated), and every
    device scans only the probed clusters *it owns* — out-of-shard probe
    slots score MASKED_SCORE and the per-device score planes combine with
    one ``pmax`` across the mesh axis.  Each candidate is scored by exactly
    its home device, so the combined plane is identical to the unsharded
    :func:`ivf_search` while per-device work drops to the local probed
    clusters.  jnp contract: ``repro.kernels.ref.sharded_ivf_search_ref``.

    ``use_pallas`` runs :func:`cluster_scan` per shard (TPU); otherwise the
    shard body is the reference gather math (CPU multi-device meshes).
    -> (scores [nq, bq*nprobe*L], probe_blocks [nb, bq*nprobe]).
    """
    from jax.sharding import PartitionSpec as P

    from repro.kernels.ref import ivf_scan_ref
    from repro.kernels.similarity import place_shards, shard_map, shard_mesh

    q, nb = pad_queries(jnp.asarray(queries, jnp.float32), block_q)
    q = _unitize(q)
    probe_blocks = ivf_probes(q, jnp.asarray(centroids), nprobe, block_q)
    kc, L, d = store.shape
    mesh = mesh if mesh is not None else shard_mesh(n_shards)
    local = max(1, -(-kc // n_shards))
    pad = n_shards * local - kc
    st = jnp.asarray(store)
    mk = jnp.asarray(mask)
    if pad:
        # equal tiles per device; padded clusters are never probed (probe
        # ids are < kc) and their mask is zero anyway
        st = jnp.concatenate([st, jnp.zeros((pad, L, d), st.dtype)])
        mk = jnp.concatenate([mk, jnp.zeros((pad, L), mk.dtype)])
    st, mk = place_shards(st, mesh), place_shards(mk, mesh)

    def body(q, p, st_local, mk_local):
        offset = jax.lax.axis_index("shard") * st_local.shape[0]
        local_p = p - offset
        in_range = (local_p >= 0) & (local_p < st_local.shape[0])
        safe = jnp.where(in_range, local_p, 0).astype(jnp.int32)
        if use_pallas:
            s = cluster_scan(q, st_local, mk_local, safe, block_q=block_q,
                             normalize=False, interpret=interpret)
        else:
            s = ivf_scan_ref(q, st_local, mk_local, safe, block_q=block_q,
                             normalize=False)
        keep = jnp.repeat(jnp.repeat(in_range, L, axis=1), block_q, axis=0)
        s = jnp.where(keep, s, MASKED_SCORE)
        return jax.lax.pmax(s, "shard")

    scores = shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), P("shard", None, None), P("shard", None)),
        out_specs=P(),
        check_vma=False)(q, probe_blocks, st, mk)
    return scores[: len(queries)], probe_blocks
