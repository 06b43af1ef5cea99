"""Public jit'd wrappers for the Pallas kernels with implementation dispatch.

    impl="auto"      Pallas on TPU, jnp reference elsewhere (CPU CI)
    impl="pallas"    force compiled Pallas (TPU)
    impl="interpret" Pallas kernel body interpreted on CPU (tests)
    impl="ref"       pure-jnp oracle

On a TPU host nothing here runs the reference unless ``impl="ref"`` asks
for it: a device query that fails raises instead of picking a fallback.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import decode_attention as _da
from repro.kernels import flash_attention as _fa
from repro.kernels import ivf_scan as _ivf
from repro.kernels import ivf_scan_q as _ivfq
from repro.kernels import ref
from repro.kernels import rmsnorm as _rn
from repro.kernels import similarity as _sim
from repro.obs import trace as _trace

DEFAULT_IMPL = "auto"


@contextlib.contextmanager
def _kernel_span(name: str, mode: str, **attrs):
    """Kernel-dispatch observability: a ``kind="kernel"`` span, so host-side
    kernel time is attributed to the owning operator span and shows in a
    JAX profile as ``repro.kernel.<name>``.  Yields the span (None when
    tracing is off — the zero-overhead default path)."""
    if not _trace.active():
        yield None
        return
    with _trace.span(f"kernel/{name}", kind="kernel",
                     event=f"repro.kernel.{name}", impl=mode, **attrs) as sp:
        yield sp


def _ready(out, sp):
    """Under a tracer, block until device work finishes so the enclosing
    kernel span measures compute, not dispatch; calls that only a JAX
    profile records, and untraced calls, keep jax's async dispatch (the
    ``np.asarray`` conversions sync anyway)."""
    if isinstance(sp, _trace.Span):
        out = jax.block_until_ready(out)
    return out


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def _resolve(impl: str | None) -> str:
    impl = impl or DEFAULT_IMPL
    if impl == "auto":
        return "pallas" if _on_tpu() else "ref"
    return impl


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    impl: str | None = None, **kw):
    mode = _resolve(impl)
    if mode == "ref":
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               interpret=(mode == "interpret"), **kw)


def decode_attention(q, k, v, lens, *, impl: str | None = None, **kw):
    mode = _resolve(impl)
    if mode == "ref":
        return ref.decode_attention_ref(q, k, v, jnp.asarray(lens))
    return _da.decode_attention(q, k, v, lens, interpret=(mode == "interpret"), **kw)


@functools.partial(jax.jit, static_argnames=("normalize",))
def _sim_ref_jit(q, c, normalize=True):
    return ref.similarity_ref(q, c, normalize=normalize)


def similarity(queries, corpus, *, normalize: bool = True,
               impl: str | None = None, **kw) -> np.ndarray:
    mode = _resolve(impl)
    with _kernel_span("similarity", mode, nq=len(queries),
                      nc=len(corpus)) as sp:
        if mode == "ref":
            out = _sim_ref_jit(jnp.asarray(queries), jnp.asarray(corpus),
                               normalize=normalize)
        else:
            out = _sim.similarity(queries, corpus, normalize=normalize,
                                  interpret=(mode == "interpret"), **kw)
        return np.asarray(_ready(out, sp))


def ivf_search(queries, centroids, store, mask, *, nprobe: int,
               block_q: int = 8, impl: str | None = None
               ) -> tuple[np.ndarray, np.ndarray]:
    """Fused IVF retrieval: centroid scoring + per-query top-``nprobe``
    probe selection + masked cluster scan over the padded inverted file.

    -> (scores [nq, block_q*nprobe*L] f32, probe_blocks [nb, block_q*nprobe]);
    masked/padded candidates score ``ref.MASKED_SCORE``."""
    mode = _resolve(impl)
    with _kernel_span("ivf_search", mode, nq=len(queries),
                      nprobe=nprobe) as sp:
        if mode == "ref":
            s, p = ref.ivf_search_ref(jnp.asarray(queries),
                                      jnp.asarray(centroids),
                                      jnp.asarray(store), jnp.asarray(mask),
                                      nprobe=nprobe, block_q=block_q)
        else:
            s, p = _ivf.ivf_search(queries, centroids, store, mask,
                                   nprobe=nprobe, block_q=block_q,
                                   interpret=(mode == "interpret"))
        s = _ready(s, sp)
        return np.asarray(s), np.asarray(p)


def ivf_delta_search(queries, centroids, store, mask, delta_vectors, *,
                     nprobe: int, block_q: int = 8, impl: str | None = None
                     ) -> tuple[np.ndarray, np.ndarray]:
    """Delta-aware IVF retrieval: the fused probed-cluster scan
    (:func:`ivf_search` — Pallas on TPU) plus an exact scan of the streaming
    delta side buffer, concatenated along the candidate axis.  The buffer is
    small by construction (the drift detector retrains past the spill
    threshold), so its exact scan rides the plain similarity kernel.

    -> (scores [nq, block_q*nprobe*L + nd] f32, probe_blocks); jnp contract:
    ``ref.ivf_delta_search_ref``."""
    mode = _resolve(impl)
    if mode == "ref":
        s, p = ref.ivf_delta_search_ref(
            jnp.asarray(queries), jnp.asarray(centroids), jnp.asarray(store),
            jnp.asarray(mask), jnp.asarray(delta_vectors),
            nprobe=nprobe, block_q=block_q)
        return np.asarray(s), np.asarray(p)
    s, p = ivf_search(queries, centroids, store, mask, nprobe=nprobe,
                      block_q=block_q, impl=impl)
    ds = similarity(queries, delta_vectors, normalize=True, impl=impl)
    return np.concatenate([s, np.asarray(ds, np.float32)], axis=1), p


def ivf_search_q(queries, centroids, store_q, scales, mask, *, nprobe: int,
                 block_q: int = 8, impl: str | None = None
                 ) -> tuple[np.ndarray, np.ndarray]:
    """Fused *quantized* IVF retrieval: the :func:`ivf_search` pipeline over
    symmetric per-vector int8 tiles (``store_q`` int8 + ``scales`` f32;
    `repro.index.quant`), dequantization fused into the cluster scan as one
    per-lane multiply on the score plane — ``d + 4`` HBM bytes per scanned
    vector instead of ``4 * d``.

    -> (scores [nq, block_q*nprobe*L] f32, probe_blocks); jnp contract:
    ``ref.ivf_search_q_ref``."""
    mode = _resolve(impl)
    with _kernel_span("ivf_search_q", mode, nq=len(queries),
                      nprobe=nprobe) as sp:
        if mode == "ref":
            s, p = ref.ivf_search_q_ref(
                jnp.asarray(queries), jnp.asarray(centroids),
                jnp.asarray(store_q, jnp.int8), jnp.asarray(scales),
                jnp.asarray(mask), nprobe=nprobe, block_q=block_q)
        else:
            s, p = _ivfq.ivf_search_q(queries, centroids, store_q, scales,
                                      mask, nprobe=nprobe, block_q=block_q,
                                      interpret=(mode == "interpret"))
        s = _ready(s, sp)
        return np.asarray(s), np.asarray(p)


def ivf_delta_search_q(queries, centroids, store_q, scales, mask, delta_q,
                       delta_scales, *, nprobe: int, block_q: int = 8,
                       impl: str | None = None
                       ) -> tuple[np.ndarray, np.ndarray]:
    """Quantized delta-aware IVF retrieval: the fused quantized probed-
    cluster scan plus a dequantize-fused exact scan of the int8 streaming
    delta side buffer, concatenated along the candidate axis.

    -> (scores [nq, block_q*nprobe*L + nd] f32, probe_blocks); jnp contract:
    ``ref.ivf_delta_search_q_ref``."""
    mode = _resolve(impl)
    if mode == "ref":
        s, p = ref.ivf_delta_search_q_ref(
            jnp.asarray(queries), jnp.asarray(centroids),
            jnp.asarray(store_q, jnp.int8), jnp.asarray(scales),
            jnp.asarray(mask), jnp.asarray(delta_q, jnp.int8),
            jnp.asarray(delta_scales), nprobe=nprobe, block_q=block_q)
        return np.asarray(s), np.asarray(p)
    s, p = ivf_search_q(queries, centroids, store_q, scales, mask,
                        nprobe=nprobe, block_q=block_q, impl=impl)
    from repro.index.quant import quantized_scores
    q = np.asarray(queries, np.float32)
    q = q / np.maximum(np.linalg.norm(q, axis=-1, keepdims=True), 1e-9)
    ds = quantized_scores(q, np.asarray(delta_q), np.asarray(delta_scales))
    return np.concatenate([s, np.asarray(ds, np.float32)], axis=1), p


def _n_devices() -> int:
    return len(jax.devices())


def _resolve_sharded(impl: str | None, n_shards: int) -> tuple[str, int]:
    """Sharded ops dispatch -> (mode, shards that run).

    * ``"shard_map"``: more than one device and more than one shard — the
      shards run on real devices (count clamped to the device count), with
      Pallas bodies on TPU;
    * ``"ref"`` (explicit, or "auto" off-TPU on one device): the jnp
      reference *simulates* the requested partitioning with identical
      numerics, which keeps single-device CPU tests meaningful;
    * ``"pallas"`` / ``"interpret"`` (explicit, or "auto" on a one-chip TPU
      host): the single-device kernel path — the shard contract makes its
      results identical to the sharded ones."""
    impl = impl or DEFAULT_IMPL
    if impl == "ref":
        return "ref", n_shards
    n_dev = _n_devices()
    if n_dev > 1 and n_shards > 1:
        return "shard_map", min(n_shards, n_dev)
    mode = _resolve("auto") if impl in ("auto", "shard_map") else impl
    return mode, (n_shards if mode == "ref" else 1)


def effective_shards(shards: int) -> int:
    """The shard count the auto dispatch will actually run: clamped to the
    device count on the shard_map path, the requested count on the jnp
    simulation path, 1 on the single-device kernel path.  Index layers use
    this so per-shard accounting (``scored_vectors_per_shard``) describes
    the real work split, not the requested layout."""
    _, n = _resolve_sharded(None, shards)
    return n


def sharded_search(queries, corpus, k: int, *, shards: int,
                   normalize: bool = True, impl: str | None = None
                   ) -> tuple[np.ndarray, np.ndarray]:
    """Device-sharded exact top-k: corpus rows split across ``shards``
    devices via ``shard_map`` (per-shard similarity kernel + local top-k),
    per-shard candidates merged on host.  Lossless — the merged top-k is
    identical to a full exact scan (``ref.sharded_search_ref`` is the jnp
    contract).  -> (scores [nq, k], global idx [nq, k])."""
    mode, shards = _resolve_sharded(impl, shards)
    with _kernel_span("sharded_search", mode, nq=len(queries),
                      nc=len(corpus), shards=shards) as sp:
        if mode == "ref":
            s, i = ref.sharded_search_ref(jnp.asarray(queries),
                                          jnp.asarray(corpus), k,
                                          max(shards, 1), normalize=normalize)
            s = _ready(s, sp)
            return np.asarray(s), np.asarray(i, np.int64)
        if mode == "shard_map":
            vals, idx = _sim.sharded_similarity_topk(
                queries, corpus, k, n_shards=shards, normalize=normalize,
                use_pallas=_on_tpu())
        else:  # one device: the similarity kernel over the whole corpus
            sims = similarity(queries, corpus, normalize=normalize, impl=mode)
            vals, idx = jax.lax.top_k(jnp.asarray(sims),
                                      min(k, sims.shape[1]))
        s, i = ref.shard_topk_merge(vals, idx, k)
        s = _ready(s, sp)
        return np.asarray(s), np.asarray(i, np.int64)


def sharded_ivf_search(queries, centroids, store, mask, *, nprobe: int,
                       shards: int, block_q: int = 8, impl: str | None = None
                       ) -> tuple[np.ndarray, np.ndarray]:
    """Device-sharded IVF retrieval: cluster tiles partitioned across
    ``shards`` devices, global probe selection, per-device masked scan of
    the locally-owned probed clusters combined with one pmax.  The score
    plane (and thus the downstream top-k) is identical to :func:`ivf_search`
    — sharding redistributes scan work, never results.  jnp contract:
    ``ref.sharded_ivf_search_ref``."""
    mode, shards = _resolve_sharded(impl, shards)
    with _kernel_span("sharded_ivf_search", mode, nq=len(queries),
                      nprobe=nprobe, shards=shards) as sp:
        if mode == "ref":
            s, p = ref.sharded_ivf_search_ref(
                jnp.asarray(queries), jnp.asarray(centroids),
                jnp.asarray(store), jnp.asarray(mask), nprobe=nprobe,
                n_shards=max(shards, 1), block_q=block_q)
        elif mode == "shard_map":
            s, p = _ivf.sharded_ivf_search(
                queries, centroids, store, mask, nprobe=nprobe,
                n_shards=shards, block_q=block_q, use_pallas=_on_tpu())
        else:
            s, p = ivf_search(queries, centroids, store, mask, nprobe=nprobe,
                              block_q=block_q, impl=mode)
        s = _ready(s, sp)
        return np.asarray(s), np.asarray(p)


def sharded_ivf_search_q(queries, centroids, store_q, scales, mask, *,
                         nprobe: int, shards: int, block_q: int = 8,
                         impl: str | None = None
                         ) -> tuple[np.ndarray, np.ndarray]:
    """Device-sharded quantized IVF retrieval: int8 cluster tiles + their
    scale rows partitioned across ``shards`` devices, global probe
    selection, per-device fused dequantize+scan of the locally-owned probed
    clusters combined with one pmax.  Score plane identical to
    :func:`ivf_search_q` — sharding redistributes scan bytes, never
    results.  jnp contract: ``ref.sharded_ivf_search_q_ref``."""
    mode, shards = _resolve_sharded(impl, shards)
    with _kernel_span("sharded_ivf_search_q", mode, nq=len(queries),
                      nprobe=nprobe, shards=shards) as sp:
        if mode == "ref":
            s, p = ref.sharded_ivf_search_q_ref(
                jnp.asarray(queries), jnp.asarray(centroids),
                jnp.asarray(store_q, jnp.int8), jnp.asarray(scales),
                jnp.asarray(mask), nprobe=nprobe, n_shards=max(shards, 1),
                block_q=block_q)
        elif mode == "shard_map":
            s, p = _ivfq.sharded_ivf_search_q(
                queries, centroids, store_q, scales, mask, nprobe=nprobe,
                n_shards=shards, block_q=block_q, use_pallas=_on_tpu())
        else:
            s, p = ivf_search_q(queries, centroids, store_q, scales, mask,
                                nprobe=nprobe, block_q=block_q, impl=mode)
        s = _ready(s, sp)
        return np.asarray(s), np.asarray(p)


def rmsnorm(x, scale, *, eps: float = 1e-5, impl: str | None = None, **kw):
    mode = _resolve(impl)
    if mode == "ref":
        return ref.rmsnorm_ref(x, scale, eps=eps)
    return _rn.rmsnorm(x, scale, eps=eps, interpret=(mode == "interpret"), **kw)
