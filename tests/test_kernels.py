"""Per-kernel validation: shape/dtype sweeps in interpret mode against the
pure-jnp oracles in repro.kernels.ref (assignment requirement)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("b,sq,sk,h,hk,hd", [
    (1, 64, 64, 4, 4, 64),
    (2, 128, 128, 4, 2, 64),
    (1, 100, 100, 8, 8, 32),     # non-multiple of block
    (2, 48, 48, 8, 2, 128),
    (1, 33, 33, 2, 1, 128),      # extreme GQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 16), (False, 0)])
def test_flash_attention_sweep(b, sq, sk, h, hk, hd, dtype, causal, window):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, sq, h, hd), dtype)
    k = jax.random.normal(ks[1], (b, sk, hk, hd), dtype)
    v = jax.random.normal(ks[2], (b, sk, hk, hd), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              impl="interpret", block_q=32, block_k=32)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("b,s,h,hk,hd", [
    (2, 64, 4, 4, 64), (3, 96, 8, 2, 64), (1, 130, 4, 1, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(b, s, h, hk, hd, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, 1, h, hd), dtype)
    k = jax.random.normal(ks[1], (b, s, hk, hd), dtype)
    v = jax.random.normal(ks[2], (b, s, hk, hd), dtype)
    lens = jnp.asarray(np.random.default_rng(0).integers(0, s, b))
    out = ops.decode_attention(q, k, v, lens, impl="interpret", block_k=32)
    want = ref.decode_attention_ref(q, k, v, lens)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("nq,nc,d", [(16, 16, 32), (37, 53, 48), (100, 7, 128)])
@pytest.mark.parametrize("normalize", [True, False])
def test_similarity_sweep(nq, nc, d, normalize, rng):
    q = rng.normal(size=(nq, d)).astype(np.float32)
    c = rng.normal(size=(nc, d)).astype(np.float32)
    out = ops.similarity(q, c, normalize=normalize, impl="interpret",
                         block_q=16, block_c=16)
    want = np.asarray(ref.similarity_ref(q, c, normalize=normalize))
    np.testing.assert_allclose(out, want, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("shape", [(8, 64), (3, 5, 128), (130, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(shape, dtype):
    x = jax.random.normal(jax.random.PRNGKey(2), shape, dtype)
    scale = jax.random.normal(jax.random.PRNGKey(3), shape[-1:], jnp.float32)
    out = ops.rmsnorm(x, scale, impl="interpret", block_rows=32)
    want = ref.rmsnorm_ref(x, scale)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


def test_ops_ref_dispatch_on_cpu():
    """impl='auto' must resolve to the jnp reference off-TPU."""
    q = np.eye(4, dtype=np.float32)
    s = ops.similarity(q, q, impl="auto")
    np.testing.assert_allclose(np.diag(s), np.ones(4), atol=1e-6)


def test_ops_device_query_errors_propagate(monkeypatch):
    """A failing device query raises: "auto" never silently picks the jnp
    reference because the platform could not be read."""
    def broken():
        raise RuntimeError("backend failed to initialize")

    monkeypatch.setattr(ops.jax, "devices", broken)
    with pytest.raises(RuntimeError, match="failed to initialize"):
        ops._resolve("auto")
    with pytest.raises(RuntimeError, match="failed to initialize"):
        ops.effective_shards(4)
