"""Compile the main path's kernels and the oracle's scoring step for one
described TPU v5e chip, with no chip attached: what the chip's compiler
refuses (block layouts, fast-memory limits, programs that do not fit) fails
here.  Nothing runs, so nothing here says anything about results or times.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library."""
import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

D, L, KC, BQ, SLOTS, NB = 384, 256, 512, 8, 16, 4   # E5-small width, IVF tiles


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: a
    compile for a described chip is written to it but cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _struct(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_case(name, sh):
    from repro.kernels import ivf_scan, ivf_scan_q, similarity
    f32, probes = jnp.float32, _struct((NB, SLOTS), jnp.int32, sh)
    queries = _struct((NB * BQ, D), f32, sh)
    if name == "similarity":
        return (lambda q, c: similarity.similarity(q, c),
                (_struct((64, D), f32, sh), _struct((4096, D), f32, sh)))
    if name == "cluster_scan":
        return (lambda q, s, m, p: ivf_scan.cluster_scan(
                    q, s, m, p, block_q=BQ, normalize=False),
                (queries, _struct((KC, L, D), f32, sh), _struct((KC, L), f32, sh),
                 probes))
    return (lambda q, s, sc, m, p: ivf_scan_q.cluster_scan_q(
                q, s, sc, m, p, block_q=BQ, normalize=False),
            (queries, _struct((KC, L, D), jnp.int8, sh), _struct((KC, L), f32, sh),
             _struct((KC, L), f32, sh), probes))


@pytest.mark.parametrize("name", ["similarity", "cluster_scan", "cluster_scan_q"])
def test_retrieval_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_case(name, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()   # the Pallas kernel, not XLA


def test_oracle_scoring_step_returns_one_row_per_prompt(one_chip):
    """Llama-3.2-3B at its published widths, depth cut to one layer: the
    scoring step's output is the [B, V] f32 log-softmax at each prompt's
    last token, never the [B, T, V] plane."""
    from repro.configs.llama3_2_3b import CONFIG
    from repro.engine.runner import ModelRunner
    from repro.models import registry

    cfg = CONFIG.with_(num_layers=1)
    runner = ModelRunner(cfg, None, max_slots=1, max_seq=16)
    params = jax.tree.map(lambda s: _struct(s.shape, s.dtype, one_chip),
                          registry.param_structs_tree(cfg))
    b, t = 32, 512
    compiled = runner._score.lower(params, _struct((b, t), jnp.int32, one_chip),
                                   _struct((b,), jnp.int32, one_chip),
                                   None).compile()
    out_bytes = compiled.memory_analysis().output_size_in_bytes
    assert out_bytes == b * cfg.vocab_size * 4
    assert out_bytes < b * t * cfg.vocab_size * 4
