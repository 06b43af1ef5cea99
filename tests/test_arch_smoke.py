"""Per-architecture smoke tests (assignment requirement): reduced same-family
configs run a real forward + train step on CPU, asserting shapes and no NaNs;
prefill/decode consistency ties the serving path to the training path.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, SHAPES, cell_applicable, get_config, get_smoke, input_specs
from repro.models import registry
from repro.train import optimizer as opt
from repro.train.trainstep import make_train_step

ALL_ARCHS = sorted(ARCHS)


def _extra(cfg, key, b):
    extra = {}
    if cfg.family == "vlm":
        extra["image_embeds"] = jax.random.normal(key, (b, cfg.num_image_tokens, cfg.d_model), jnp.float32)
    if cfg.family == "audio":
        extra["audio_frames"] = jax.random.normal(key, (b, cfg.num_audio_frames, cfg.d_model), jnp.float32)
    return extra or None


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_forward_and_decode_consistency(arch):
    cfg = get_smoke(arch)
    if cfg.is_moe:
        cfg = cfg.with_(capacity_factor=8.0)  # no drops -> decode must match
    key = jax.random.PRNGKey(0)
    params = registry.init_params(cfg, key)
    B, S = 2, 24
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    extra = _extra(cfg, key, B)

    logits, aux = registry.forward(cfg, params, tokens, extra=extra)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert not np.isnan(np.asarray(logits)).any()

    cache = registry.init_cache(cfg, B, S + 4)
    lp, cache = registry.prefill(cfg, params, tokens[:, :S - 1], cache, extra=extra)
    assert np.allclose(np.asarray(lp), np.asarray(logits[:, :S - 1]), atol=1e-3)
    ld, _ = registry.decode_step(cfg, params, tokens[:, S - 1:S], cache,
                                 jnp.int32(S - 1), extra=extra)
    assert np.allclose(np.asarray(ld[:, 0]), np.asarray(logits[:, S - 1]), atol=1e-3)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_forward_last_positions_match_full_forward(arch):
    """``forward(last=pos)`` (the scoring path) unembeds only each row's
    ``pos`` and equals the full forward's logits there, in every family."""
    cfg = get_smoke(arch)
    key = jax.random.PRNGKey(2)
    params = registry.init_params(cfg, key)
    B, S = 3, 20
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    extra = _extra(cfg, key, B)
    pos = jnp.asarray([0, 11, S - 1], jnp.int32)
    full, _ = registry.forward(cfg, params, tokens, extra=extra)
    got, _ = registry.forward(cfg, params, tokens, extra=extra, last=pos)
    assert got.shape == (B, 1, cfg.vocab_size)
    np.testing.assert_allclose(np.asarray(got[:, 0]),
                               np.asarray(full[jnp.arange(B), pos]), atol=1e-4)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_param_draw_matches_eager_ops(arch):
    """Each leaf is drawn as one jitted program; its values are bit for bit
    those of the eager ``(scale * normal).astype(dtype)`` it replaces."""
    specs = registry.param_specs(get_smoke(arch))
    paths = sorted(specs)
    for path, key in zip(paths, jax.random.split(jax.random.PRNGKey(4), len(paths))):
        spec = specs[path]
        if spec.init not in ("normal", "scaled"):
            continue
        fan_in = spec.shape[0] if len(spec.shape) == 1 else int(np.prod(spec.shape[:-1]))
        scale = (spec.init_scale * 0.02 if spec.init == "normal"
                 else spec.init_scale / math.sqrt(max(fan_in, 1)))
        eager = (scale * jax.random.normal(key, spec.shape, jnp.float32)).astype(spec.dtype)
        np.testing.assert_array_equal(np.asarray(spec.materialize(key), np.float32),
                                      np.asarray(eager, np.float32), err_msg=str(path))


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_train_step(arch):
    cfg = get_smoke(arch)
    key = jax.random.PRNGKey(1)
    params = registry.init_params(cfg, key)
    ocfg = opt.OptimizerConfig(total_steps=2, warmup_steps=1)
    state = opt.init_state(params, ocfg)
    step = jax.jit(make_train_step(cfg, ocfg, microbatches=2))
    B, S = 4, 16
    batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
             "labels": jax.random.randint(key, (B, S), 0, cfg.vocab_size)}
    extra = _extra(cfg, key, B)
    if extra:
        batch.update(extra)
    params2, state2, metrics = step(params, state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    # params actually changed
    delta = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
                for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params2)))
    assert delta > 0


def test_full_configs_match_assignment():
    """The full configs carry the exact assigned hyperparameters."""
    spec = {
        "llama-3.2-vision-11b": (40, 4096, 32, 8, 14336, 128256),
        "mixtral-8x22b": (56, 6144, 48, 8, 16384, 32768),
        "llama4-maverick-400b-a17b": (48, 5120, 40, 8, 8192, 202048),
        "qwen1.5-4b": (40, 2560, 20, 20, 6912, 151936),
        "llama3.2-3b": (28, 3072, 24, 8, 8192, 128256),
        "deepseek-7b": (30, 4096, 32, 32, 11008, 102400),
        "qwen2-72b": (80, 8192, 64, 8, 29568, 152064),
        "xlstm-125m": (12, 768, 4, 4, 0, 50304),
        "zamba2-7b": (81, 3584, 32, 32, 14336, 32000),
        "whisper-small": (12, 768, 12, 12, 3072, 51865),
    }
    for name, (L, d, h, hk, ff, v) in spec.items():
        cfg = get_config(name)
        assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                cfg.d_ff, cfg.vocab_size) == (L, d, h, hk, ff, v), name


def test_param_counts_near_nameplate():
    approx = {"qwen2-72b": 72e9, "mixtral-8x22b": 141e9,
              "llama4-maverick-400b-a17b": 400e9, "llama3.2-3b": 3.2e9,
              "deepseek-7b": 7e9, "xlstm-125m": 125e6}
    for name, n in approx.items():
        got = get_config(name).param_count()
        assert 0.65 * n < got < 1.35 * n, (name, got, n)


def test_shape_cells_and_skips():
    cells = 0
    skips = []
    for arch in ALL_ARCHS:
        for shape in SHAPES.values():
            ok, why = cell_applicable(get_config(arch), shape)
            cells += 1
            if not ok:
                skips.append((arch, shape.name))
    assert cells == 40
    assert all(s == "long_500k" for _, s in skips)
    assert {a for a, _ in skips} == set(ALL_ARCHS) - {"xlstm-125m", "zamba2-7b"}


def test_input_specs_cover_modalities():
    cfg = get_config("whisper-small")
    specs = input_specs(cfg, SHAPES["train_4k"])
    assert specs["audio_frames"].shape == (256, 1500, 768)
    cfg = get_config("llama-3.2-vision-11b")
    specs = input_specs(cfg, SHAPES["decode_32k"])
    assert specs["tokens"].shape == (128, 1)
    assert "cache_len" in specs
