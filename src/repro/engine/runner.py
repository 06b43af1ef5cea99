"""Jitted model-step closures for the inference engine.

One ``ModelRunner`` owns params + jitted prefill/decode functions.  Prefill is
bucketed by prompt length (power-of-two padding) so the number of distinct
compilations stays logarithmic; decode is a single compilation over the full
slot batch with per-slot cache lengths.
"""
from __future__ import annotations

import functools
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import registry
from repro.obs import trace as _trace


def _bucket(n: int, minimum: int = 16) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


class ModelRunner:
    """Owns params and compiled steps for one model."""

    def __init__(self, cfg: ModelConfig, params, *, max_slots: int, max_seq: int):
        self.cfg = cfg
        self.params = params
        self.max_slots = max_slots
        self.max_seq = max_seq
        self.cache = registry.init_cache(cfg, max_slots, max_seq)

        @jax.jit
        def _decode(params, tokens, cache, lens):
            logits, cache = registry.decode_step(cfg, params, tokens, cache, lens)
            return logits[:, 0].astype(jnp.float32), cache

        self._decode = _decode

        @functools.partial(jax.jit, static_argnames=("bucket",))
        def _prefill(params, tokens, cache1, true_len, extra, bucket):
            logits, cache1 = registry.prefill(cfg, params, tokens, cache1, extra=extra or None)
            last = logits[0, true_len - 1].astype(jnp.float32)
            return last, cache1

        self._prefill = _prefill

        @jax.jit
        def _write_slot(cache, cache1, slot):
            return jax.tree.map(lambda g, p: g.at[:, slot].set(p[:, 0].astype(g.dtype)), cache, cache1)

        self._write_slot = _write_slot

    # -- prefill one request into a slot --------------------------------
    def prefill_into_slot(self, tokens: np.ndarray, slot: int, extra: dict | None = None):
        """tokens: [T] int32. Returns last-token logits [V]."""
        t = int(tokens.shape[0])
        assert t <= self.max_seq, f"prompt {t} > max_seq {self.max_seq}"
        bucket = min(_bucket(t), self.max_seq)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :t] = tokens
        cache1 = registry.init_cache(self.cfg, 1, self.max_seq)
        logits, cache1 = self._prefill(self.params, jnp.asarray(padded), cache1,
                                       jnp.int32(t), extra, bucket)
        self.cache = self._write_slot(self.cache, cache1, jnp.int32(slot))
        return _fetch(logits)

    # -- one decode step over all slots ----------------------------------
    def decode(self, tokens: np.ndarray, lens: np.ndarray):
        """tokens: [slots] int32 (next input per slot); lens: [slots] int32."""
        logits, self.cache = self._decode(self.params, jnp.asarray(tokens[:, None]),
                                          self.cache, jnp.asarray(lens))
        return _fetch(logits)

    # -- whole-sequence scoring (no cache) -------------------------------
    @functools.cached_property
    def _score(self):
        @jax.jit
        def score(params, tokens, last, extra):
            logits, _ = registry.forward(self.cfg, params, tokens, extra=extra or None,
                                         last=last)
            return jax.nn.log_softmax(logits[:, 0].astype(jnp.float32), axis=-1)

        return score

    def logprobs(self, tokens: np.ndarray, last: np.ndarray,
                 extra: dict | None = None) -> np.ndarray:
        """tokens: [B,T], last: [B] position of each row's last real token
        -> log-probs [B,V] of the token following it.  Causal attention
        makes right padding invisible to those positions, so callers pad T
        to a bucket without changing the result."""
        return _fetch(self._score(self.params, jnp.asarray(tokens),
                                  jnp.asarray(last, jnp.int32), extra))


def _fetch(x) -> np.ndarray:
    """Bring a step's output to the host: the one place a step waits for
    the device, under its own span."""
    with _trace.span("runner/fetch", "runner", event="repro.runner.fetch"):
        return np.asarray(x)
