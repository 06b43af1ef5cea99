"""Random weights for one configuration, drawn on the device in one jitted
call from the run's seed, in the dtype they are served in.

The tree has the key layout the system takes for the configuration's
architecture (its module's ``leaf_plan``); ``check_layout`` holds it against
the system's own parameter table before the weights are handed over, so a
layout change fails loudly instead of serving the wrong tensors.  The same
tree feeds the plain reference."""
from __future__ import annotations

import functools
import statistics

import jax
import jax.numpy as jnp
import numpy as np

from bench import configs

TRUE_ID, FALSE_ID = 259, 260      # the byte tokenizer's label tokens


def leaf_plan(cfg: dict) -> dict:
    """path -> (shape, dtype, init, std) of every parameter, from the
    configuration's architecture module.  ``init`` is ``normal`` (std * N),
    ``one_plus`` (1 + std * N) or ``zeros``."""
    return configs.architecture(cfg).leaf_plan(cfg)


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = v
    return out


@functools.partial(jax.jit, static_argnames=("items",))
def _draw(key, *, items):
    keys = jax.random.split(key, len(items))
    flat = {}
    for k, (path, (shape, dt, init, std)) in zip(keys, items):
        dt = jnp.dtype(dt)
        if init == "zeros":
            x = jnp.zeros(shape, dt)
        else:
            x = jax.random.normal(k, shape, dt) * jnp.asarray(std, dt)
            if init == "one_plus":
                x = x + jnp.asarray(1, dt)
        flat[path] = x
    return _nest(flat)


def seed32(seed: int, salt: int = 0) -> int:
    """A 32-bit seed from any whole number (the run's seed may exceed 32 bits)."""
    return int(np.random.SeedSequence([abs(int(seed)), salt]).generate_state(1)[0])


def draw(cfg: dict, seed: int, *, salt: int = 0) -> dict:
    """The configuration's weights as one jitted program on the default device."""
    key = jax.random.key(seed32(seed, salt), impl="rbg")
    return _draw(key, items=tuple(sorted(leaf_plan(cfg).items())))


def label_direction(hidden: np.ndarray, *, selectivity: float, margin_sd: float) -> np.ndarray:
    """The vector ``delta`` [d] that makes the label margin
    ``logit(<true>) - logit(<false>) = h . delta`` of a row with final hidden
    state ``h`` vary from row to row, with about ``selectivity`` of the rows
    above 0 and a spread of ``margin_sd`` nats, judged on the rows
    ``hidden`` [n, d].

    Random weights give every prompt nearly the same final hidden state, so
    two independently drawn label columns say true to nearly every row or to
    nearly none.  ``delta`` points along the widest direction of variation
    of the first half of the rows (their first principal component, less
    any part along the rows' mean, which they all share); the second half
    sets its length and the multiple of the rows' mean that moves the cut to
    the selectivity's normal quantile.  Fitting the direction and its scale
    on different rows keeps the spread on fresh rows what it is on these."""
    h = np.asarray(hidden, np.float64)
    half = len(h) // 2
    fit, judge = h[:half], h[half:]
    mu = h.mean(0)
    _, _, vt = np.linalg.svd(fit - fit.mean(0), full_matrices=False)
    u = vt[0] - (vt[0] @ mu) / (mu @ mu) * mu      # none of the shared part
    u = u * (margin_sd / (judge @ u).std())
    shift = mu / (mu @ mu)                           # h . shift is about 1 on every row
    cut = float(np.mean(judge @ u)) + margin_sd * statistics.NormalDist().inv_cdf(
        1.0 - selectivity)
    return u - cut / float(np.mean(judge @ shift)) * shift


@functools.partial(jax.jit, donate_argnums=0)
def _set_false_column(head, col):
    return head.at[:, FALSE_ID].set(col.astype(head.dtype))


def set_label_margin(weights: dict, delta: np.ndarray) -> dict:
    """The head with the <false> column redrawn as <true>'s minus ``delta``
    (an untied head; the tree's old head is donated)."""
    head = weights["out"]["head"]
    col = head[:, TRUE_ID].astype(jnp.float32) - jnp.asarray(delta, jnp.float32)
    return dict(weights, out={"head": _set_false_column(head, col)})


def check_layout(weights: dict, specs: dict) -> None:
    """Raise unless the drawn tree has exactly the system's parameter paths,
    shapes and dtypes (``specs``: path -> object with ``shape``/``dtype``)."""
    flat = {}

    def walk(node, prefix):
        for k, v in node.items():
            if isinstance(v, dict):
                walk(v, prefix + (k,))
            else:
                flat[prefix + (k,)] = v

    walk(weights, ())
    got = {p: (tuple(v.shape), jnp.dtype(v.dtype)) for p, v in flat.items()}
    want = {p: (tuple(s.shape), jnp.dtype(s.dtype)) for p, s in specs.items()}
    if got != want:
        diff = sorted(set(got.items()) ^ set(want.items()))
        raise ValueError(f"drawn weights do not match the system's layout: {diff[:6]}")
