"""Common infrastructure: parameter specs, pytree path utilities, dtypes.

The central abstraction is the ParamSpec table: every model exposes
``param_specs(cfg) -> dict[path, ParamSpec]`` — a *shape-level* description of
its parameters (shape, dtype, logical axis names, initializer).  From one spec
table we derive:

  * materialized parameters (``init_params``) for smoke tests / real runs,
  * ``jax.ShapeDtypeStruct`` stand-ins for the multi-pod dry-run (a 400B-param
    model never has to be allocated on the CPU host),
  * ``NamedSharding``s via the logical-axis rule tables in ``repro.dist``.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np

Path = tuple[str, ...]

# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """Shape-level description of a single parameter tensor.

    ``axes`` names each dimension with a *logical* axis ("embed", "mlp",
    "heads", "vocab", "layers", ...).  Physical sharding is resolved later by
    rule tables (see ``repro.dist.sharding``); the model code never mentions
    mesh axes.
    """

    shape: tuple[int, ...]
    axes: tuple[str | None, ...]
    dtype: Any = jnp.bfloat16
    init: str = "normal"  # normal | zeros | ones | scaled (fan-in)
    init_scale: float = 1.0

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)

    def struct(self) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(self.shape, self.dtype)

    def materialize(self, key: jax.Array) -> jax.Array:
        if self.init == "zeros":
            return jnp.zeros(self.shape, self.dtype)
        if self.init == "ones":
            return jnp.ones(self.shape, self.dtype)
        if self.init == "normal":
            scale = self.init_scale * 0.02
        elif self.init == "scaled":  # fan-in scaled
            fan_in = self.shape[0] if len(self.shape) == 1 else int(np.prod(self.shape[:-1]))
            scale = self.init_scale / math.sqrt(max(fan_in, 1))
        else:  # pragma: no cover - guarded by tests
            raise ValueError(f"unknown init {self.init}")
        return _draw_normal(key, self.shape, jnp.dtype(self.dtype), jnp.float32(scale))


@functools.partial(jax.jit, static_argnums=(1, 2))
def _draw_normal(key, shape, dtype, scale):
    """``scale * N(0, 1)`` cast to ``dtype``, as one program: a leaf costs its
    float32 draw as a temporary beside its output, where the eager ops keep
    the draw, its scaled copy and the cast alive at once (5.6 GB for a
    28 x 3072 x 8192 stack).  The barrier keeps the multiply out of the
    draw's fusion, so the values are bit for bit those of the eager ops."""
    x = jax.lax.optimization_barrier(jax.random.normal(key, shape, jnp.float32))
    return (scale * x).astype(dtype)


SpecTree = dict[Path, ParamSpec]


def unflatten(flat: Mapping[Path, Any]) -> dict:
    """{(a,b,c): v} -> {a: {b: {c: v}}}."""
    out: dict = {}
    for path, value in flat.items():
        node = out
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = value
    return out


def flatten(tree: Mapping, prefix: Path = ()) -> dict[Path, Any]:
    out: dict[Path, Any] = {}
    for k, v in tree.items():
        p = prefix + (k,)
        if isinstance(v, Mapping):
            out.update(flatten(v, p))
        else:
            out[p] = v
    return out


def init_params(specs: SpecTree, key: jax.Array) -> dict:
    """Materialize a spec table into a nested param dict (deterministic)."""
    paths = sorted(specs.keys())
    keys = jax.random.split(key, max(len(paths), 1))
    flat = {p: specs[p].materialize(keys[i]) for i, p in enumerate(paths)}
    return unflatten(flat)


def param_structs(specs: SpecTree) -> dict:
    """ShapeDtypeStruct tree for dry-run lowering (no allocation)."""
    return unflatten({p: s.struct() for p, s in specs.items()})


def param_count(specs: SpecTree) -> int:
    return sum(int(np.prod(s.shape)) for s in specs.values())


def param_bytes(specs: SpecTree) -> int:
    return sum(int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize for s in specs.values())


def tree_size_bytes(tree: Any) -> int:
    leaves = jax.tree.leaves(tree)
    return sum(int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize for l in leaves)


def cast_tree(tree: Any, dtype: Any) -> Any:
    return jax.tree.map(lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


# ---------------------------------------------------------------------------
# Misc numeric helpers
# ---------------------------------------------------------------------------


def pad_to_multiple(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def pytree_allclose(a: Any, b: Any, **kw) -> bool:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(np.allclose(x, y, **kw) for x, y in zip(la, lb))
