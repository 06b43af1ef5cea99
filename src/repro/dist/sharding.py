"""Logical-axis -> mesh-axis sharding rules (GSPMD side of the dist layer).

Models name every tensor dimension with a *logical* axis ("embed_in",
"kv_heads", "batch", ...; see ``repro.common.ParamSpec``).  A rule table maps
each logical axis to an ordered tuple of *candidate* mesh axes, and
``resolve_pspec`` turns (shape, logical axes, mesh, rules) into a concrete
``PartitionSpec`` under two invariants:

  * divisibility fallback — a mesh axis is only taken while the accumulated
    shard count divides the dimension size (a 6-head tensor on a 4-wide
    ``model`` axis stays replicated rather than erroring);
  * each mesh axis is used at most once per spec, first dimension wins
    (``batch`` grabbing ``data`` leaves ``kv_seq`` only ``model``).

``activation_rules`` installs a (mesh, rules) context consumed by
``shard_activation`` inside model code — the models never mention mesh axes.
"""
from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

_ctx = threading.local()


# ---------------------------------------------------------------------------
# Rule tables
# ---------------------------------------------------------------------------

# Logical axis -> ordered candidate mesh axes.  Missing / empty -> replicated.
_TRAIN_RULES = {
    # parameter axes: FSDP-style over "data", tensor-parallel over "model"
    "embed_in": ("data",),
    "embed_out": ("data",),
    "embed": ("data",),
    "vocab": ("model",),
    "mlp": ("model",),
    "mlp_out": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "experts": ("model",),
    "experts_in": ("data",),
    "layers": ("pod",),
    # activation axes
    "batch": ("pod", "data"),
    "seq_act": ("model",),
    "embed_act": ("model",),
    "kv_seq": ("data", "model"),
    "frames": (),
    "seq": (),
    "qkv": (),
    "qkv_in": (),
}

# Serving with weights replicated over "data" (throughput replicas); only the
# head-ish axes are tensor-parallel and the KV cache is context-parallel over
# "model" (kv_seq listed before kv_heads so the sequence dim wins the axis).
_SERVE_REPLICATED_RULES = {
    "vocab": ("model",),
    "mlp": ("model",),
    "mlp_out": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "experts": ("model",),
    "batch": ("pod", "data"),
    "kv_seq": ("model",),
    "seq_act": (),
    "embed_act": (),
}

RULE_TABLES: dict[str, dict[str, tuple[str, ...]]] = {
    "default": _TRAIN_RULES,
    "serve_replicated": _SERVE_REPLICATED_RULES,
}


def _rules_table(rules) -> dict:
    return RULE_TABLES[rules] if isinstance(rules, str) else rules


def _mesh_sizes(mesh) -> dict[str, int]:
    shape = mesh.shape  # OrderedDict name -> size on Mesh and AbstractMesh
    return dict(shape)


# ---------------------------------------------------------------------------
# Resolution
# ---------------------------------------------------------------------------


def resolve_pspec(shape, axes, mesh, rules) -> P:
    """(shape, logical axes, mesh, rule table|name) -> PartitionSpec.

    Greedy per-dimension: walk each dimension's candidate mesh axes in rule
    order, taking an axis only if it exists on the mesh, is still unused in
    this spec, and the accumulated shard count keeps dividing the dimension.
    """
    table = _rules_table(rules)
    sizes = _mesh_sizes(mesh)
    used: set[str] = set()
    entries = []
    for dim, ax in zip(shape, axes):
        taken: list[str] = []
        prod = 1
        for cand in table.get(ax, ()) if ax is not None else ():
            if cand not in sizes or cand in used:
                continue
            if dim % (prod * sizes[cand]) != 0:
                continue
            taken.append(cand)
            prod *= sizes[cand]
        used.update(taken)
        entries.append(None if not taken else taken[0] if len(taken) == 1 else tuple(taken))
    return P(*entries)


def spec_shardings(specs, mesh, rules="default"):
    """SpecTree {path: ParamSpec} -> nested tree of NamedSharding."""
    from repro.common import unflatten
    table = _rules_table(rules)
    return unflatten({
        path: NamedSharding(mesh, resolve_pspec(s.shape, s.axes, mesh, table))
        for path, s in specs.items()})


# ---------------------------------------------------------------------------
# Activation-sharding context
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def activation_rules(mesh, rules="default"):
    """Install (mesh, rules) so ``shard_activation`` constrains activations."""
    prev = getattr(_ctx, "cfg", None)
    _ctx.cfg = (mesh, _rules_table(rules))
    try:
        yield
    finally:
        _ctx.cfg = prev


def shard_activation(x, axes):
    """Sharding hint on an activation; identity when no rules are installed."""
    ctx = getattr(_ctx, "cfg", None)
    if ctx is None:
        return x
    mesh, rules = ctx
    spec = resolve_pspec(x.shape, axes, mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
