"""Configuration files and the modules they name.

Every configuration (and every ``proxy`` block inside one) names two modules
beside this file: ``architecture``, the system's side of that architecture
(its ``ModelConfig``, weight layout and operation counts; the contract is in
``dense.py``), and ``reference``, its plain float32 forward pass.  A new
architecture adds its own pair of modules and names them in its files; no
harness code names one."""
from __future__ import annotations

import importlib


def _named(cfg: dict, key: str):
    name = cfg.get(key)
    if not isinstance(name, str) or not name.isidentifier():
        raise KeyError(f"configuration {cfg.get('name')!r} names no {key!r} module "
                       f"(got {name!r}); each configuration and proxy block names one")
    return importlib.import_module(f"{__name__}.{name}")


def architecture(cfg: dict):
    """The module the configuration names under ``architecture``."""
    return _named(cfg, "architecture")


def reference(cfg: dict):
    """The plain reference module the configuration names under ``reference``."""
    return _named(cfg, "reference")
