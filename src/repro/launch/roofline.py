"""Roofline-term derivation from compiled dry-run artifacts.

Terms (per step, across the whole mesh):
    compute    = HLO_FLOPs_global     / (chips * PEAK_FLOPS)
    memory     = HLO_bytes_global     / (chips * HBM_BW)
    collective = collective_bytes_dev / ICI_BW          (per-device wire bytes)

``cost_analysis`` on the SPMD-compiled module reports *per-device* flops /
bytes (verified empirically in tests/test_roofline.py); we multiply by chip
count for the global terms.  Collective bytes are not in cost_analysis: we
parse the optimized HLO text, resolve each collective's operand shapes, and
sum operand bytes per device (ring transfer cost ~= operand bytes x (n-1)/n
for all-gather/reduce-scatter; all-reduce counted twice — see
``_COLLECTIVE_WIRE_FACTOR``).

Hardware constants: TPU v5e.
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Any


PEAK_FLOPS = 197e12       # bf16 per chip
HBM_BW = 819e9            # bytes/s per chip
ICI_BW = 50e9             # bytes/s per link (per-device injection, ~one link)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16, "token": 0, "s4": 1, "u4": 1, "f8e4m3fn": 1,
    "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?(%?[\w.\-]+)\s*=\s*(\([^)]*\)|\S+)\s")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# approximate wire bytes per device = factor * operand bytes
_COLLECTIVE_WIRE_FACTOR = {
    "all-gather": 1.0,        # operand is the local shard; ship it around the ring
    "reduce-scatter": 1.0,
    "all-reduce": 2.0,        # RS + AG
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}


def xla_cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()`` as a dict ({} where the backend has none)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:  # pragma: no cover - backend without cost analysis
        return {}
    return ca or {}


def shape_bytes(type_str: str) -> int:
    """'bf16[8,128,4096]{...}' -> bytes. Tuples '(f32[..], f32[..])' summed."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict[str, float]:
    """Per-device wire bytes by collective kind, parsed from optimized HLO."""
    # first pass: map instruction name -> result type string
    types: dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if m:
            types[m.group(1).lstrip("%")] = m.group(2)

    out: dict[str, float] = {k: 0.0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if not m:
            continue
        body = line[m.end(2):] if False else line
        for kind in _COLLECTIVES:
            # match e.g. " = bf16[...] all-gather(%operand, ...)"
            km = re.search(rf"\s{re.escape(kind)}(?:-start|-done)?\(([^)]*)\)", body)
            if km is None:
                continue
            if f"{kind}-done" in body:   # -done carries no new wire traffic
                continue
            ops = [o.strip().lstrip("%") for o in km.group(1).split(",")]
            b = 0
            for op in ops:
                op = op.split(" ")[0]
                if op in types:
                    b += shape_bytes(types[op])
                else:  # inline-typed operand e.g. "bf16[8,16]{1,0} %fusion.3"
                    b += shape_bytes(op)
            out[kind] += b * _COLLECTIVE_WIRE_FACTOR[kind]
            break
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return out


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops_per_dev: float
    hlo_bytes_per_dev: float
    coll_bytes_per_dev: float
    model_flops: float            # 6*N*D (active) for the step's tokens
    mem_per_dev: dict[str, float]
    coll_breakdown: dict[str, float]
    scopes: dict[str, list] = dataclasses.field(default_factory=dict)
    seq_len: int = 0

    @property
    def t_compute(self) -> float:
        return self.hlo_flops_per_dev / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes_per_dev / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.coll_bytes_per_dev / ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def step_time(self) -> float:
        """Roofline step time = max of the three terms (perfect overlap)."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / global HLO flops — remat/padding/capacity waste."""
        total = self.hlo_flops_per_dev * self.chips
        return self.model_flops / total if total else 0.0

    @property
    def mfu(self) -> float:
        """Model-flops utilization at the roofline step time."""
        return self.model_flops / (self.chips * PEAK_FLOPS * self.step_time) \
            if self.step_time else 0.0

    # -- Pallas-flash adjusted memory term --------------------------------
    # The XLA (non-kernel) attention path materializes S^2 f32 score chains
    # in HBM; the Pallas flash kernel (repro.kernels.flash_attention) keeps
    # them in VMEM.  Adjusted traffic replaces the attn_core scope bytes with
    # the analytic flash traffic  F * (2/Bq + 2/S)  (KV re-reads per q-block
    # of Bq=1024 + q/o streams); see DESIGN.md and EXPERIMENTS.md §Roofline.
    @property
    def flash_adjusted_bytes(self) -> float:
        if "attn_core" not in self.scopes:
            return self.hlo_bytes_per_dev
        f_attn, b_attn = self.scopes["attn_core"]
        flash = f_attn * (2.0 / 1024.0 + (2.0 / self.seq_len if self.seq_len else 0.0))
        return self.hlo_bytes_per_dev - b_attn + flash

    @property
    def t_memory_flash(self) -> float:
        return self.flash_adjusted_bytes / HBM_BW

    @property
    def step_time_flash(self) -> float:
        return max(self.t_compute, self.t_memory_flash, self.t_collective)

    @property
    def mfu_flash(self) -> float:
        return self.model_flops / (self.chips * PEAK_FLOPS * self.step_time_flash) \
            if self.step_time_flash else 0.0

    def to_json(self) -> dict[str, Any]:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips,
            "hlo_flops_per_dev": self.hlo_flops_per_dev,
            "hlo_bytes_per_dev": self.hlo_bytes_per_dev,
            "coll_bytes_per_dev": self.coll_bytes_per_dev,
            "model_flops": self.model_flops,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck, "step_time_s": self.step_time,
            "useful_flops_ratio": self.useful_flops_ratio, "mfu": self.mfu,
            "t_memory_flash_s": self.t_memory_flash,
            "step_time_flash_s": self.step_time_flash, "mfu_flash": self.mfu_flash,
            "mem_per_dev": self.mem_per_dev,
            "coll_breakdown": self.coll_breakdown,
            "scopes": self.scopes,
        }


def model_flops_for_cell(cfg, cell) -> float:
    """6*N_active*D for train, 2*N_active*D for inference fwd (per step)."""
    n_active = cfg.active_param_count()
    if cell.kind == "train":
        tokens = cell.global_batch * cell.seq_len
        return 6.0 * n_active * tokens
    if cell.kind == "prefill":
        tokens = cell.global_batch * cell.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * cell.global_batch


def analyse(compiled, lowered_text: str, *, arch: str, shape: str, mesh_name: str,
            chips: int, model_flops: float, seq_len: int = 0) -> Roofline:
    # Static HLO walk: XLA's cost_analysis does not multiply while-loop trip
    # counts (scan-over-layers would be undercounted ~100x) — see
    # hlo_analysis.py and tests/test_roofline.py.
    from repro.launch.hlo_analysis import analyze_text
    costs = analyze_text(lowered_text)
    flops = costs.flops
    byts = costs.bytes
    try:
        ma = compiled.memory_analysis()
        mem = {
            "argument": float(getattr(ma, "argument_size_in_bytes", 0)),
            "output": float(getattr(ma, "output_size_in_bytes", 0)),
            "temp": float(getattr(ma, "temp_size_in_bytes", 0)),
            "alias": float(getattr(ma, "alias_size_in_bytes", 0)),
        }
        mem["peak"] = mem["argument"] + mem["output"] + mem["temp"] - mem["alias"]
    except Exception:  # pragma: no cover
        mem = {}
    coll = dict(costs.coll)
    coll.setdefault("total", 0.0)
    mem["cpu_upcast_bytes_excluded"] = costs.cpu_upcast_bytes
    # cross-check fields (known-undercounting XLA numbers, kept for reference)
    ca = xla_cost_analysis(compiled)
    mem["xla_flops_nocount_loops"] = float(ca.get("flops", 0.0))
    return Roofline(arch=arch, shape=shape, mesh=mesh_name, chips=chips,
                    hlo_flops_per_dev=flops, hlo_bytes_per_dev=byts,
                    coll_bytes_per_dev=coll["total"], model_flops=model_flops,
                    mem_per_dev=mem, coll_breakdown=coll, scopes=dict(costs.scopes),
                    seq_len=seq_len)
