import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# NOTE: the two lines above MUST run before any jax-importing module — jax
# locks the device count at first init.  REPRO_DRYRUN_DEVICES overrides for
# small local debugging runs.
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_DRYRUN_DEVICES"])

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the real step function (train_step including the
optimizer update, or serve prefill/decode against a full-size KV cache),
lowers it with ShapeDtypeStruct stand-ins (no allocation — a 400B-param tree
never materializes), compiles for the production mesh, and records
memory_analysis / cost_analysis / the collective schedule into a JSON
artifact consumed by the roofline report (EXPERIMENTS.md §Dry-run/§Roofline).

    PYTHONPATH=src python -m repro.launch.dryrun --mesh single --arch qwen2-72b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import common
from repro.configs import ARCHS, SHAPES, cell_applicable, get_config, input_specs
from repro.dist import sharding as shd
from repro.launch import roofline
from repro.launch.mesh import make_production_mesh
from repro.models import registry
from repro.train import optimizer as opt
from repro.train.trainstep import make_train_step

# Per-arch dry-run knobs (memory-driven; see EXPERIMENTS.md §Dry-run notes).
# Default is NO gradient accumulation: with FSDP residency the weights are
# re-gathered once per microbatch, so fewer microbatches = less collective
# traffic; memory is held down by remat + model-sharded saved residuals
# (embed_act rule) instead.
TRAIN_MICROBATCHES: dict[str, int] = {}
DEFAULT_MICROBATCHES = 1
# 400B + f32 Adam does not fit 256x16GB; single-pod uses bf16 moments, no
# master (stochastic-rounding-free bf16 update; documented deviation).
OPT_OVERRIDES = {
    "llama4-maverick-400b-a17b": dict(state_dtype="bfloat16", use_master=False),
}
SERVE_RULES = {  # weights-replicated-over-data serving for <=72B; FSDP for 400B
    "llama4-maverick-400b-a17b": "default",
}


def _input_shardings(specs: dict, mesh, rules_name: str) -> dict:
    rules = shd.RULE_TABLES[rules_name]
    out = {}
    for name, s in specs.items():
        if name in ("tokens", "labels"):
            axes = ("batch", "seq")
        elif name in ("image_embeds", "audio_frames"):
            axes = ("batch", "frames", "embed_act")
        else:  # cache_len scalar
            axes = ()
        out[name] = NamedSharding(mesh, shd.resolve_pspec(s.shape, axes, mesh, rules))
    return out


def build_cell(arch: str, shape: str, mesh, *, rules: str | None = None,
               microbatches: int | None = None):
    """Returns (lowered, meta) for one (arch x shape) on ``mesh``."""
    cfg = get_config(arch)
    cell = SHAPES[shape]
    ok, why = cell_applicable(cfg, cell)
    if not ok:
        return None, {"skipped": why}

    pspecs = registry.param_specs(cfg)
    params = common.param_structs(pspecs)
    t0 = time.time()

    ospecs = cspecs = None
    if cell.kind == "train":
        rules = rules or "default"
        opt_cfg = opt.OptimizerConfig(**OPT_OVERRIDES.get(arch, {}))
        ospecs = opt.state_specs(pspecs, opt_cfg)
        opt_structs = common.param_structs(ospecs)
        mb = microbatches or TRAIN_MICROBATCHES.get(arch, DEFAULT_MICROBATCHES)
        step = make_train_step(cfg, opt_cfg, microbatches=mb)
        in_specs = input_specs(cfg, cell)
        batch = dict(in_specs)
        shardings = (
            shd.spec_shardings(pspecs, mesh, rules),
            shd.spec_shardings(ospecs, mesh, rules),
            _input_shardings(in_specs, mesh, rules),
        )
        with jax.set_mesh(mesh), shd.activation_rules(mesh, rules):
            jitted = jax.jit(step, in_shardings=shardings,
                             out_shardings=(shardings[0], shardings[1], None),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(params, opt_structs, batch)
        meta = {"kind": "train", "microbatches": mb, "rules": rules}

    elif cell.kind == "prefill":
        rules = rules or SERVE_RULES.get(arch, "serve_replicated")
        cspecs = registry.cache_specs(cfg, cell.global_batch, cell.seq_len)
        cache = common.param_structs(cspecs)
        in_specs = input_specs(cfg, cell)
        extra_keys = [k for k in in_specs if k not in ("tokens",)]

        def serve_prefill(params, tokens, cache, extra):
            logits, cache = registry.prefill(cfg, params, tokens, cache,
                                             extra=extra or None, last_only=True)
            return logits[:, 0].astype(jnp.float32), cache

        ish = _input_shardings(in_specs, mesh, rules)
        extra = {k: in_specs[k] for k in extra_keys} or None
        extra_sh = {k: ish[k] for k in extra_keys} or None
        shardings = (shd.spec_shardings(pspecs, mesh, rules), ish["tokens"],
                     shd.spec_shardings(cspecs, mesh, rules), extra_sh)
        with jax.set_mesh(mesh), shd.activation_rules(mesh, rules):
            jitted = jax.jit(serve_prefill, in_shardings=shardings,
                             out_shardings=(None, shardings[2]), donate_argnums=(2,))
            lowered = jitted.lower(params, in_specs["tokens"], cache, extra)
        meta = {"kind": "prefill", "rules": rules}

    else:  # decode
        rules = rules or SERVE_RULES.get(arch, "serve_replicated")
        cfg = cfg.with_(decode_cp=True)  # shard_map context-parallel decode
        cspecs = registry.cache_specs(cfg, cell.global_batch, cell.seq_len)
        cache = common.param_structs(cspecs)
        in_specs = input_specs(cfg, cell)

        def serve_step(params, tokens, cache, cache_len):
            logits, cache = registry.decode_step(cfg, params, tokens, cache, cache_len)
            return logits[:, 0].astype(jnp.float32), cache

        ish = _input_shardings(in_specs, mesh, rules)
        shardings = (shd.spec_shardings(pspecs, mesh, rules), ish["tokens"],
                     shd.spec_shardings(cspecs, mesh, rules), ish["cache_len"])
        with jax.set_mesh(mesh), shd.activation_rules(mesh, rules):
            jitted = jax.jit(serve_step, in_shardings=shardings,
                             out_shardings=(None, shardings[2]), donate_argnums=(2,))
            lowered = jitted.lower(params, in_specs["tokens"], cache,
                                   in_specs["cache_len"])
        meta = {"kind": "decode", "rules": rules}

    meta["lower_s"] = time.time() - t0
    meta["param_count"] = common.param_count(pspecs)
    meta["active_param_count"] = cfg.active_param_count()
    # analytic lower bound on per-device HBM traffic for one step (the
    # roofline floor: weights/caches/optimizer state each touched once-ish;
    # see EXPERIMENTS.md §Roofline notes)
    chips = mesh.devices.size
    pbytes = common.param_bytes(pspecs)
    if cell.kind == "train":
        obytes = common.param_bytes(ospecs)
        act = cell.global_batch * cell.seq_len * cfg.d_model * 2 * max(cfg.num_layers, 1)
        ideal = 3 * pbytes + 2 * obytes + act  # fwd+remat+bwd reads, opt rw, residuals
    else:
        cbytes = common.param_bytes(cspecs) if cell.kind != "train" else 0
        ideal = pbytes + cbytes
    meta["ideal_bytes_per_dev"] = ideal / chips
    return lowered, meta


def run_cell(arch: str, shape: str, mesh_name: str, out_dir: str, *,
             rules: str | None = None, microbatches: int | None = None,
             save_hlo: bool = False) -> dict:
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    chips = mesh.devices.size
    cfg = get_config(arch)
    cell = SHAPES[shape]
    rec: dict = {"arch": arch, "shape": shape, "mesh": mesh_name, "chips": chips}
    try:
        lowered, meta = build_cell(arch, shape, mesh, rules=rules, microbatches=microbatches)
        rec.update(meta)
        if lowered is None:
            rec["status"] = "skipped"
        else:
            t0 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = time.time() - t0
            hlo = compiled.as_text()
            rl = roofline.analyse(compiled, hlo, arch=arch, shape=shape,
                                  mesh_name=mesh_name, chips=chips,
                                  model_flops=roofline.model_flops_for_cell(cfg, cell),
                                  seq_len=cell.seq_len)
            rec["roofline"] = rl.to_json()
            rec["status"] = "ok"
            if save_hlo:
                with open(os.path.join(out_dir, f"{arch}__{shape}__{mesh_name}.hlo"), "w") as f:
                    f.write(hlo)
    except Exception as e:  # noqa: BLE001 - recorded as a failing cell
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{arch}__{shape}__{mesh_name}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--rules", default=None)
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--skip-done", action="store_true")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if not args.all and not args.arch:
        ap.error("pass --arch/--shape or --all")

    n_ok = n_skip = n_err = 0
    for mesh_name in meshes:
        for arch in archs:
            for shape in shapes:
                path = os.path.join(args.out, f"{arch}__{shape}__{mesh_name}.json")
                if args.skip_done and os.path.exists(path):
                    with open(path) as f:
                        if json.load(f).get("status") in ("ok", "skipped"):
                            continue
                t0 = time.time()
                rec = run_cell(arch, shape, mesh_name, args.out, rules=args.rules,
                               microbatches=args.microbatches, save_hlo=args.save_hlo)
                status = rec["status"]
                n_ok += status == "ok"
                n_skip += status == "skipped"
                n_err += status == "error"
                extra = ""
                if status == "ok":
                    r = rec["roofline"]
                    extra = (f"bottleneck={r['bottleneck']} step={r['step_time_s']:.4g}s "
                             f"mfu={r['mfu']:.3f}")
                elif status == "error":
                    extra = rec["error"][:120]
                print(f"[dryrun] {mesh_name:6s} {arch:26s} {shape:12s} {status:8s} "
                      f"({time.time()-t0:.1f}s) {extra}", flush=True)
    print(f"[dryrun] done ok={n_ok} skipped={n_skip} errors={n_err}", flush=True)


if __name__ == "__main__":
    main()
