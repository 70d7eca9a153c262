import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape)
cell on the production mesh and record memory/cost/collective statistics.

The two lines above MUST precede any other import (jax locks the device
count on first init); 512 placeholder host devices back both the single-pod
(16 data x 16 model = 256 chips) and the multi-pod (2 pods x 16 x 16 = 512
chips) meshes.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-0.6b \
      --shape train_4k [--multi-pod]
  PYTHONPATH=src python -m repro.launch.dryrun --all          # 40 cells
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod
Artifacts land in artifacts/dryrun/<arch>.<shape>.<mesh>.json.
"""
import argparse
import json
import pathlib
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, get_config
from repro.launch import roofline as RL
from repro.launch.costmodel import jaxpr_cost
from repro.launch.mesh import make_production_mesh
from repro.launch.shapes import (SHAPES, ShapeSpec, adjust_config,
                                 batch_input_specs, cell_is_runnable,
                                 cell_rules)
from repro.launch.serve import make_prefill_step, make_serve_step
from repro.launch.train import make_train_step
from repro.models.common import (ModelConfig, ParamDef, abstract_params,
                                 spec as rspec, with_axis_sizes)
from repro.models.transformer import Model
from repro.optim.optimizers import AdamW, constant_schedule

ART_DIR = pathlib.Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def _batch_shardings(mesh, rules, specs):
    def spec_for(name, sds):
        if name == "tokens":
            return P(rules.get("batch"), None)
        return P(rules.get("batch"), None, None)
    return {k: NamedSharding(mesh, spec_for(k, v)) for k, v in specs.items()}


def _tree_ns(mesh, tree_specs):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tree_specs,
        is_leaf=lambda x: isinstance(x, P))


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               rules_override=None, cfg_override=None):
    """Lower + compile one cell; returns (record, compiled)."""
    shape = SHAPES[shape_name]
    cfg = adjust_config(get_config(arch), shape)
    if cfg_override:
        cfg = cfg.replace(**cfg_override)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = 512 if multi_pod else 256
    data_size = mesh.shape["data"]
    rules = cell_rules(shape, multi_pod, data_size)
    if rules_override:
        rules.update(rules_override)
    rules = with_axis_sizes(rules, mesh)
    model = Model(cfg)

    params_abs = model.abstract()
    pspecs = model.specs(rules)
    params_ns = _tree_ns(mesh, pspecs)
    in_specs = batch_input_specs(cfg, shape)
    batch_ns = _batch_shardings(mesh, rules, in_specs)

    defs = model.param_defs()
    moe_frac = 1.0
    if cfg.n_experts:
        moe_frac = (cfg.top_k + cfg.n_shared_experts) / cfg.n_experts
    n_total, n_active = RL.count_params(defs, {"expert_frac": moe_frac})

    t0 = time.time()
    cost = None
    with mesh:
        if shape.kind == "train":
            # bf16 optimizer moments for 100B+ models (llama4: 400B x 10B
            # per param would exceed 16GB/chip with f32 moments)
            mv = jnp.bfloat16 if n_total > 100e9 else jnp.float32
            opt = AdamW(schedule=constant_schedule(1e-4), mv_dtype=mv)
            opt_abs = jax.eval_shape(opt.init, params_abs)
            opt_ns = _tree_ns(mesh, opt.state_specs(pspecs))
            state_abs = {"params": params_abs, "opt": opt_abs}
            state_ns = {"params": params_ns, "opt": opt_ns}
            step = make_train_step(model, opt, rules)
            lowered = jax.jit(step, in_shardings=(state_ns, batch_ns),
                              out_shardings=(state_ns, None),
                              donate_argnums=(0,)).lower(state_abs, in_specs)
            cost = jaxpr_cost(step, state_abs, in_specs)
            tokens = shape.global_batch * shape.seq
            training = True
        elif shape.kind == "prefill":
            # cache must hold the token sequence plus any patch prefix
            step = make_prefill_step(model, rules,
                                     max_len=shape.seq + cfg.n_patches + 8)
            lowered = jax.jit(step, in_shardings=(params_ns, batch_ns),
                              ).lower(params_abs, in_specs)
            cost = jaxpr_cost(step, params_abs, in_specs)
            tokens = shape.global_batch * shape.seq
            training = False
        else:  # decode
            cache_abs = model.make_cache(shape.global_batch, shape.seq,
                                         abstract=True)
            cache_specs = _cache_pspecs(model, cache_abs, rules)
            cache_ns = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), cache_specs,
                is_leaf=lambda x: isinstance(x, P))
            step = make_serve_step(model, rules)
            lowered = jax.jit(step,
                              in_shardings=(params_ns, cache_ns,
                                            batch_ns["tokens"]),
                              out_shardings=(None, cache_ns),
                              donate_argnums=(1,)).lower(
                params_abs, cache_abs, in_specs["tokens"])
            cost = jaxpr_cost(step, params_abs, cache_abs,
                              in_specs["tokens"])
            tokens = shape.global_batch
            training = False
        compiled = lowered.compile()
    compile_s = time.time() - t0

    mem = compiled.memory_analysis()
    record = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "status": "ok",
        "compile_us": compile_s * 1e6,
        "n_params_total": n_total,
        "n_params_active": n_active,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
        },
        "roofline": RL.analyze(compiled, chips, n_active, tokens, training,
                               flops=cost.flops if cost else None,
                               hbm_bytes=cost.bytes if cost else None),
    }
    return record, compiled


def _cache_pspecs(model: Model, cache_abs, rules):
    """PartitionSpecs for the decode cache: KV seq/heads per rules; leading
    layer-stack dim unsharded; batch per rules.  Divisibility fallback is
    applied through ``rspec`` (e.g. 5 KV heads on a 16-way axis -> None)."""
    LOGICAL = {
        "k": ("batch", "cache_seq", "cache_heads", None),
        "v": ("batch", "cache_seq", "cache_heads", None),
        "k_scale": ("batch", "cache_seq", "cache_heads"),
        "v_scale": ("batch", "cache_seq", "cache_heads"),
        "ssm": ("batch", "ssm_heads", None, None),
        "h": ("batch", "rnn"),
        "conv": ("batch", None, None),
    }

    def spec_for(path, leaf):
        keys = [str(getattr(p, "key", "")) for p in path]
        name = keys[-1] if keys else ""
        nd = len(leaf.shape)
        axes = LOGICAL.get(name)
        if axes is None or nd < len(axes):
            return P()
        lead = nd - len(axes)        # leading layer-stack dims (unsharded)
        full = (None,) * lead + axes
        return rspec(rules, *full, shape=leaf.shape)

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_abs)
    return jax.tree_util.tree_unflatten(
        treedef, [spec_for(p, l) for p, l in flat])


def optimized_overrides(arch: str, shape_name: str):
    """The winning §Perf variants, generalized to every cell:
    decode -> 2-D cache sharding + dynamic-scale int8 KV;
    MoE train/prefill -> scatter dispatch + 16k dispatch blocks;
    train/prefill -> flash-attention kernel cost substitution."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    rules, cfgo = {}, {}
    flash = False
    if shape.kind == "decode":
        if shape.global_batch >= 16:
            rules["cache_seq"] = "model"
        cfgo["cache_dtype"] = jnp.int8
    else:
        flash = True
        if cfg.n_experts:
            cfgo["moe_dispatch"] = "scatter"
            cfgo["moe_block"] = 16384
    return rules, cfgo, flash


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: pathlib.Path, optimized: bool = False) -> dict:
    ok, why = cell_is_runnable(arch, shape_name)
    mesh_tag = "2x16x16" if multi_pod else "16x16"
    tag = ".opt" if optimized else ""
    out_path = out_dir / f"{arch}.{shape_name}.{mesh_tag}{tag}.json"
    if not ok:
        record = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
                  "status": "skipped", "reason": why}
    else:
        try:
            if optimized:
                rules_o, cfg_o, flash = optimized_overrides(arch, shape_name)
                record, compiled = lower_cell(arch, shape_name, multi_pod,
                                              rules_override=rules_o,
                                              cfg_override=cfg_o)
                if flash:
                    from repro.launch.hillclimb import \
                        apply_flash_substitution
                    cfg = adjust_config(get_config(arch), SHAPES[shape_name])
                    if cfg_o:
                        cfg = cfg.replace(**cfg_o)
                    record = apply_flash_substitution(record, cfg,
                                                      shape_name, skip=True)
            else:
                record, compiled = lower_cell(arch, shape_name, multi_pod)
            print(f"  memory_analysis: {compiled.memory_analysis()}")
            ca = compiled.cost_analysis()
            if isinstance(ca, list):
                ca = ca[0]
            print(f"  cost_analysis: flops={ca.get('flops', 0):.3e} "
                  f"bytes={ca.get('bytes accessed', 0):.3e}")
        except Exception as exc:
            record = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
                      "status": "error", "error": f"{type(exc).__name__}: {exc}",
                      "trace": traceback.format_exc()[-2000:]}
    out_dir.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(record, indent=1))
    r = record.get("roofline", {})
    print(f"[{record['status']:7s}] {arch} x {shape_name} x {mesh_tag}"
          + (f"  bound={r.get('bound')} frac={r.get('roofline_fraction', 0):.3f}"
             if r else (f"  ({record.get('reason', record.get('error', ''))})")))
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--optimized", action="store_true",
                    help="apply the winning §Perf variants to every cell")
    ap.add_argument("--out", default=str(ART_DIR))
    args = ap.parse_args()
    out_dir = pathlib.Path(args.out)

    if args.all:
        archs = ARCHS
        shapes = list(SHAPES)
    else:
        archs = [args.arch] if args.arch else ARCHS[:1]
        shapes = [args.shape] if args.shape else ["train_4k"]

    n_fail = 0
    for arch in archs:
        for shape in shapes:
            rec = run_cell(arch, shape, args.multi_pod, out_dir,
                           optimized=args.optimized)
            if rec["status"] == "error":
                n_fail += 1
    if n_fail:
        raise SystemExit(f"{n_fail} cells failed")


if __name__ == "__main__":
    main()
