import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

# NOTE: the lines above MUST precede every other import (jax locks the
# device count and platform on first init), which is why the docstring
# sits below them.  The 512 devices are forced HOST devices: pinning the
# CPU platform keeps a machine with a TPU from handing the mesh its chip.
DOC = """Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware:
  * jax.jit(step, in_shardings, out_shardings).lower(...).compile()
    must succeed on the 16x16 single-pod mesh and the 2x16x16
    multi-pod mesh for every assigned cell;
  * records memory_analysis(), cost_analysis() and the collective
    schedule (parsed from optimized HLO) into a JSON artifact that
    benchmarks/roofline.py consumes.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-3b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out DIR]
"""

import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp

from repro import configs
from repro.configs.base import SHAPES, cells_for
from repro.dist import sharding as S
from repro.launch import steps as steps_mod
from repro.launch.hlo_analysis import analyze_collectives
from repro.launch.mesh import make_production_mesh
from repro.layers import common as C
from repro.models import transformer as M
from repro.optim import optimizer as opt_mod

def memory_summary(compiled) -> dict:
    out = {}
    try:
        ma = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
            v = getattr(ma, k, None)
            if v is not None:
                out[k] = int(v)
    except Exception as e:  # CPU backend may not implement it
        out["error"] = str(e)
    return out


def run_cell(arch: str, shape: str, multi_pod: bool, *,
             microbatches: int = 8, strategy: str = "default",
             donate: bool = True, overrides: dict | None = None) -> dict:
    cfg = configs.get_config(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    cell = SHAPES[shape]
    if cell.name == "long_500k" and not cfg.sub_quadratic:
        return {"arch": arch, "shape": shape, "multi_pod": multi_pod,
                "status": "skipped",
                "reason": "full-attention arch; long_500k needs sub-quadratic "
                          "attention (see DESIGN.md §4)"}

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size

    params_shapes, specs = M.abstract_init(cfg)
    params_shapes = steps_mod.to_dtype_structs(params_shapes, jnp.bfloat16)

    kind = cell.kind
    rules = (S.rules_decode(multi_pod) if kind == "decode"
             else S.rules_train(multi_pod, fsdp=(kind == "train")))
    pshard = S.param_shardings(mesh, params_shapes, specs, rules)
    bspec = steps_mod.input_specs(cfg, cell)
    bshard = S.batch_shardings(mesh, bspec, rules)

    C.set_sharding_context(mesh, rules)
    try:
        if kind == "train":
            # global batch must split into microbatches divisible by the dp shards
            mb = microbatches
            opt_cfg = opt_mod.AdamWConfig(
                moment_dtype=jnp.bfloat16 if cfg.d_model >= 4096 else jnp.float32)
            opt_shapes = opt_mod.abstract_state(opt_cfg, params_shapes)
            ospecs = opt_mod.state_specs(specs)
            oshard = {
                "m": S.param_shardings(mesh, opt_shapes["m"], specs, rules),
                "v": S.param_shardings(mesh, opt_shapes["v"], specs, rules),
                "step": S.replicated(mesh),
            }
            step = steps_mod.build_train_step(cfg, opt_cfg, microbatches=mb)
            jitted = jax.jit(step, in_shardings=(pshard, oshard, bshard),
                             out_shardings=(pshard, oshard, None),
                             donate_argnums=(0, 1) if donate else ())
            lowered = jitted.lower(params_shapes, opt_shapes, bspec)
        elif kind == "prefill":
            step = steps_mod.build_prefill_step(cfg)
            jitted = jax.jit(step, in_shardings=(pshard, bshard),
                             out_shardings=None)
            lowered = jitted.lower(params_shapes, bspec)
        else:  # decode
            cshapes = steps_mod.cache_specs_abstract(cfg, cell)
            cspecs = M.cache_specs(cfg)
            cshard = S.param_shardings(mesh, cshapes, cspecs, rules)
            step = steps_mod.build_decode_step(cfg)
            jitted = jax.jit(step, in_shardings=(pshard, cshard, bshard),
                             out_shardings=(None, cshard),
                             donate_argnums=(1,) if donate else ())
            lowered = jitted.lower(params_shapes, cshapes, bspec)

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        cost = dict(compiled.cost_analysis() or {})
        cost = {k: float(v) for k, v in cost.items()
                if isinstance(v, (int, float)) and k in
                ("flops", "bytes accessed", "transcendentals",
                 "bytes accessed0{}", "bytes accessedout{}", "optimal_seconds")}
        coll = analyze_collectives(compiled.as_text())
        mem = memory_summary(compiled)
        result = {
            "arch": arch, "shape": shape, "multi_pod": multi_pod,
            "status": "ok", "kind": kind, "devices": n_dev,
            "strategy": strategy,
            "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
            "cost_analysis": cost,
            "collectives": coll,
            "memory": mem,
        }
        return result
    finally:
        C.clear_sharding_context()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--microbatches", type=int, default=8)
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    jobs = []
    if args.all:
        for arch in configs.ARCH_IDS:
            cfg = configs.get_config(arch)
            for cell in cells_for(cfg):
                jobs.append((arch, cell.name))
    else:
        jobs.append((args.arch, args.shape))

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = 0
    for arch, shape in jobs:
        for mp in meshes:
            tag = f"{arch}__{shape}__{'pod2' if mp else 'pod1'}"
            path = os.path.join(args.out, tag + ".json")
            if os.path.exists(path):
                print(f"[skip-cached] {tag}")
                continue
            try:
                res = run_cell(arch, shape, mp,
                               microbatches=args.microbatches)
            except Exception as e:
                failures += 1
                res = {"arch": arch, "shape": shape, "multi_pod": mp,
                       "status": "error", "error": str(e),
                       "traceback": traceback.format_exc()}
            with open(path, "w") as f:
                json.dump(res, f, indent=1)
            status = res["status"]
            extra = ""
            if status == "ok":
                extra = (f" flops/dev={res['cost_analysis'].get('flops', 0):.3e}"
                         f" coll={res['collectives']['total_bytes_executed']:.3e}B"
                         f" compile={res['compile_s']}s")
            print(f"[{status}] {tag}{extra}", flush=True)
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
