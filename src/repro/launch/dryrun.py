import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This proves the distribution config is coherent without real hardware:
``.lower().compile()`` must succeed on the single-pod (16,16) mesh and
the multi-pod (2,16,16) mesh for every runnable cell; the compiled
artifact supplies memory_analysis / cost_analysis / the collective
schedule for EXPERIMENTS.md §Dry-run and §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-72b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
      --out results/dryrun.jsonl
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import SHAPES, cells, cell_status, get_arch
from repro.launch.mesh import make_production_mesh
from repro.models.api import get_model
from repro.optim import adamw, warmup_cosine
from repro.runtime import roofline, sharding as shd
from repro.runtime.train import make_train_step


def grad_accum_for(cfg) -> int:
    if cfg.d_model >= 8192:
        return 4
    if cfg.d_model >= 4096:
        return 2
    return 1


def count_params(tree) -> int:
    return int(sum(np.prod(l.shape) for l in jax.tree.leaves(tree)))


def active_params(cfg, tree) -> int:
    total = count_params(tree)
    if not cfg.is_moe:
        return total
    inactive = 0

    def visit(path, leaf):
        nonlocal inactive
        keys = [getattr(p, "key", str(p)) for p in path]
        if "mlp" in keys and len(leaf.shape) >= 3 and cfg.n_experts in leaf.shape:
            inactive += int(np.prod(leaf.shape) *
                            (1 - cfg.top_k / cfg.n_experts))
    jax.tree_util.tree_map_with_path(visit, tree)
    return total - inactive


def build_lowered(arch_id: str, shape_name: str, mesh, mesh_name: str,
                  opt_level: int = 0):
    """Build and lower one cell.  Returns (lowered, meta)."""
    cfg = get_arch(arch_id)
    shape = SHAPES[shape_name]
    # --- optimization ladder (EXPERIMENTS.md §Perf) ---
    #  serve opt>=1: bf16 weights, TP-only sharding (no per-token FSDP
    #                all-gather);  opt>=2: int8 KV cache
    #  train opt>=1: bf16 FSDP gathers (fp32 master weights)
    kv_quant = ("int8" if (opt_level >= 2 and shape.kind == "decode"
                           and cfg.block_type == "transformer") else "none")
    moe_impl = ("shardmap" if (opt_level >= 2 and cfg.is_moe
                               and shape.kind == "train") else "dense")
    model = get_model(cfg, compute_dtype=jnp.bfloat16, remat="full",
                      **({"kv_quant": kv_quant, "moe_impl": moe_impl}
                         if cfg.block_type == "transformer" else {}))
    params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if opt_level >= 1 and shape.kind != "train":
        params_shape = shd.cast_float_specs(params_shape, jnp.bfloat16)
        pspecs = shd.serve_param_specs(mesh, params_shape)
    else:
        pspecs = shd.param_specs(mesh, params_shape)
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
    n_params = count_params(params_shape)
    n_active = active_params(cfg, params_shape)
    in_specs = model.input_specs(shape)

    if shape.kind == "train":
        ga = grad_accum_for(cfg)
        sched = warmup_cosine(3e-4, 100, 10_000)
        init_fn, upd_fn = adamw(lr=sched)
        opt_shape = jax.eval_shape(init_fn, params_shape)
        oshard = type(opt_shape)(
            step=NamedSharding(mesh, P()),
            m=pshard, v=pshard)
        tstep = make_train_step(
            model, upd_fn, grad_accum=ga,
            gather_dtype=jnp.bfloat16 if opt_level >= 1 else None)
        bshard = shd.to_shardings(mesh, shd.batch_spec(mesh, in_specs))
        rep = NamedSharding(mesh, P())
        lowered = jax.jit(
            tstep,
            in_shardings=(pshard, oshard, bshard),
            out_shardings=(pshard, oshard,
                           {"loss": rep, "grad_norm": rep}),
            donate_argnums=(0, 1),
        ).lower(params_shape, opt_shape, in_specs)
        tokens = shape.global_batch * shape.seq_len
        mf = roofline.model_flops_estimate(n_active, tokens, "train")
        return lowered, dict(n_params=n_params, n_active=n_active,
                             model_flops=mf, grad_accum=ga)

    if shape.kind == "prefill":
        bshard = shd.to_shardings(mesh, shd.batch_spec(mesh, in_specs))
        if cfg.encoder_only:
            fn = lambda p, b: model.forward(p, b)[0]
            lowered = jax.jit(fn, in_shardings=(pshard, bshard)).lower(
                params_shape, in_specs)
        else:
            cache_shape = jax.eval_shape(
                lambda: model.cache_spec(shape.global_batch, shape.seq_len))
            cshard = shd.to_shardings(
                mesh, shd.cache_spec_shardings(mesh, cache_shape))
            lowered = jax.jit(
                model.prefill,
                in_shardings=(pshard, bshard),
                out_shardings=(NamedSharding(mesh, P()), cshard),
            ).lower(params_shape, in_specs)
        tokens = shape.global_batch * shape.seq_len
        mf = roofline.model_flops_estimate(n_active, tokens, "prefill")
        return lowered, dict(n_params=n_params, n_active=n_active,
                             model_flops=mf)

    # decode: one new token against a seq_len cache
    cache_spec = in_specs["cache"]
    cshard = shd.to_shardings(mesh, shd.cache_spec_shardings(mesh, cache_spec))
    tshard = NamedSharding(mesh, shd.decode_token_spec(mesh,
                                                       shape.global_batch))
    lowered = jax.jit(
        model.decode_step,
        in_shardings=(pshard, cshard, tshard),
        out_shardings=(NamedSharding(mesh, P()), cshard),
        donate_argnums=(1,),
    ).lower(params_shape, cache_spec, in_specs["tokens"])
    mf = roofline.model_flops_estimate(n_active, shape.global_batch,
                                       "decode")
    return lowered, dict(n_params=n_params, n_active=n_active,
                         model_flops=mf)


def run_cell(arch_id: str, shape_name: str, mesh_name: str,
             opt_level: int = 0, lower_only: bool = False) -> dict:
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    chips = int(np.prod(list(mesh.shape.values())))
    rec = dict(arch=arch_id, shape=shape_name, mesh=mesh_name, chips=chips,
               opt_level=opt_level, status="ok")
    t0 = time.time()
    try:
        with mesh:
            lowered, meta = build_lowered(arch_id, shape_name, mesh,
                                          mesh_name, opt_level)
            rec.update(meta)
            rec["lower_s"] = round(time.time() - t0, 1)
            if lower_only:
                rec["status"] = "lowered"
                return rec
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t1, 1)
            mem = compiled.memory_analysis()
            print(mem)
            cost = compiled.cost_analysis()
            print({k: v for k, v in cost.items()
                   if k in ("flops", "bytes accessed")})
            terms = roofline.analyze(compiled, None, arch_id, shape_name,
                                     mesh_name, chips, meta["model_flops"])
            rec["roofline"] = terms.to_dict()
            rec["memory"] = {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
                "output_bytes": getattr(mem, "output_size_in_bytes", 0),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
                "generated_code_bytes": getattr(
                    mem, "generated_code_size_in_bytes", 0),
                # per-device working set (args are sharded over chips)
                "temp_bytes_per_device": getattr(
                    mem, "temp_size_in_bytes", 0),
            }
    except Exception as e:  # record the failure — these are bugs to fix
        rec["status"] = "fail"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["total_s"] = round(time.time() - t0, 1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None)
    ap.add_argument("--mesh", type=str, default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", type=str, default="results/dryrun.jsonl")
    ap.add_argument("--opt", type=int, default=0,
                    help="optimization level (hillclimb variants)")
    ap.add_argument("--skip-done", action="store_true")
    ap.add_argument("--lower-only", action="store_true",
                    help="stop after .lower() (fast structural check)")
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    todo = []
    if args.all:
        for arch, shape, _ in cells(runnable_only=True):
            for m in meshes:
                todo.append((arch.name, shape.name, m))
    else:
        assert args.arch and args.shape
        for m in meshes:
            todo.append((args.arch, args.shape, m))

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    done = set()
    if args.skip_done and os.path.exists(args.out):
        with open(args.out) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    if r.get("status") == "ok" and r.get("opt_level", 0) == args.opt:
                        done.add((r["arch"], r["shape"], r["mesh"]))
                except json.JSONDecodeError:
                    pass

    n_fail = 0
    for arch_id, shape_name, mesh_name in todo:
        key = (arch_id.replace("_", "-"), shape_name, mesh_name)
        norm = (get_arch(arch_id).name, shape_name, mesh_name)
        if norm in done:
            print(f"SKIP (done) {norm}")
            continue
        print(f"=== {arch_id} x {shape_name} x {mesh_name} ===", flush=True)
        rec = run_cell(arch_id, shape_name, mesh_name, args.opt,
                       lower_only=args.lower_only)
        rec["arch"] = get_arch(arch_id).name
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
        if rec["status"] in ("ok", "lowered"):
            if rec["status"] == "lowered":
                print(f"  LOWERED in {rec['lower_s']}s", flush=True)
            else:
                rf = rec["roofline"]
                print(f"  OK  compile={rec['compile_s']}s "
                      f"flops={rf['hlo_flops']:.3e} bytes={rf['hlo_bytes']:.3e} "
                      f"coll={rf['coll_bytes']:.3e} "
                      f"bottleneck={rf['bottleneck']}", flush=True)
        else:
            n_fail += 1
            print(f"  FAIL {rec['error']}", flush=True)
    print(f"done: {len(todo)} cells, {n_fail} failures")
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
