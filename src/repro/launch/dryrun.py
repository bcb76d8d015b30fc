import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
# The two lines above MUST run before any other import (jax locks the
# device count at first initialization).

"""Multi-pod dry-run driver.

For every (architecture × input shape × mesh) cell: build the production
mesh from placeholder host devices, lower the jitted step with
ShapeDtypeStruct inputs and explicit in_shardings, ``.compile()`` it (the
SPMD partitioner must succeed), and record ``memory_analysis()`` /
``cost_analysis()`` / the parsed collective schedule as a JSON artifact
for EXPERIMENTS.md §Dry-run and §Roofline.

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all \
        --out artifacts/dryrun
"""

import argparse
import dataclasses
import json
import time
import traceback


def dataclasses_asdict(x):
    return dataclasses.asdict(x)

import jax
import jax.numpy as jnp

from ..configs import get_config, list_archs
from ..dist.sharding import batch_specs, cache_specs, named, param_specs
from ..models.config import ModelConfig
from ..nn.context import QuantContext
from ..optim import cosine_warmup
from .mesh import make_production_mesh
from .roofline import peaks, roofline
from .specs import (SHAPES, applicable, input_specs, microbatches_for,
                    state_struct)


#: the chip the production meshes stand for (16x16 = one v5e pod); the
#: placeholder host devices have no kind of their own
TARGET_KIND = "TPU v5 lite"


def _ctx(cfg: ModelConfig, overrides=None) -> QuantContext:
    kw = dict(compute_dtype=jnp.bfloat16)
    if overrides:
        kw.update(overrides)
    return QuantContext(**kw)


def build_lowerable(cfg: ModelConfig, shape: str, mesh, *,
                    ctx_overrides=None, microbatches=None, kv8=False):
    """Returns (jitted_fn, example_args_structs) for one cell."""
    from ..train.step import build_serve_step, build_train_step
    from ..models.api import get_family, prefill_fn

    plan = SHAPES[shape]
    ctx = _ctx(cfg, ctx_overrides)
    specs = input_specs(cfg, shape,
                        dtype=jnp.int8 if kv8 else jnp.bfloat16)

    if plan.kind == "train":
        dp = 1
        for a in ("pod", "data"):
            if a in mesh.axis_names:
                dp *= mesh.shape[a]
        mb = microbatches if microbatches is not None else \
            microbatches_for(cfg, shape, dp)
        st = state_struct(cfg)
        specs_all = param_specs(st, mesh)
        step = build_train_step(
            cfg, ctx, lr_fn=lambda s: cosine_warmup(
                s, peak=3e-4, warmup=2000, total=100_000),
            microbatches=mb, grad_specs=specs_all["params"])
        st_sh = named(specs_all, mesh)
        b_sh = named(batch_specs(specs["batch"], mesh), mesh)
        rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        fn = jax.jit(step, in_shardings=(st_sh, b_sh),
                     out_shardings=(st_sh, rep), donate_argnums=(0,))
        return fn, (st, specs["batch"])

    params = jax.eval_shape(
        lambda: get_family(cfg).init(jax.random.PRNGKey(0), cfg,
                                     dtype=jnp.bfloat16))
    p_sh = named(param_specs(params, mesh), mesh)
    c_sh = named(cache_specs(specs["cache"], mesh), mesh)

    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())

    if plan.kind == "prefill":
        def prefill_step(p, batch, cache):
            return prefill_fn(p, batch, cache, cfg, ctx)
        b_sh = named(batch_specs(specs["batch"], mesh), mesh)
        fn = jax.jit(prefill_step, in_shardings=(p_sh, b_sh, c_sh),
                     out_shardings=(rep, c_sh), donate_argnums=(2,))
        return fn, (params, specs["batch"], specs["cache"])

    serve = build_serve_step(cfg, ctx)
    t_sh = named(batch_specs(specs["tokens"], mesh), mesh)
    pos_sh = named(batch_specs(specs["pos"], mesh), mesh)
    fn = jax.jit(serve, in_shardings=(p_sh, c_sh, t_sh, pos_sh),
                 out_shardings=(rep, c_sh), donate_argnums=(1,))
    return fn, (params, specs["cache"], specs["tokens"], specs["pos"])


def model_flops_for(cfg: ModelConfig, shape: str) -> float:
    """MODEL_FLOPS: 6·N·D for training, 2·N·D for inference, with N the
    *matmul-active* params (embedding gathers excluded — see
    ModelConfig.flop_params)."""
    plan = SHAPES[shape]
    n = cfg.flop_params()
    if plan.kind == "train":
        tokens = plan.batch * plan.seq
        return 6.0 * n * tokens
    if plan.kind == "prefill":
        return 2.0 * n * plan.batch * plan.seq
    return 2.0 * n * plan.batch  # decode: one token per sequence


def run_cell(arch: str, shape: str, mesh_kind: str, *, ctx_overrides=None,
             microbatches=None, verbose=True, tag="", kv8=False):
    cfg = get_config(arch)
    ok, reason = applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape, "mesh": mesh_kind,
                "status": "skipped", "reason": reason}
    from ..dist.constrain import use_mesh
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = mesh.size
    t0 = time.time()
    with use_mesh(mesh):
        fn, args = build_lowerable(cfg, shape, mesh,
                                   ctx_overrides=ctx_overrides,
                                   microbatches=microbatches, kv8=kv8)
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    mem_d = {k: int(getattr(mem, k)) for k in
             ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes")
             if hasattr(mem, k)}
    cost = dict(compiled.cost_analysis())
    hlo = compiled.as_text()
    if os.environ.get("DRYRUN_SAVE_HLO"):
        os.makedirs("artifacts/hlo", exist_ok=True)
        with open(f"artifacts/hlo/{arch}__{shape}__{mesh_kind}.hlo.txt",
                  "w") as f:
            f.write(hlo)
    rep = roofline(arch=arch, shape=shape, mesh=mesh_kind, chips=chips,
                   cost=cost, hlo_text=hlo,
                   model_flops=model_flops_for(cfg, shape),
                   hw=peaks(TARGET_KIND), memory_analysis=mem_d)
    out = rep.to_json()
    from ..dist.options import flags as _flags
    out.update(status="ok", lower_s=round(t_lower, 1),
               compile_s=round(t_compile, 1), tag=tag,
               opt_flags=dataclasses_asdict(_flags()),
               microbatches=(microbatches if microbatches is not None
                             else microbatches_for(cfg, shape,
                                                   512 // 16 if mesh_kind == "multi" else 16)))
    if verbose:
        print(f"[{arch} × {shape} × {mesh_kind}] chips={chips} "
              f"compute={rep.compute_s*1e3:.2f}ms "
              f"memory={rep.memory_s*1e3:.2f}ms "
              f"collective={rep.collective_s*1e3:.2f}ms "
              f"bottleneck={rep.bottleneck} mfu={rep.mfu:.3f}")
        print("  memory_analysis:", mem_d)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--all", action="store_true",
                    help="run every (arch × shape) for --mesh")
    ap.add_argument("--out", default=None, help="artifact directory")
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--opt", default=None,
                    help="'all' or comma list of perf flags "
                         "(grad_specs,sp_attn,seq_kv)")
    ap.add_argument("--tag", default="", help="artifact filename suffix")
    ap.add_argument("--kv8", action="store_true",
                    help="int8 KV cache for decode/prefill cells")
    args = ap.parse_args()

    if args.opt:
        from ..dist.options import PerfFlags, set_flags
        if args.opt == "all":
            set_flags(PerfFlags.all_on())
        else:
            names = set(args.opt.split(","))
            set_flags(PerfFlags(**{n: True for n in names}))

    cells = []
    if args.all:
        archs = [a for a in list_archs() if a != "jet-mlp"]
        for a in archs:
            for s in SHAPES:
                cells.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    results = []
    for arch, shape in cells:
        try:
            r = run_cell(arch, shape, args.mesh,
                         microbatches=args.microbatches, tag=args.tag,
                         kv8=args.kv8)
        except Exception as e:  # a failed cell is a bug — surface it
            traceback.print_exc()
            r = {"arch": arch, "shape": shape, "mesh": args.mesh,
                 "status": "error", "error": repr(e)}
        results.append(r)
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            suffix = f"__{args.tag}" if args.tag else ""
            fn = os.path.join(args.out,
                              f"{arch}__{shape}__{args.mesh}{suffix}.json")
            with open(fn, "w") as f:
                json.dump(r, f, indent=1)

    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"\ndry-run summary [{args.mesh}]: {n_ok} ok, {n_skip} skipped, "
          f"{n_err} errors")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
