"""Training entrypoint: config → mesh → data → resilient loop.

Usage (CPU-scale example; the same driver runs on a real pod by picking a
different mesh)::

    PYTHONPATH=src python -m repro.launch.train --arch olmoe-1b-7b \
        --smoke --steps 100 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt

Features exercised: sharded state (FSDP×TP), microbatched gradient
accumulation, deterministic data replay, async checkpoints, fault
injection + restore, straggler monitoring, quantization context flags
(--quant fake --lut).
"""

from __future__ import annotations

import argparse
import logging

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import CheckpointManager
from ..configs import get_config
from ..core.qtypes import FixedPointType
from ..core.precision import LayerPrecision, PrecisionPolicy
from ..data.pipeline import make_batch
from ..dist.constrain import use_mesh
from ..dist.sharding import batch_specs, named, param_specs
from ..ft import FaultInjector, ResilientLoop, StragglerMonitor
from ..nn.context import QuantContext
from ..optim import cosine_warmup
from ..train.step import build_train_step, init_state
from .compile_cache import configure_compile_cache
from .mesh import make_local_mesh


def build_ctx(args, backend=None) -> QuantContext:
    """The context for parsed command-line flags.  ``--f32`` sets both
    the compute and the parameter dtype (bf16 otherwise); ``backend``
    selects the kernel lowerings (None = the registry default)."""
    policy = PrecisionPolicy()
    if args.quant != "none":
        qt = FixedPointType(args.qbits, max(args.qbits // 2, 2))
        policy = PrecisionPolicy.uniform(qt)
    dtype = jnp.float32 if args.f32 else jnp.bfloat16
    return QuantContext(mode=args.quant, policy=policy, use_lut=args.lut,
                        compute_dtype=dtype, param_dtype=dtype,
                        reuse_factor=args.reuse_factor, backend=backend)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--quant", default="none",
                    choices=["none", "fake", "int8"])
    ap.add_argument("--qbits", type=int, default=8)
    ap.add_argument("--lut", action="store_true")
    ap.add_argument("--f32", action="store_true")
    ap.add_argument("--reuse-factor", type=int, default=1)
    ap.add_argument("--fail-at", type=int, nargs="*", default=[],
                    help="inject faults at these steps (FT demo)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    configure_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    ctx = build_ctx(args)
    mesh = make_local_mesh(model=args.model_parallel)

    step_fn = build_train_step(
        cfg, ctx,
        lr_fn=lambda s: cosine_warmup(s, peak=args.lr,
                                      warmup=max(args.steps // 20, 1),
                                      total=args.steps),
        microbatches=args.microbatches)

    with use_mesh(mesh):
        state = init_state(jax.random.PRNGKey(args.seed), cfg)
        st_sh = named(param_specs(state, mesh), mesh)
        state = jax.device_put(state, st_sh)
        rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())

        def batch_fn(step):
            b = make_batch(cfg, step, args.batch, args.seq, seed=args.seed)
            b_sh = named(batch_specs(b, mesh), mesh)
            return jax.device_put(b, b_sh)

        b0 = batch_fn(0)
        b_sh = named(batch_specs(b0, mesh), mesh)
        jstep = jax.jit(step_fn, in_shardings=(st_sh, b_sh),
                        out_shardings=(st_sh, rep), donate_argnums=(0,))

        manager = CheckpointManager(args.ckpt_dir, keep=3)
        restored, ckpt_step = manager.restore_latest(
            jax.tree_util.tree_map(np.asarray, state), shardings=st_sh)
        start = 0
        if restored is not None:
            state, start = restored, ckpt_step
            print(f"resumed from checkpoint step {start}")

        loop = ResilientLoop(
            jstep, batch_fn, manager, checkpoint_every=args.ckpt_every,
            fault_injector=FaultInjector(args.fail_at) if args.fail_at else None,
            straggler=StragglerMonitor())
        out = loop.run(state, start_step=start, num_steps=args.steps,
                       shardings=st_sh, log_every=args.log_every)
        print(f"done: step={out['step']} loss={float(out['metrics']['loss']):.4f} "
              f"restores={out['restores']}")
    return out


if __name__ == "__main__":
    main()
