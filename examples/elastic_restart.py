"""Fault tolerance + elasticity demo: train with injected failures,
recover from checkpoints, then restart the SAME checkpoint on a DIFFERENT
mesh shape (the elastic-rescale path a 1000-node deployment needs when a
pod is lost).

Run:  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python examples/elastic_restart.py
"""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.data.pipeline import make_batch
from repro.dist.constrain import use_mesh
from repro.dist.sharding import batch_specs, named, param_specs
from repro.ft import FaultInjector, ResilientLoop, StragglerMonitor
from repro.launch.mesh import make_mesh
from repro.nn.context import QuantContext
from repro.train.step import build_train_step, init_state


def run_on_mesh(mesh, ckpt_dir, steps, fail_at=()):
    cfg = get_config("yi-6b").smoke()
    ctx = QuantContext(compute_dtype=jnp.float32)
    step_fn = build_train_step(cfg, ctx, lr_fn=lambda s: 1e-3,
                               microbatches=1)
    with use_mesh(mesh):
        state = init_state(jax.random.PRNGKey(0), cfg)
        st_sh = named(param_specs(state, mesh), mesh)
        state = jax.device_put(state, st_sh)
        rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())

        def batch_fn(step):
            b = make_batch(cfg, step, 8, 32)
            return jax.device_put(b, named(batch_specs(b, mesh), mesh))

        b_sh = named(batch_specs(batch_fn(0), mesh), mesh)
        jstep = jax.jit(step_fn, in_shardings=(st_sh, b_sh),
                        out_shardings=(st_sh, rep), donate_argnums=(0,))

        mgr = CheckpointManager(ckpt_dir, keep=3)
        restored, ckstep = mgr.restore_latest(
            jax.tree_util.tree_map(np.asarray, state), shardings=st_sh)
        start = 0
        if restored is not None:
            state, start = restored, ckstep
            print(f"  resumed from step {start} onto mesh "
                  f"{dict(mesh.shape)}")

        mon = StragglerMonitor()
        loop = ResilientLoop(jstep, batch_fn, mgr, checkpoint_every=5,
                             fault_injector=FaultInjector(fail_at),
                             straggler=mon)
        out = loop.run(state, start_step=start, num_steps=steps,
                       shardings=st_sh)
        print(f"  reached step {out['step']}, "
              f"loss {float(out['metrics']['loss']):.4f}, "
              f"restores={out['restores']}")
        return out


def main():
    n = len(jax.devices())
    ckpt = tempfile.mkdtemp(prefix="elastic_")
    print(f"devices: {n}; checkpoints: {ckpt}")

    print("\nPhase 1: (n//2, 2) mesh with injected faults at steps 7, 12")
    mesh1 = make_mesh((max(n // 2, 1), min(2, n)), ("data", "model"))
    run_on_mesh(mesh1, ckpt, steps=15, fail_at=(7, 12))

    print("\nPhase 2: elastic restart on a (n, 1) mesh — same checkpoint")
    mesh2 = make_mesh((n, 1), ("data", "model"))
    run_on_mesh(mesh2, ckpt, steps=10)

    print("\nelastic restart OK")


if __name__ == "__main__":
    main()
