"""Multi-device distribution tests (8 host devices via subprocess — the
test process itself must keep a single device; see conftest)."""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n: int = 8, timeout: int = 600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"      # a child never reaches for a chip
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


@pytest.mark.slow
def test_sharded_train_step_runs_and_converges():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.dist.sharding import param_specs, batch_specs, named
        from repro.dist.constrain import use_mesh
        from repro.nn.context import QuantContext
        from repro.train.step import build_train_step, init_state
        from repro.data.pipeline import make_batch

        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = get_config("yi-6b").smoke()
        ctx = QuantContext(compute_dtype=jnp.float32)
        step = build_train_step(cfg, ctx, lr_fn=lambda s: 3e-3,
                                microbatches=2)
        with use_mesh(mesh):
            state = init_state(jax.random.PRNGKey(0), cfg)
            st_sh = named(param_specs(state, mesh), mesh)
            state = jax.device_put(state, st_sh)
            b = make_batch(cfg, 0, 8, 32)
            b_sh = named(batch_specs(b, mesh), mesh)
            rep = NamedSharding(mesh, P())
            jstep = jax.jit(step, in_shardings=(st_sh, b_sh),
                            out_shardings=(st_sh, rep),
                            donate_argnums=(0,))
            losses = []
            for i in range(24):
                batch = jax.device_put(make_batch(cfg, i, 8, 32), b_sh)
                state, m = jstep(state, batch)
                losses.append(float(m["loss"]))
        assert losses[-1] < losses[0] - 0.3, losses
        print("CONVERGED", losses[0], "->", losses[-1])
    """)
    assert "CONVERGED" in out


@pytest.mark.slow
def test_quantized_psum_matches_exact():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.core.qtypes import FixedPointType
        from repro.dist.compression import quantized_psum

        mesh = make_mesh((8,), ("pod",))
        x = jnp.asarray(np.random.RandomState(0).randn(8, 64),
                        jnp.float32)

        def f(x):
            exact = jax.lax.psum(x, "pod")
            q = quantized_psum(x, "pod", FixedPointType(8, 1))
            return exact, q

        exact, q = jax.shard_map(
            f, mesh=mesh, in_specs=jax.sharding.PartitionSpec("pod"),
            out_specs=jax.sharding.PartitionSpec("pod"))(x)
        rel = float(jnp.abs(exact - q).max() /
                    (jnp.abs(exact).max() + 1e-9))
        assert rel < 0.05, rel           # int8 payload: ~1% error
        print("COMPRESSION OK", rel)
    """)
    assert "COMPRESSION OK" in out


@pytest.mark.slow
def test_elastic_checkpoint_across_meshes():
    """Save sharded on a (4,2) mesh, restore onto (2,4) and (8,1)."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from repro.launch.mesh import make_mesh
        from repro.checkpoint import CheckpointManager
        from repro.configs import get_config
        from repro.dist.sharding import param_specs, named
        from repro.models.api import get_family

        cfg = get_config("gemma-2b").smoke()
        fam = get_family(cfg)
        params = fam.init(jax.random.PRNGKey(0), cfg)
        d = tempfile.mkdtemp()
        m1 = make_mesh((4, 2), ("data", "model"))
        p1 = jax.device_put(params, named(param_specs(params, m1), m1))
        mgr = CheckpointManager(d)
        mgr.save({"params": p1}, 1, blocking=True)

        for shape in [(2, 4), (8, 1)]:
            m2 = make_mesh(shape, ("data", "model"))
            sh2 = named(param_specs({"params": params}, m2), m2)
            restored, step = mgr.restore_latest({"params": params},
                                                shardings=sh2)
            assert step == 1
            for a, b in zip(jax.tree_util.tree_leaves(restored),
                            jax.tree_util.tree_leaves(params)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("ELASTIC OK")
    """)
    assert "ELASTIC OK" in out


@pytest.mark.slow
def test_pod_sharded_grad_compression_lowers():
    """shard_map(manual over pod, auto inside) + quantized psum compiles
    on a (2,2,2) pod mesh."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from jax.sharding import PartitionSpec as P
        from repro.core.qtypes import FixedPointType
        from repro.dist.compression import make_pod_sharded_grad_fn

        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))

        def grad_fn(params, batch):
            def loss(p):
                return jnp.mean((batch @ p) ** 2)
            return jax.grad(loss)(params), {"loss": jnp.zeros(())}

        f = make_pod_sharded_grad_fn(
            grad_fn, mesh,
            in_specs=(P(), P("pod")),
            out_specs=(P(), P()),
            qtype=FixedPointType(8, 1))
        params = jnp.asarray(np.random.RandomState(0).randn(16, 4),
                             jnp.float32)
        batch = jnp.asarray(np.random.RandomState(1).randn(8, 16),
                            jnp.float32)
        with mesh:
            g, m = jax.jit(f)(params, batch)
        assert g.shape == params.shape
        print("POD COMPRESS OK", float(jnp.abs(g).max()))
    """)
    assert "POD COMPRESS OK" in out
