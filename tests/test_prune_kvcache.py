"""Beyond-deliverable features: pruning (paper §III weights compression),
int8 KV cache, error-feedback compression."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.prune import (apply_masks, magnitude_mask, make_masks,
                              nm_mask, sparsity)


class TestPruning:
    @settings(max_examples=25, deadline=None)
    @given(st.floats(0.1, 0.9), st.integers(1, 5))
    def test_magnitude_mask_sparsity(self, target, seed):
        w = jnp.asarray(np.random.RandomState(seed).randn(32, 48))
        m = magnitude_mask(w, target)
        kept = float(jnp.mean(m))
        assert abs(kept - (1 - target)) < 0.05
        # the kept entries are exactly the largest-magnitude ones
        thresh = float(jnp.abs(w * m)[m].min())
        assert float(jnp.abs(w * ~m).max()) <= thresh + 1e-7

    @pytest.mark.parametrize("n,m", [(2, 4), (1, 4), (1, 2)])
    def test_nm_mask_structure(self, n, m):
        w = jnp.asarray(np.random.RandomState(0).randn(64, 16))
        mask = nm_mask(w, n, m)
        groups = mask.reshape(64 // m, m, 16)
        counts = jnp.sum(groups, axis=1)
        assert bool(jnp.all(counts == n))
        # kept entries dominate dropped ones within each group
        wg = jnp.abs(w.reshape(64 // m, m, 16))
        kept_min = jnp.min(jnp.where(groups, wg, jnp.inf), axis=1)
        drop_max = jnp.max(jnp.where(~groups, wg, -jnp.inf), axis=1)
        assert bool(jnp.all(kept_min >= drop_max - 1e-7))

    def test_masked_training_keeps_sparsity_and_learns(self):
        """The paper's training-phase sparsity enforcement: mask survives
        optimization and the masked model still fits the task."""
        rng = np.random.RandomState(0)
        W_true = rng.randn(16, 8).astype(np.float32)
        x = jnp.asarray(rng.randn(256, 16), jnp.float32)
        y = x @ W_true
        params = {"w": jnp.asarray(rng.randn(16, 8), jnp.float32)}
        masks = make_masks(params, structured=(2, 4))
        params = apply_masks(params, masks)

        def loss(p):
            return jnp.mean((x @ p["w"] - y) ** 2)

        l0 = float(loss(params))
        for _ in range(100):
            g = jax.grad(loss)(params)
            params = jax.tree_util.tree_map(lambda p, gg: p - 0.02 * gg,
                                            params, g)
            params = apply_masks(params, masks)
        assert sparsity(params) == 0.5
        # a 2:4-masked linear model cannot fit a dense target exactly —
        # assert substantial optimization under the mask, not exact fit
        assert float(loss(params)) < 0.7 * l0


class TestInt8KVCache:
    def test_serving_consistency_and_size(self):
        from repro.configs import get_config
        from repro.models.api import get_family
        from repro.nn.context import QuantContext
        ctx = QuantContext(compute_dtype=jnp.float32)
        cfg = get_config("yi-6b").smoke()
        fam = get_family(cfg)
        params = fam.init(jax.random.PRNGKey(0), cfg)
        B, S, DEC = 2, 8, 3
        toks = jax.random.randint(jax.random.PRNGKey(1), (B, S + DEC), 0,
                                  cfg.vocab)

        def run(dtype):
            cache = fam.init_cache(cfg, B, S + DEC, dtype)
            lg, cache = fam.prefill(params, toks[:, :S], cache, cfg, ctx)
            pos = jnp.full((B,), S, jnp.int32)
            for t in range(DEC):
                lg, cache = fam.decode_step(params, toks[:, S + t:S + t + 1],
                                            cache, pos + t, cfg, ctx)
            return lg, cache

        lg_f, cache_f = run(jnp.float32)
        lg_q, cache_q = run(jnp.int8)
        rel = float(jnp.abs(lg_f - lg_q).max() / (jnp.abs(lg_f).max()))
        assert rel < 0.05, rel
        assert bool(jnp.all(jnp.argmax(lg_f[:, 0], -1)
                            == jnp.argmax(lg_q[:, 0], -1)))
        # payload really is int8
        assert cache_q["dense"]["k"].dtype == jnp.int8

    def test_quantize_kv_roundtrip_bound(self):
        from repro.nn.attention import _quantize_kv
        u = jnp.asarray(np.random.RandomState(0).randn(2, 4, 8, 32),
                        jnp.float32)
        q, s = _quantize_kv(u)
        back = q.astype(jnp.float32) * s.astype(jnp.float32)
        err = jnp.abs(back - u)
        amax = jnp.abs(u).max(axis=-1, keepdims=True)
        # half-ulp of the int8 grid + the bf16 scale's own rounding error
        bound = amax / 127.0 * 0.5 + amax * 2.0 ** -7
        assert bool(jnp.all(err <= bound + 1e-6))


class TestErrorFeedback:
    def test_residual_cancels_bias(self):
        """Over repeated reductions of the SAME tensor, error feedback
        makes the running mean of reduced values converge to the exact
        reduction (plain quantization keeps a constant bias)."""
        import os
        import subprocess
        import sys
        import textwrap
        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env["PYTHONPATH"] = "src"
        env["JAX_PLATFORMS"] = "cpu"  # a child never reaches for a chip
        code = textwrap.dedent("""
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import PartitionSpec as P
            from repro.core.qtypes import FixedPointType
            from repro.dist.compression import (quantized_psum,
                                                quantized_psum_ef)
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((4,), ("pod",))
            x = jnp.asarray(np.random.RandomState(0).randn(4, 64),
                            jnp.float32)
            qt = FixedPointType(4, 1)   # brutal 4-bit to expose bias

            def f(x):
                exact = jax.lax.psum(x, "pod")
                r = jnp.zeros_like(x)
                acc_ef = jnp.zeros_like(x)
                acc_q = jnp.zeros_like(x)
                for _ in range(24):
                    out, r = quantized_psum_ef(x, r, "pod", qt)
                    acc_ef += out
                    acc_q += quantized_psum(x, "pod", qt)
                return exact, acc_ef / 24, acc_q / 24

            exact, mean_ef, mean_q = jax.shard_map(
                f, mesh=mesh, in_specs=P("pod"), out_specs=P("pod"))(x)
            err_ef = float(jnp.abs(mean_ef - exact).max())
            err_q = float(jnp.abs(mean_q - exact).max())
            print("EF", err_ef, "Q", err_q)
            assert err_ef < 0.5 * err_q, (err_ef, err_q)
        """)
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, timeout=300,
                           env=env, cwd=os.path.dirname(
                               os.path.dirname(os.path.abspath(__file__))))
        assert r.returncode == 0, r.stdout + r.stderr
