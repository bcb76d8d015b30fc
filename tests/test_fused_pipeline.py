"""Fused quantized dense pipeline tests.

Covers the three legs end to end:

* pre-quantized (QTensor) weights through ``linear()`` — bit-exact vs
  the dynamic-quant path, and ZERO weight-quantization ops per forward
  (counted in the jaxpr);
* the fused qmatmul epilogue (bias + LUT activation) vs the explicit
  three-op ``ref`` composition, and the one-``pallas_call`` claim;
* batched chunked prefill vs the per-token decode loop (same first
  generated token), plus engine hygiene (empty prompts, slot
  invalidation, live slots undisturbed by refills).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.precision import PrecisionPolicy
from repro.core.qtypes import FixedPointType, QTensor
from repro.core.quantize import dequantize_params, ptq_params
from repro.core.tables import TableSpec
from repro.kernels.ops import lut_activation, qmatmul
from repro.kernels.ref import lut_activation_ref, qmatmul_ref
from repro.launch.hlo_analysis import count_jaxpr_primitive as \
    _count_primitive
from repro.nn.context import QuantContext
from repro.nn.linear import linear, linear_init

RNG = np.random.RandomState(0)
QT8 = FixedPointType(8, 4)


def _int8_ctx(**kw):
    return QuantContext(mode="int8", policy=PrecisionPolicy.uniform(QT8),
                        compute_dtype=jnp.float32, **kw)


# ===========================================================================
class TestPrequantLinear:
    def test_qtensor_weights_bitexact_vs_dynamic(self):
        ctx = _int8_ctx()
        p = linear_init(jax.random.PRNGKey(0), 64, 48, bias=True)
        p["b"] = jnp.asarray(RNG.randn(48), jnp.float32)
        x = jnp.asarray(RNG.randn(3, 5, 64), jnp.float32)
        y_dyn = linear(p, x, ctx, path="mlp/up")
        qp = ptq_params(p, QT8)
        assert isinstance(qp["w"], QTensor)
        assert not isinstance(qp["b"], QTensor)  # bias stays float
        y_pre = linear(qp, x, ctx, path="mlp/up")
        np.testing.assert_array_equal(np.asarray(y_dyn), np.asarray(y_pre))

    def test_zero_weight_quant_ops_per_forward(self):
        """Acceptance: with QTensor weights the forward jaxpr contains NO
        weight calibrate/round — only the single activation round."""
        ctx = _int8_ctx()
        p = linear_init(jax.random.PRNGKey(0), 64, 48)
        qp = ptq_params(p, QT8)
        x = jnp.asarray(RNG.randn(4, 64), jnp.float32)

        dyn = jax.make_jaxpr(lambda xx: linear(p, xx, ctx))(x)
        pre = jax.make_jaxpr(lambda xx: linear(qp, xx, ctx))(x)
        n_dyn = _count_primitive(dyn.jaxpr, "round")
        n_pre = _count_primitive(pre.jaxpr, "round")
        # dynamic path rounds activations AND weights; prequant only acts
        assert n_dyn == 2, n_dyn
        assert n_pre == 1, n_pre
        # the weight max-abs calibration also disappears
        assert _count_primitive(pre.jaxpr, "reduce_max") \
            < _count_primitive(dyn.jaxpr, "reduce_max")

    def test_stacked_weights_scan_sliceable(self):
        """ptq scales keep the leading stack axis so lax.scan can slice
        QTensor params layer by layer."""
        w = jnp.asarray(RNG.randn(4, 16, 32), jnp.float32)   # (L, in, out)
        q = ptq_params({"w": w}, QT8)["w"]
        assert q.data.shape == (4, 16, 32)
        assert q.scale.shape == (4, 1, 32)

        def body(carry, p_l):
            y = linear(p_l, carry, _int8_ctx())
            return jnp.tanh(y[..., :16]), None

        out, _ = jax.lax.scan(body, jnp.ones((2, 16)), {"w": q})
        assert out.shape == (2, 16)

    def test_embed_router_and_conv_stay_dense(self):
        params = {"embed": {"table": jnp.ones((32, 8))},
                  "moe": {"router": jnp.ones((8, 4)),
                          "w_gate": jnp.ones((4, 8, 16))},
                  "ssm": {"conv_w": jnp.ones((4, 8)),
                          "in_proj": {"w": jnp.ones((8, 16))}}}
        q = ptq_params(params, QT8)
        assert not isinstance(q["embed"]["table"], QTensor)
        assert not isinstance(q["moe"]["router"], QTensor)
        assert not isinstance(q["ssm"]["conv_w"], QTensor)
        assert isinstance(q["moe"]["w_gate"], QTensor)
        assert isinstance(q["ssm"]["in_proj"]["w"], QTensor)

    def test_mla_family_serves_with_ptq_params(self):
        """wkv_b is consumed raw (reshaped, not via linear) — the PTQ
        QTensor must dequantize instead of crashing (deepseek/MLA)."""
        from repro.configs import get_config
        from repro.models.api import get_family
        cfg = get_config("deepseek-v2-236b").smoke()
        ctx = _int8_ctx()
        fam = get_family(cfg)
        params = fam.init(jax.random.PRNGKey(0), cfg)
        qparams = ptq_params(params, ctx.policy)
        cache = fam.init_cache(cfg, 1, 12, jnp.float32)
        toks = jnp.asarray(RNG.randint(0, cfg.vocab, (1, 4)), jnp.int32)
        last, cache = fam.prefill(qparams, toks, cache, cfg, ctx)
        lg, _ = fam.decode_step(qparams, toks[:, :1], cache,
                                jnp.asarray([4], jnp.int32), cfg, ctx)
        assert np.isfinite(np.asarray(last)).all()
        assert np.isfinite(np.asarray(lg)).all()

    def test_qtensor_specs_keep_payload_sharding(self):
        """param_specs must not let the scale's size-1 axes strip the
        payload's FSDP axis — payload and scale get separate specs."""
        from jax.sharding import PartitionSpec as P
        from repro.dist.sharding import named, param_specs
        from repro.launch.mesh import make_local_mesh
        mesh = make_local_mesh()
        qp = ptq_params({"blk": {"w": jnp.ones((128, 256))}}, QT8)
        specs = param_specs(qp, mesh)
        assert isinstance(specs["blk"]["w"], QTensor)
        assert len(specs["blk"]["w"].data) == 2      # payload rule intact
        # the scale's own spec is guarded against the SCALE's shape: any
        # mesh axis assigned to its size-1 dim must divide 1
        s_spec = specs["blk"]["w"].scale
        scale_shape = qp["blk"]["w"].scale.shape
        for axis, dim in zip(tuple(s_spec), scale_shape):
            if axis is not None:
                assert dim % mesh.shape[axis] == 0
        put = jax.device_put(qp, named(specs, mesh))  # trees must line up
        assert isinstance(put["blk"]["w"], QTensor)

    def test_qtensor_under_float_modes_dequantizes(self):
        """QTensor weights still work when the context is not int8."""
        p = linear_init(jax.random.PRNGKey(1), 32, 16)
        qp = ptq_params(p, QT8)
        x = jnp.asarray(RNG.randn(4, 32), jnp.float32)
        ctx = QuantContext(compute_dtype=jnp.float32)
        y_q = linear(qp, x, ctx)
        y_ref = x @ dequantize_params(qp)["w"]
        np.testing.assert_allclose(np.asarray(y_q), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-5)


# ===========================================================================
class TestFusedEpilogue:
    def _operands(self, m=32, k=128, n=64):
        a = RNG.randint(-127, 128, (m, k)).astype(np.int8)
        b = RNG.randint(-127, 128, (k, n)).astype(np.int8)
        sa = (RNG.rand(m, 1).astype(np.float32) + 0.1) * 0.005
        sb = (RNG.rand(1, n).astype(np.float32) + 0.1) * 0.005
        bias = RNG.randn(n).astype(np.float32)
        return a, b, sa, sb, bias

    @pytest.mark.parametrize("indexing", ["interp", "nearest", "trunc"])
    @pytest.mark.parametrize("gated", [False, True])
    def test_fused_matches_ref_composition(self, indexing, gated):
        a, b, sa, sb, bias = self._operands()
        fn = "silu_gate" if gated else "sigmoid"
        spec = TableSpec(fn, 512, -10.0, 10.0, None, indexing)
        # explicit composition: qmatmul -> +bias -> LUT
        y = qmatmul_ref(a, b, sa, sb)
        y = y + bias.reshape(1, -1)
        z = lut_activation_ref(y, spec)
        want = y * z if gated else z
        got = qmatmul(a, b, sa, sb, bias=bias, act_spec=spec,
                      act_gated=gated, backend="pallas")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)
        got_ref = qmatmul(a, b, sa, sb, bias=bias, act_spec=spec,
                          act_gated=gated, backend="ref")
        np.testing.assert_array_equal(np.asarray(got_ref), np.asarray(want))

    def test_bias_only_epilogue(self):
        a, b, sa, sb, bias = self._operands()
        want = np.asarray(qmatmul_ref(a, b, sa, sb)) + bias.reshape(1, -1)
        got = qmatmul(a, b, sa, sb, bias=bias, backend="pallas")
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4,
                                   atol=1e-4)

    def test_fused_is_one_pallas_call(self):
        """Acceptance: one kernel launch where the unfused path used
        three (matmul, bias add, activation)."""
        a, b, sa, sb, bias = self._operands()
        spec = TableSpec("sigmoid", 256, -8.0, 8.0, None, "interp")

        fused = jax.make_jaxpr(lambda: qmatmul(
            a, b, sa, sb, bias=bias, act_spec=spec,
            backend="pallas"))()
        unfused = jax.make_jaxpr(lambda: lut_activation(
            qmatmul(a, b, sa, sb, backend="pallas") + bias.reshape(1, -1),
            spec, backend="pallas"))()
        assert _count_primitive(fused.jaxpr, "pallas_call") == 1
        assert _count_primitive(unfused.jaxpr, "pallas_call") == 2

    def test_linear_fuses_under_int8_lut(self):
        """linear(act=...) under int8+LUT emits ONE pallas_call and
        matches the unfused act_fn composition."""
        from repro.nn.activations import act_fn
        ctx = _int8_ctx(use_lut=True, table_indexing="interp",
                        backend="pallas")
        p = linear_init(jax.random.PRNGKey(2), 64, 32, bias=True)
        p["b"] = jnp.asarray(RNG.randn(32), jnp.float32)
        qp = ptq_params(p, QT8)
        x = jnp.asarray(RNG.randn(4, 64), jnp.float32)

        fused = jax.make_jaxpr(
            lambda xx: linear(qp, xx, ctx, path="mlp/up", act="silu"))(x)
        assert _count_primitive(fused.jaxpr, "pallas_call") == 1

        y_fused = linear(qp, x, ctx, path="mlp/up", act="silu")
        y_unfused = act_fn("silu", linear(qp, x, ctx, path="mlp/up"), ctx,
                           path="mlp/up/act")
        np.testing.assert_allclose(np.asarray(y_fused),
                                   np.asarray(y_unfused), rtol=1e-4,
                                   atol=1e-4)


# ===========================================================================
class TestBatchedPrefill:
    @pytest.fixture(scope="class")
    def setup(self):
        from repro.configs import get_config
        from repro.models.api import get_family
        cfg = get_config("gemma-2b").smoke()
        ctx = QuantContext(compute_dtype=jnp.float32)
        fam = get_family(cfg)
        params = fam.init(jax.random.PRNGKey(0), cfg)
        from repro.launch.mesh import make_local_mesh
        return cfg, ctx, params, make_local_mesh()

    def _engine(self, setup, **kw):
        from repro.launch.serve import Engine
        cfg, ctx, params, mesh = setup
        return Engine(cfg, ctx, params, mesh, batch=2, max_len=40, **kw)

    def test_first_token_matches_per_token_loop(self, setup):
        """Acceptance: batched chunked prefill produces the same first
        generated token (and subsequent decode) as the old per-token
        decode loop."""
        from repro.dist.constrain import use_mesh
        rs = np.random.RandomState(0)
        prompts = {0: rs.randint(0, setup[0].vocab, (13,)),
                   1: rs.randint(0, setup[0].vocab, (7,))}
        with use_mesh(setup[3]):
            chunked = self._engine(setup, prefill_chunk=4)
            chunked.add_requests(prompts)
            looped = self._engine(setup)
            looped.chunked = False          # force the legacy loop
            looped.add_requests(prompts)
            np.testing.assert_array_equal(chunked.tokens, looped.tokens)
            for _ in range(4):
                chunked.step()
                looped.step()
            assert chunked.outputs == looped.outputs

    def test_chunked_prefill_call_count(self, setup):
        """Prompt ingestion is O(ceil(max_len / chunk)) full-batch steps,
        not O(prompt_len) per slot."""
        from repro.dist.constrain import use_mesh
        rs = np.random.RandomState(1)
        with use_mesh(setup[3]):
            eng = self._engine(setup, prefill_chunk=4)
            calls = {"n": 0}
            inner = eng.prefill

            def counting_prefill(*a, **k):
                calls["n"] += 1
                return inner(*a, **k)

            eng.prefill = counting_prefill
            eng.add_requests({0: rs.randint(0, setup[0].vocab, (13,)),
                              1: rs.randint(0, setup[0].vocab, (7,))})
            assert calls["n"] == 4          # ceil(13 / 4) for BOTH slots

    def test_empty_prompt_is_defined(self, setup):
        from repro.dist.constrain import use_mesh
        with use_mesh(setup[3]):
            eng = self._engine(setup)
            eng.add_requests({0: np.zeros((0,), np.int32)})
            assert eng.live[0]
            assert eng.pos[0] == 1          # the implicit BOS pad token
            assert 0 <= eng.tokens[0, 0] < setup[0].vocab

    def test_finish_invalidates_slot_cache(self, setup):
        from repro.dist.constrain import use_mesh
        rs = np.random.RandomState(2)
        with use_mesh(setup[3]):
            eng = self._engine(setup)
            eng.add_requests({0: rs.randint(0, setup[0].vocab, (6,)),
                              1: rs.randint(0, setup[0].vocab, (6,))})
            eng.step()
            eng.finish(0)
            assert not eng.live[0] and eng.pos[0] == 0
            for leaf in jax.tree_util.tree_leaves(eng.cache):
                assert not np.asarray(leaf[:, 0]).any()   # slot 0 zeroed
                assert np.asarray(leaf[:, 1]).any()       # slot 1 intact

    def test_refill_does_not_disturb_live_slot(self, setup):
        """A mid-flight batched refill must leave a generating slot's
        token stream identical to an undisturbed run."""
        from repro.dist.constrain import use_mesh
        rs = np.random.RandomState(3)
        p0 = rs.randint(0, setup[0].vocab, (9,))
        p1 = rs.randint(0, setup[0].vocab, (11,))
        with use_mesh(setup[3]):
            solo = self._engine(setup, prefill_chunk=4)
            solo.add_requests({0: p0})
            for _ in range(6):
                solo.step()

            eng = self._engine(setup, prefill_chunk=4)
            eng.add_requests({0: p0})
            for _ in range(3):
                eng.step()
            eng.add_requests({1: p1})       # refill while slot 0 is live
            for _ in range(3):
                eng.step()
        assert eng.outputs[0] == solo.outputs[0]
