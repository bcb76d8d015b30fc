"""The roofline's HLO analyzer: loop correction, dot flops, collectives."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.roofline import RooflineReport, peaks, roofline


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


class TestLoopCorrection:
    def test_scan_equals_unroll(self):
        W = jax.ShapeDtypeStruct((16, 128, 128), jnp.float32)
        X = jax.ShapeDtypeStruct((64, 128), jnp.float32)

        def body(x, w):
            return jnp.tanh(x @ w), None

        def scanned(x, ws):
            return jax.lax.scan(body, x, ws)[0]

        def unrolled(x, ws):
            for i in range(16):
                x, _ = body(x, ws[i])
            return x

        fs = analyze_hlo(_compile(scanned, X, W).as_text(), 1).flops
        fu = analyze_hlo(_compile(unrolled, X, W).as_text(), 1).flops
        assert abs(fs - fu) / fu < 0.01
        expected = 2 * 64 * 128 * 128 * 16
        assert abs(fs - expected) / expected < 0.02

    def test_nested_scans_multiply(self):
        W = jax.ShapeDtypeStruct((4, 8, 32, 32), jnp.float32)
        X = jax.ShapeDtypeStruct((16, 32), jnp.float32)

        def inner(x, w):
            return x @ w, None

        def outer(x, ws):
            def step(x, wstack):
                return jax.lax.scan(inner, x, wstack)[0], None
            return jax.lax.scan(step, x, ws)[0]

        f = analyze_hlo(_compile(outer, X, W).as_text(), 1).flops
        expected = 2 * 16 * 32 * 32 * 8 * 4
        assert abs(f - expected) / expected < 0.05

    def test_dot_general_batched(self):
        A = jax.ShapeDtypeStruct((4, 64, 32), jnp.float32)
        B = jax.ShapeDtypeStruct((4, 32, 16), jnp.float32)

        def f(a, b):
            return jnp.einsum("bij,bjk->bik", a, b)

        flops = analyze_hlo(_compile(f, A, B).as_text(), 1).flops
        expected = 2 * 4 * 64 * 32 * 16
        assert abs(flops - expected) / expected < 0.02


class TestCollectiveParsing:
    HLO = """
HloModule test, entry_computation_layout={()->f32[]}

ENTRY %main.1 (p: f32[128,256]) -> f32[128,256] {
  %p = f32[128,256]{1,0} parameter(0)
  %all-reduce.1 = f32[128,256]{1,0} all-reduce(%p), replica_groups=[16,16]<=[256], to_apply=%add
  %all-gather.2 = f32[128,4096]{1,0} all-gather(%all-reduce.1), replica_groups=[16,16]<=[256], dimensions={1}
  ROOT %collective-permute.3 = f32[128,256]{1,0} collective-permute(%p), source_target_pairs={{0,1}}
}
"""

    def test_wire_bytes_ring_model(self):
        a = analyze_hlo(self.HLO, 256)
        kinds = {c["kind"]: c for c in a.collectives}
        t_ar = 128 * 256 * 4
        assert kinds["all-reduce"]["wire_bytes"] == pytest.approx(
            2 * t_ar * 15 / 16)
        t_ag = 128 * 4096 * 4
        assert kinds["all-gather"]["wire_bytes"] == pytest.approx(
            t_ag * 15 / 16)
        assert kinds["collective-permute"]["wire_bytes"] == \
            pytest.approx(128 * 256 * 4)


class TestRooflineReport:
    def test_terms_and_bottleneck(self):
        rep = RooflineReport(
            arch="x", shape="train_4k", mesh="single", chips=256,
            flops_per_chip=197e12, bytes_per_chip=819e9,
            wire_bytes_per_chip=0.0, bytes_all_per_chip=1e12,
            compute_s=1.0, memory_s=1.0, collective_s=0.1,
            model_flops=197e12 * 256 * 0.5, peak_flops=197e12)
        assert rep.bottleneck in ("compute", "memory")
        assert rep.step_time == 1.0
        assert rep.mfu == pytest.approx(0.5)

    def test_roofline_from_text(self):
        rep = roofline(arch="t", shape="s", mesh="single", chips=256,
                       cost={"flops": 1.0},
                       hlo_text=TestCollectiveParsing.HLO,
                       model_flops=1e12, hw=peaks("TPU v5 lite"))
        assert rep.collective_s > 0
        assert rep.raw_cost_analysis["flops"] == 1.0

    def test_peaks_keyed_by_device_kind(self):
        hw = peaks("TPU v5 lite")
        assert (hw.peak_flops, hw.hbm_bw) == (197e12, 819e9)
        assert hw.source
        with pytest.raises(KeyError):
            peaks("cpu")            # no default row for an unknown chip
