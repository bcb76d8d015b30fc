"""Ahead-of-time compiles of the main-path kernels for a described v5e.

Each case lowers one Pallas kernel at yi-6b widths (d_model 4096, 32 q /
4 kv heads of 128, d_ff 11008) for a TPU v5e that is described, not
attached, and asserts the compiled program holds the Mosaic kernel
(``tpu_custom_call``).  Nothing runs: these catch what the chip's
compiler refuses (tiling, VMEM, unsupported lowerings, kernels GSPMD
cannot partition) without a chip.

The topology is described inside a module fixture, never while a module
is imported, and the persistent compilation cache is off around the
compiles (a described chip's executable cannot be read back).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from repro.core.tables import TableSpec
from repro.kernels.flash_attention import (flash_attention_pallas,
                                           paged_attention_pallas)
from repro.kernels.lut_activation import lut_activation_pallas
from repro.kernels.qmatmul import qmatmul_pallas

# yi-6b widths
D, HQ, HKV, DH, FF = 4096, 32, 4, 128, 11008
B, PS, WIDTH, POOL = 8, 16, 43, 274         # chip_smoke's page geometry


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _has_kernel(fn, *args) -> bool:
    return "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


def test_qmatmul_with_bias(one_chip):
    args = (_spec((8, D), jnp.int8, one_chip),
            _spec((D, FF), jnp.int8, one_chip),
            _spec((8, 1), jnp.float32, one_chip),
            _spec((1, FF), jnp.float32, one_chip),
            _spec((FF,), jnp.float32, one_chip))
    assert _has_kernel(lambda a, b, sa, sb, bias: qmatmul_pallas(
        a, b, sa, sb, bias, out_dtype=jnp.bfloat16), *args)


def _paged_args(one_chip, s):
    return (_spec((B, HQ, s, DH), jnp.bfloat16, one_chip),
            _spec((POOL, HKV, PS, DH), jnp.float32, one_chip),
            _spec((POOL, HKV, PS, DH), jnp.float32, one_chip),
            _spec((B, WIDTH), jnp.int32, one_chip),
            _spec((B,), jnp.int32, one_chip))


@pytest.mark.parametrize("s,kv_split,pages_per_step", [
    (1, 1, 1),            # decode, the unsplit kernel
    (1, None, None),      # decode at the auto knobs (split kernel)
    (16, None, None),     # 16-token chunked prefill
])
def test_paged_attention(one_chip, s, kv_split, pages_per_step):
    assert _has_kernel(lambda *a: paged_attention_pallas(
        *a, kv_split=kv_split, pages_per_step=pages_per_step),
        *_paged_args(one_chip, s))


@pytest.mark.parametrize("s", [1, 128], ids=["decode", "prefill_chunk"])
def test_paged_attention_glm4_geometry(one_chip, s):
    """glm4-9b's: 32 q heads over 2 kv heads of 128 (16 per kv head), a
    520-page table for 8192-token contexts, 3800 pages of 16 tokens, at
    the knobs the engine resolves for it (kv_split 16, pages_per_step 8)."""
    args = (_spec((B, 32, s, DH), jnp.bfloat16, one_chip),
            _spec((3801, 2, PS, DH), jnp.float32, one_chip),
            _spec((3801, 2, PS, DH), jnp.float32, one_chip),
            _spec((B, 520), jnp.int32, one_chip),
            _spec((B,), jnp.int32, one_chip))
    assert _has_kernel(lambda *a: paged_attention_pallas(
        *a, kv_split=16, pages_per_step=8), *args)


def test_flash_attention(one_chip):
    q = _spec((1, HQ, 2048, DH), jnp.bfloat16, one_chip)
    kv = _spec((1, HKV, 2048, DH), jnp.bfloat16, one_chip)
    assert _has_kernel(lambda q, k, v: flash_attention_pallas(
        q, k, v, causal=True), q, kv, kv)


@pytest.mark.parametrize("indexing", ["interp", "nearest", "trunc"])
def test_lut_activation(one_chip, indexing):
    spec = TableSpec("silu_gate", 1024, -10.0, 10.0, None, indexing)
    x = _spec((B, 128, FF), jnp.bfloat16, one_chip)
    assert _has_kernel(lambda x: lut_activation_pallas(x, spec), x)


def test_qmatmul_lut_epilogue(one_chip):
    spec = TableSpec("silu_gate", 1024, -10.0, 10.0, None, "interp")
    args = (_spec((8, D), jnp.int8, one_chip),
            _spec((D, FF), jnp.int8, one_chip),
            _spec((8, 1), jnp.float32, one_chip),
            _spec((1, FF), jnp.float32, one_chip))
    assert _has_kernel(lambda a, b, sa, sb: qmatmul_pallas(
        a, b, sa, sb, act_spec=spec, act_gated=True,
        out_dtype=jnp.bfloat16), *args)


def test_paged_attention_shard_mapped(topo, monkeypatch):
    """The model's paged-kernel call on a (1, 4) (data, model) mesh: q
    heads and kv heads split over ``model``, the replicated pool sliced
    by head — the Mosaic call must sit inside shard_map, or the
    compiler refuses to partition it."""
    import repro.kernels.ops as ops
    from repro.dist.constrain import use_mesh
    from repro.launch.mesh import make_mesh
    from repro.nn.attention import _paged_kernel
    from repro.nn.context import QuantContext
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    mesh = make_mesh((1, 4), ("data", "model"), devices=topo.devices[:4])
    heads = NamedSharding(mesh, P(None, "model"))
    rep = NamedSharding(mesh, P())
    q = _spec((B, HQ, 1, DH), jnp.bfloat16, heads)
    pages = _spec((POOL, HKV, PS, DH), jnp.float32, rep)
    bt = _spec((B, WIDTH), jnp.int32, rep)
    pos = _spec((B,), jnp.int32, rep)
    ctx = QuantContext(kv_split=4, pages_per_step=8)
    with use_mesh(mesh):
        text = jax.jit(lambda q, k, v, bt, pos: _paged_kernel(
            q, k, v, bt, pos, ctx)).lower(q, pages, pages, bt, pos) \
            .compile().as_text()
    assert "tpu_custom_call" in text
