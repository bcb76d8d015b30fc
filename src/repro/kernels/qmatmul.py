"""Pallas TPU kernel: int8 × int8 → int32 quantized matmul with fused
requantization and an optional fused epilogue (bias + LUT activation).

The MXU adaptation of the paper's fixed-point datapath: on FPGA the
``ac_fixed`` multiply-accumulates map to DSP slices; on TPU the analogous
hard resource is the MXU's native int8 systolic path with int32
accumulation.  The kernel tiles (M, N, K) into MXU-aligned blocks
(multiples of 128), accumulates partial products in an int32 VMEM scratch
across the K grid dimension, and fuses the dequantization (per-row ×
per-column scales) into the final K step — so the narrow int8 operands are
what moves through HBM→VMEM, which is the entire bandwidth win of
quantization.

**Fused epilogue** (the hls4ml dense→activation dataflow fusion, ported):
hls4ml's win is that dense output never round-trips through memory before
the activation LUT — the fixed-point result streams straight into the
BRAM table.  Here the same fusion happens in the final K step: while the
(bm, bn) accumulator tile is still VMEM-resident, the kernel optionally

* adds a per-column ``bias`` row, and
* applies a LUT activation (a :class:`~repro.core.tables.TableSpec`
  constant table riding in VMEM, gathered on the VPU; ``act_gated=True``
  computes ``y * table(y)`` — the exact gated silu/gelu form).

One ``pallas_call`` therefore replaces three kernel launches (matmul →
bias add → LUT activation) and two (M, N) HBM round trips of the f32
intermediate.  The pre-quantized serving path
(:func:`repro.core.quantize.ptq_params` → QTensor weights →
:func:`repro.nn.linear.linear`) lands here with zero per-forward weight
quantization work.

VMEM working set per grid step: bm*bk + bk*bn (int8) + bm*bn*4 (acc)
+ bm*bn*out bytes (+ bn*4 bias + 4*n table when fused).  Defaults
(256, 256, 256) → ~0.5 MiB, comfortably inside the ~16 MiB v5e VMEM with
double-buffering headroom; a 1024-entry table adds 4 KiB.

The ``reuse_factor`` knob from the paper maps here: larger ``bk`` = more
MACs per loaded block (lower "reuse", more parallel resource/VMEM), smaller
``bk`` = the same MXU tile re-used across more sequential K steps.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.tables import TableSpec
from .lut_activation import apply_table, table_tile

__all__ = ["qmatmul_pallas"]


def _kernel(*refs, k_steps: int, has_bias: bool, act_spec, act_gated: bool):
    a_ref, b_ref, sa_ref, sb_ref = refs[:4]
    rest = list(refs[4:])
    bias_ref = rest.pop(0) if has_bias else None
    t_ref = rest.pop(0) if act_spec is not None else None
    o_ref, acc_ref = rest

    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    b = b_ref[...]
    acc_ref[...] += jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)

    @pl.when(k == k_steps - 1)
    def _finish():
        sa = sa_ref[...]            # (bm, 1) f32
        sb = sb_ref[...]            # (1, bn) f32
        y = acc_ref[...].astype(jnp.float32) * sa * sb
        if has_bias:
            y = y + bias_ref[...]   # (1, bn) f32
        if act_spec is not None:    # LUT epilogue on the VMEM-resident tile
            y = apply_table(y, t_ref[...], lo=act_spec.lo,
                            step_inv=1.0 / act_spec.step, n=act_spec.n,
                            indexing=act_spec.indexing, gated=act_gated)
        o_ref[...] = y.astype(o_ref.dtype)


def _pad_to(x, axis, mult):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


@functools.partial(jax.jit, static_argnames=("out_dtype", "bm", "bn", "bk",
                                             "act_spec", "act_gated",
                                             "interpret"))
def qmatmul_pallas(a_data: jnp.ndarray, b_data: jnp.ndarray,
                   a_scale: jnp.ndarray, b_scale: jnp.ndarray,
                   bias: Optional[jnp.ndarray] = None,
                   *, out_dtype=jnp.float32,
                   act_spec: Optional[TableSpec] = None,
                   act_gated: bool = False,
                   bm: int = 256, bn: int = 256,
                   bk: int = 256, interpret: bool = False) -> jnp.ndarray:
    """(M,K)int8 @ (K,N)int8 with per-row/per-col scales → (M,N) float.

    ``a_scale`` broadcasts as (M, 1) or scalar; ``b_scale`` as (1, N) or
    scalar.  Shapes are padded to block multiples transparently.

    ``bias``: optional (N,)/(1, N) f32 row fused into the final K step.
    ``act_spec``: optional LUT activation applied in the same step
    (``act_gated=True`` → ``y * table(y)``, the exact silu/gelu form).
    """
    m, k = a_data.shape
    k2, n = b_data.shape
    assert k == k2, (a_data.shape, b_data.shape)
    bm = min(bm, max(128, 1 << (m - 1).bit_length())) if m < bm else bm
    bn = min(bn, max(128, 1 << (n - 1).bit_length())) if n < bn else bn
    bk = min(bk, max(128, 1 << (k - 1).bit_length())) if k < bk else bk

    a_scale = jnp.broadcast_to(jnp.asarray(a_scale, jnp.float32), (m, 1))
    b_scale = jnp.broadcast_to(jnp.asarray(b_scale, jnp.float32), (1, n))

    a_data, pm = _pad_to(a_data, 0, bm)
    a_data, _ = _pad_to(a_data, 1, bk)
    b_data, _ = _pad_to(b_data, 0, bk)
    b_data, pn = _pad_to(b_data, 1, bn)
    a_scale, _ = _pad_to(a_scale, 0, bm)
    b_scale, _ = _pad_to(b_scale, 1, bn)

    mp, kp = a_data.shape
    np_ = b_data.shape[1]
    grid = (mp // bm, np_ // bn, kp // bk)

    operands = [a_data, b_data, a_scale, b_scale]
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
        pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
    ]
    if bias is not None:
        brow = jnp.broadcast_to(
            jnp.asarray(bias, jnp.float32).reshape(1, -1), (1, n))
        brow, _ = _pad_to(brow, 1, bn)
        operands.append(brow)
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
    if act_spec is not None:
        table = table_tile(act_spec)
        operands.append(table)
        # the table is replicated into VMEM for every block
        in_specs.append(pl.BlockSpec(table.shape, lambda i, j, kk: (0, 0)))

    out = pl.pallas_call(
        functools.partial(_kernel, k_steps=grid[2],
                          has_bias=bias is not None, act_spec=act_spec,
                          act_gated=act_gated),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)

    return out[:m, :n]
