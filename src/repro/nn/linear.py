"""Linear layers with pluggable numerics (the heart of the quantized path).

``linear()`` consults the :class:`~repro.nn.context.QuantContext`:

* ``none``  — einsum in ``compute_dtype`` (bf16 MXU path).
* ``fake``  — straight-through fake-quant of weights (and activations if a
  type is set): numerically simulates the paper's ``ac_fixed``/minifloat
  deployment while staying in float storage (QAT & accuracy studies).
* ``int8``  — dynamic-range integer execution: per-row activation scales,
  per-column weight scales, int8×int8→int32 on the MXU via the
  ``qmatmul`` Pallas kernel (HBM traffic halves vs bf16 — the deployment
  path).

**Pre-quantized weights**: ``p["w"]`` may be a
:class:`~repro.core.qtypes.QTensor` produced offline by
:func:`repro.core.quantize.ptq_params`.  Under ``int8`` the payload and
scales feed ``qmatmul`` directly — zero ``calibrate_scale``/``round`` ops
on the weight per forward call (only the activation is quantized
dynamically).  Under other modes the QTensor is dequantized once into the
compute dtype.  This is the hls4ml deployment contract: quantize at model
conversion, not per inference.

**Fused epilogue**: passing ``act=`` (with ``ctx.use_lut``) fuses the
bias add and the LUT activation into the qmatmul kernel's final K step —
linear + bias + activation in ONE kernel launch / HBM pass (the paper's
dense→activation dataflow fusion).  When the fused path does not apply,
``act`` falls back to :func:`repro.nn.activations.act_fn` with identical
numerics.

Per-layer heterogeneity comes from ``ctx.policy.resolve(path)`` — the
hls4ml per-layer config dict, de-specialized.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.precision import LayerPrecision
from ..core.registry import resolve
from ..dist.constrain import kernel_map, splits
from ..core.quantize import calibrate_scale, fake_quant
from ..core.qtypes import FixedPointType, MiniFloatType, QTensor
from ..core.tables import GATED_FORMS, TableSpec
from .context import DEFAULT_CTX, QuantContext

__all__ = ["linear_init", "linear"]

#: activations the fused LUT epilogue supports (relu is cheaper exact;
#: softplus needs the piecewise-exact asymptote outside the table domain).
_FUSABLE_ACTS = ("sigmoid", "tanh", "gelu", "silu")


def linear_init(rng, d_in: int, d_out: int, *, bias: bool = False,
                dtype=jnp.float32, scale: Optional[float] = None):
    std = scale if scale is not None else d_in ** -0.5
    p = {"w": (jax.random.normal(rng, (d_in, d_out), jnp.float32) * std
               ).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def _act_table(act: str, ctx: QuantContext,
               path: str) -> Tuple[TableSpec, bool]:
    """TableSpec + gated flag matching act_fn's LUT selection exactly."""
    from .activations import _LUT_DOMAIN  # table domains live with act_fn
    prec = ctx.policy.resolve(path)
    n = prec.table_n or ctx.table_n
    qt = prec.table_qtype
    lo, hi = _LUT_DOMAIN[act]
    gated = act in GATED_FORMS
    fn = GATED_FORMS[act] if gated else act
    return TableSpec(fn, n, lo, hi, qt, ctx.table_indexing), gated


def _int8_matmul(x2: jnp.ndarray, wq: jnp.ndarray, sw: jnp.ndarray,
                 qt: FixedPointType, ctx: QuantContext, *,
                 bias=None, act_spec=None, act_gated=False) -> jnp.ndarray:
    """(T, K) @ (K, N) through the int8 MXU path (+ fused epilogue).

    The weight arrives already quantized (payload ``wq``, per-column
    scales ``sw``); only the activation is quantized here (per-row
    dynamic scale — it changes every call, the weight does not).  The
    Pallas lowering runs per shard of the mesh: rows over the data
    axes, output columns over the model axis (a Mosaic call cannot be
    partitioned by GSPMD).
    """
    from ..kernels.ops import qmatmul  # local: kernels import nn-free core

    sx = calibrate_scale(x2, qt, channel_axes=(0,))          # (T, 1)
    xq = jnp.clip(jnp.round(x2 / sx), qt.int_min, qt.int_max).astype(qt.dtype)

    def run(xq, wq, sx, sw, *bias):
        return qmatmul(xq, wq, sx, sw, bias=bias[0] if bias else None,
                       act_spec=act_spec, act_gated=act_gated,
                       out_dtype=ctx.compute_dtype, backend=ctx.backend)

    args = (xq, wq, sx, sw) + (() if bias is None else (bias,))
    if resolve("qmatmul", ctx.backend) != "pallas":
        return run(*args)
    rows = "dp" if splits("dp", xq.shape[0]) else None
    cols = "tp" if splits("tp", wq.shape[1]) else None
    specs = (P(rows, None), P(None, cols), P(rows, None), P(None, cols),
             P(cols))
    return kernel_map(run, *args, in_specs=specs[:len(args)],
                      out_specs=P(rows, cols))


def _quantize_weight(w: jnp.ndarray, qt: FixedPointType):
    """Dynamic per-column weight quantization (the non-PTQ fallback)."""
    sw = calibrate_scale(w, qt, channel_axes=(1,))           # (1, N)
    wq = jnp.clip(jnp.round(w / sw), qt.int_min, qt.int_max).astype(qt.dtype)
    return wq, sw


def linear(p, x: jnp.ndarray, ctx: QuantContext = DEFAULT_CTX, *,
           path: str = "", act: Optional[str] = None,
           act_path: Optional[str] = None) -> jnp.ndarray:
    """Apply ``act(x @ w (+ b))`` under the context's numeric mode.

    ``act``: optional activation name fused into the kernel epilogue when
    the int8 LUT path applies, applied via ``act_fn`` otherwise.
    ``act_path``: policy-resolution path for the activation (defaults to
    ``f"{path}/act"``), so fused and unfused paths resolve identically.
    """
    w = p["w"]
    prec: LayerPrecision = ctx.policy.resolve(path)
    prequant = isinstance(w, QTensor)
    mode = ctx.mode
    if not prequant and prec.weights is None and mode != "none":
        mode = "none"

    # which int8 weight feed applies?
    wq = sw = qt = None
    if mode == "int8":
        if prequant and isinstance(w.qtype, FixedPointType) \
                and w.qtype.width <= 8:
            qt = w.qtype                       # PTQ artifact: ready to run
            wq, sw = w.data, w.scale.reshape(1, -1)
        elif not prequant and isinstance(prec.weights, FixedPointType) \
                and prec.weights.width <= 8:
            qt = prec.weights                  # dynamic: quantize per call

    bias = p.get("b")
    act_done = bias_done = False
    if qt is not None:
        t_shape = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
        if wq is None:
            wq, sw = _quantize_weight(w.astype(jnp.float32), qt)
        fuse_act = act in _FUSABLE_ACTS and ctx.use_lut
        spec, gated = (_act_table(act, ctx, act_path or f"{path}/act")
                       if fuse_act else (None, False))
        fb = None if bias is None else bias.astype(jnp.float32)
        y = _int8_matmul(x2, wq, sw, qt, ctx, bias=fb, act_spec=spec,
                         act_gated=gated)
        y = y.reshape(*t_shape, wq.shape[-1])
        bias_done, act_done = True, fuse_act
    else:
        if prequant:
            w = w.dequantize(ctx.compute_dtype)
        if mode == "fake" and prec.weights is not None:
            w = fake_quant(w.astype(jnp.float32), prec.weights)
        if mode == "fake" and prec.activations is not None:
            x = fake_quant(x.astype(jnp.float32), prec.activations)
        y = jnp.einsum("...k,kn->...n", x.astype(ctx.compute_dtype),
                       w.astype(ctx.compute_dtype))
    if bias is not None and not bias_done:
        y = y + bias.astype(y.dtype)
    if act is not None and not act_done:
        from .activations import act_fn
        y = act_fn(act, y, ctx, path=act_path or f"{path}/act")
    return y
