"""Quantizers: PTQ, fake-quant QAT (straight-through), dynamic-range int8.

Three quantization modes, mirroring the paper's usage tiers:

* **static fixed point** (``ac_fixed`` semantics): binary-point scale fixed
  by the type — the paper-faithful mode.  :func:`fake_quant`.
* **dynamic-range fixed point**: scale calibrated from data (per-tensor or
  per-channel max-abs), integer payload carried in a :class:`QTensor` and
  executed on the MXU int8 path.  :func:`quantize_dynamic` /
  :func:`ptq_params`.
* **minifloat** (custom floating point): :func:`fake_quant` with a
  :class:`~repro.core.qtypes.MiniFloatType`.

All fake-quant ops are differentiable via the straight-through estimator
(identity gradient inside the representable range, zero outside — the
standard clipping STE), so the same machinery serves QAT.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from .qtypes import FixedPointType, MiniFloatType, QTensor

__all__ = [
    "fake_quant",
    "quantize_dynamic",
    "calibrate_scale",
    "ptq_params",
    "ptq_leaf",
    "dequantize_params",
]

QType = Union[FixedPointType, MiniFloatType]


# --------------------------------------------------------------------------
# Straight-through fake quantization (QAT + paper-faithful static PTQ).
# --------------------------------------------------------------------------
@jax.custom_vjp
def _ste_round_trip(x: jnp.ndarray, lo: float, hi: float, q: jnp.ndarray):
    # q is the already-quantized value; lo/hi bound the representable range.
    del x, lo, hi
    return q


def _ste_fwd(x, lo, hi, q):
    return q, (x, lo, hi)


def _ste_bwd(res, g):
    x, lo, hi = res
    mask = ((x >= lo) & (x <= hi)).astype(g.dtype)
    return (g * mask, None, None, None)


_ste_round_trip.defvjp(_ste_fwd, _ste_bwd)


def fake_quant(x: jnp.ndarray, qtype: QType) -> jnp.ndarray:
    """Round-trip ``x`` through ``qtype`` with straight-through gradients."""
    if isinstance(qtype, FixedPointType):
        lo, hi = qtype.min_value, qtype.max_value
    else:
        hi = qtype.max_value
        lo = -hi
    q = qtype.quantize(x)
    return _ste_round_trip(x, lo, hi, q.astype(x.dtype))


# --------------------------------------------------------------------------
# Dynamic-range integer quantization (the MXU execution path).
# --------------------------------------------------------------------------
def calibrate_scale(x: jnp.ndarray, qtype: FixedPointType,
                    channel_axes: Sequence[int] = ()) -> jnp.ndarray:
    """Max-abs scale so the observed range maps onto the integer range.

    ``channel_axes`` are the axes *kept* (per-channel); all others reduce.
    Returned scale broadcasts against ``x`` (kept axes retain their size).
    """
    reduce_axes = tuple(a for a in range(x.ndim) if a not in
                        tuple(a % x.ndim for a in channel_axes))
    amax = jnp.max(jnp.abs(x), axis=reduce_axes, keepdims=True)
    amax = jnp.maximum(amax, 1e-12)
    return (amax / qtype.int_max).astype(jnp.float32)


def quantize_dynamic(x: jnp.ndarray, qtype: FixedPointType,
                     channel_axes: Sequence[int] = (),
                     scale: Optional[jnp.ndarray] = None) -> QTensor:
    """Quantize with a calibrated (or provided) scale into a QTensor."""
    if scale is None:
        scale = calibrate_scale(x, qtype, channel_axes)
    data = jnp.clip(jnp.round(x / scale), qtype.int_min, qtype.int_max)
    return QTensor(data.astype(qtype.dtype), scale, qtype)


# --------------------------------------------------------------------------
# Whole-pytree PTQ (the hls4ml "convert a trained model" flow).
# --------------------------------------------------------------------------
#: leaf keys that feed matmul consumers (nn.linear / nn.moe) and can
#: therefore carry a QTensor.  Everything else — embedding tables
#: (gathered, not matmul'd), routers (precision-sensitive, §Arch),
#: depthwise conv filters, norms, biases — stays a dense float array.
_MATMUL_WEIGHT_KEYS = frozenset({"w", "w_gate", "w_up", "w_down"})


def _is_weight(path: Tuple, leaf) -> bool:
    if not hasattr(leaf, "ndim") or leaf.ndim < 2:
        return False  # biases / scales / norms stay high precision
    joined = "/".join(str(p) for p in path).lower()
    if "embed" in joined or "router" in joined:
        return False
    name = str(path[-1]) if path else ""
    return name in _MATMUL_WEIGHT_KEYS


def _weight_channel_axes(ndim: int) -> Tuple[int, ...]:
    """Keep every axis except the contraction axis (-2).

    A weight is (..., d_in, d_out): per-out-channel scales with all
    leading (layer-stack / expert) axes kept, so stacked QTensor params
    slice cleanly under ``lax.scan`` (data and scale share the leading
    L axis).
    """
    return tuple(a for a in range(ndim) if a != ndim - 2)


def ptq_params(params, policy, *,
               channel_axes: Optional[Sequence[int]] = None,
               predicate=_is_weight):
    """Post-training-quantize a parameter pytree.

    ``policy`` is a :class:`repro.core.precision.PrecisionPolicy` (or a
    single qtype applied uniformly).  Weight matrices become
    :class:`QTensor`; everything else passes through.  Mirrors hls4ml's
    model conversion: the trained float model in, a quantized deployable
    artifact out.  The result feeds :func:`repro.nn.linear.linear`
    directly — serving quantizes weights ONCE here, never per forward.

    ``channel_axes`` (axes *kept* by the scale) defaults to "all but the
    contraction axis": per-out-channel scales that also keep any leading
    layer-stack / expert axes, so stacked params remain scannable.
    """
    return jax.tree_util.tree_map_with_path(
        lambda p, l: ptq_leaf(p, l, policy, channel_axes=channel_axes,
                              predicate=predicate), params)


def ptq_leaf(path, leaf, policy, *,
             channel_axes: Optional[Sequence[int]] = None,
             predicate=_is_weight):
    """:func:`ptq_params` for one leaf at tree ``path`` (key entries or
    strings): its QTensor, or the leaf itself where it is not a weight
    or the policy gives it no type."""
    from .precision import PrecisionPolicy  # local import to avoid a cycle

    path = tuple(_path_key(k) for k in path)
    if not predicate(path, leaf):
        return leaf
    if isinstance(policy, PrecisionPolicy):
        qt = policy.resolve("/".join(str(p) for p in path)).weights
    else:
        qt = policy
    if qt is None:
        return leaf
    if isinstance(qt, MiniFloatType):
        return qt.quantize(leaf)
    axes = (channel_axes if channel_axes is not None
            else _weight_channel_axes(leaf.ndim))
    return quantize_dynamic(leaf, qt, channel_axes=axes)


def dequantize_params(qparams, dtype=jnp.float32):
    """Inverse of :func:`ptq_params` (for accuracy-loss measurement)."""
    return jax.tree_util.tree_map(
        lambda l: l.dequantize(dtype) if isinstance(l, QTensor) else l,
        qparams, is_leaf=lambda l: isinstance(l, QTensor))


def _path_key(k) -> str:
    for attr in ("key", "name", "idx"):
        if hasattr(k, attr):
            return str(getattr(k, attr))
    return str(k)
