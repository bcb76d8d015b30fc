"""Execution context threading the paper's knobs through the model stack.

:class:`QuantContext` is how the de-specialized library reaches every
layer: which numeric mode the matmuls run in, whether activations go
through constant tables, which backend lowers the hot ops, and the
``reuse_factor``.  It is a frozen dataclass (hashable) so jitted step
functions can close over it as static configuration.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp

from ..core.precision import PrecisionPolicy
from ..core.qtypes import FixedPointType

__all__ = ["QuantContext", "DEFAULT_CTX", "kernel_path", "lowerings"]

_MODES = ("none", "fake", "int8")


@dataclasses.dataclass(frozen=True)
class QuantContext:
    """Numeric execution configuration for one forward/backward pass.

    mode:
      * ``none`` — matmuls in ``compute_dtype`` (paper-faithful float path).
      * ``fake`` — straight-through fake quantization of weights (+
        activations if the policy says so): QAT / PTQ-accuracy simulation.
      * ``int8`` — dynamic-range integer execution on the MXU path via the
        ``qmatmul`` kernel (weights pre-quantized or quantized on the fly).
    use_lut:
      route non-trivial activations (gelu/silu/softplus/softmax-exp)
      through trace-time constant tables instead of transcendentals.
    reuse_factor:
      the paper's parallelism/resource knob.  1 = fully parallel.  Higher
      values serialize: layer-scan stays rolled (unroll = max(8 //
      reuse_factor, 1)) and kernel block K is divided accordingly.
    backend:
      kernel backend override (None = registry default; "ref" | "pallas").
      The model takes its Pallas kernel paths only where
      :func:`kernel_path` holds; :func:`lowerings` says what runs.
    """

    mode: str = "none"
    policy: PrecisionPolicy = PrecisionPolicy()
    act_qtype: Optional[FixedPointType] = None
    use_lut: bool = False
    table_n: int = 1024
    table_indexing: str = "interp"
    reuse_factor: int = 1
    backend: Optional[str] = None
    compute_dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    softmax_exact_divide: bool = True
    respect_user_type: bool = False   # de-specialized softmax-table fix
    #: 8 → int8 KV cache with per-(token, head) scales (paper's
    #: quantization aimed at the dominant decode memory term); None = the
    #: cache dtype passed to init_cache (bf16 default).
    kv_cache_bits: Optional[int] = None
    #: split-KV paged attention — the kernel-side reuse-factor pair.
    #: ``kv_split`` cuts each slot's block table into that many parallel
    #: flash-decoding partitions (merged by a log-sum-exp combine);
    #: ``pages_per_step`` is the multi-page DMA tile per grid step.
    #: None = resolve from the cached cost model
    #: (:func:`repro.kernels.flash_attention.choose_kv_split`); 1/1 is
    #: byte-for-byte the pre-split kernel.
    kv_split: Optional[int] = None
    pages_per_step: Optional[int] = None
    #: route the paged f32 decode path through the Pallas kernel even
    #: off-TPU (interpret mode) — the CPU conformance hook that lets the
    #: engine suites drive the real block-table kernel end to end; never
    #: set in production serving (interpret mode is orders of magnitude
    #: slower than the gather/einsum CPU path).
    force_paged_kernel: bool = False

    def __post_init__(self):
        if self.mode not in _MODES:
            raise ValueError(f"mode must be one of {_MODES}")
        if self.reuse_factor < 1:
            raise ValueError("reuse_factor >= 1")
        for knob in ("kv_split", "pages_per_step"):
            v = getattr(self, knob)
            if v is not None and v < 1:
                raise ValueError(f"{knob} must be >= 1 (or None = auto)")

    @property
    def scan_unroll(self) -> int:
        return max(8 // self.reuse_factor, 1)


DEFAULT_CTX = QuantContext()


def kernel_path(ctx: QuantContext) -> bool:
    """Whether the attention modules call their Pallas kernels under
    ``ctx`` on this host (``pallas`` backend on a TPU); elsewhere they
    take the einsum path, which :func:`lowerings` reports."""
    from ..kernels.ops import on_tpu
    return ctx.backend == "pallas" and on_tpu()


def lowerings(ctx: QuantContext, *, pages=None, spec: bool = False) -> dict:
    """op name -> the lowering a model run under ``ctx`` resolves it to.

    Only the ops such a run calls are listed: ``attention`` (prompt
    forward without a cache), ``paged_attention`` (given ``pages``, one
    layer's page pools or their keys), ``qmatmul`` (``mode="int8"``),
    ``lut_activation`` (``use_lut``), ``sample_tokens`` and, with
    ``spec``, ``verify_tokens``.  Values come from
    :func:`repro.kernels.ops.lowering`, except where the model bypasses
    the op: ``einsum`` (attention off the kernel path) and
    ``einsum-gather`` (paged attention that gathers its pages, as
    :func:`repro.nn.attention.paged_kernel_reads` decides).
    """
    from ..kernels.ops import lowering
    from .attention import paged_kernel_reads
    out = {"attention": lowering("attention", ctx.backend)
           if kernel_path(ctx) else "einsum"}
    if pages is not None:
        out["paged_attention"] = (lowering("paged_attention", "pallas")
                                  if paged_kernel_reads(ctx, pages)
                                  else "einsum-gather")
    if ctx.mode == "int8":
        out["qmatmul"] = lowering("qmatmul", ctx.backend)
    if ctx.use_lut:
        out["lut_activation"] = lowering("lut_activation", ctx.backend)
    out["sample_tokens"] = lowering("sample_tokens", ctx.backend)
    if spec:
        out["verify_tokens"] = lowering("verify_tokens", ctx.backend)
    return out
