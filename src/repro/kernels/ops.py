"""Public kernel API with backend dispatch (the de-specialized interface).

Every op is registered under the backends it supports; callers use these
wrappers (or the registry directly) and never import a specific lowering.
Off the TPU the ``pallas`` backend runs its kernels in interpret mode,
which executes the kernel body in Python — the portability story the paper
asks for: one interface, ``ref`` everywhere, specialization where the
hardware exists.  :func:`lowering` names what a call actually runs
(``pallas``, ``pallas-interpret``, ``ref`` or ``xla-fusion``), so an
entry point can say so rather than degrade quietly.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..core.registry import get_impl, register_op, resolve
from ..core.tables import TableSpec
from . import ref as _ref
from .flash_attention import (flash_attention_pallas, paged_attention_pallas,
                              paged_attention_xla)
from .lut_activation import lut_activation_pallas
from .qmatmul import qmatmul_pallas
from .sampling import sample_tokens_fused
from .speculative import verify_tokens_fused

__all__ = ["lut_activation", "qmatmul", "attention", "paged_attention",
           "sample_tokens", "verify_tokens", "on_tpu", "lowering",
           "KERNEL_OPS"]

#: ops whose ``pallas`` lowering is a ``pl.pallas_call``; the other ops'
#: ``pallas`` registrations are XLA fusions (see sample_tokens below).
KERNEL_OPS = ("attention", "paged_attention", "qmatmul", "lut_activation")


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return not on_tpu()


def lowering(name: str, backend: Optional[str] = None) -> str:
    """What ``name`` runs under ``backend`` on this host: ``pallas``
    (compiled kernel), ``pallas-interpret`` (the kernel body in Python,
    off the TPU), ``xla-fusion`` (a non-kernel specialized lowering) or
    the registry backend it fell back to (``ref``, ``xla``)."""
    b = resolve(name, backend)
    if b != "pallas":
        return b
    if name not in KERNEL_OPS:
        return "xla-fusion"
    return "pallas" if on_tpu() else "pallas-interpret"


# -- registrations ---------------------------------------------------------
register_op("lut_activation", "ref")(_ref.lut_activation_ref)


@register_op("lut_activation", "pallas")
def _lut_pallas(x, spec: TableSpec, **kw):
    return lut_activation_pallas(x, spec, interpret=_interpret(), **kw)


register_op("qmatmul", "ref")(_ref.qmatmul_ref)


@register_op("qmatmul", "pallas")
def _qmatmul_pallas(a, b, sa, sb, bias=None, out_dtype=jnp.float32, **kw):
    return qmatmul_pallas(a, b, sa, sb, bias, out_dtype=out_dtype,
                          interpret=_interpret(), **kw)


register_op("sample_tokens", "ref")(_ref.sample_tokens_ref)

# the specialized lowering is an XLA fusion rather than a pallas_call:
# sampling reads (B, V) floats once, so the win is living inside the
# decode jit (token never leaves the device), not a custom kernel.
register_op("sample_tokens", "pallas")(sample_tokens_fused)


register_op("verify_tokens", "ref")(_ref.verify_tokens_ref)

# same stance as sample_tokens: verification touches (B, S, V) floats
# once — the value is running INSIDE the fused decode scan (accepted
# lengths and the rewound position never leave the device), so the
# specialized lowering is an XLA fusion, not a pallas_call.
register_op("verify_tokens", "pallas")(verify_tokens_fused)


register_op("attention", "ref")(_ref.flash_attention_ref)


register_op("paged_attention", "ref")(_ref.paged_attention_ref)

# third lowering: the split-KV *schedule* (scan over page tiles,
# partition axis batched, log-sum-exp combine) through plain XLA — the
# portable way to run/measure the flash-decoding schedule on non-TPU
# hosts, and the serial-chain baseline (split=1, tile=1) the
# long-context bench compares against.
register_op("paged_attention", "xla")(paged_attention_xla)


@register_op("paged_attention", "pallas")
def _paged_attention_pallas(q, k_pages, v_pages, block_tables, qpos, *,
                            softmax_scale=None, **kw):
    return paged_attention_pallas(q, k_pages, v_pages, block_tables, qpos,
                                  softmax_scale=softmax_scale,
                                  interpret=_interpret(), **kw)


#: re-exported tuning helpers (the reuse-factor knob's cost model and
#: the split-merge formula shared with the ref oracle)
from .flash_attention import (auto_pages_per_step, choose_kv_split,  # noqa: E402
                              combine_splits)


@register_op("attention", "pallas")
def _attention_pallas(q, k, v, *, causal=True, softmax_scale=None, **kw):
    return flash_attention_pallas(q, k, v, causal=causal,
                                  softmax_scale=softmax_scale,
                                  interpret=_interpret(), **kw)


# -- public wrappers -------------------------------------------------------
def lut_activation(x: jnp.ndarray, spec: TableSpec, *,
                   backend: Optional[str] = None, **kw) -> jnp.ndarray:
    return get_impl("lut_activation", backend)(x, spec, **kw)


def qmatmul(a_data, b_data, a_scale, b_scale, *, bias=None,
            act_spec: Optional[TableSpec] = None, act_gated: bool = False,
            out_dtype=jnp.float32, backend: Optional[str] = None,
            **kw) -> jnp.ndarray:
    """Quantized matmul with optional fused epilogue (bias + LUT act).

    With ``bias``/``act_spec`` set, linear + bias + activation execute as
    ONE kernel launch (one HBM pass) instead of three — the Pallas
    analogue of hls4ml's dense→activation dataflow fusion.
    """
    kw = dict(kw)
    if bias is not None:
        kw["bias"] = bias
    if act_spec is not None:
        kw.update(act_spec=act_spec, act_gated=act_gated)
    return get_impl("qmatmul", backend)(a_data, b_data, a_scale, b_scale,
                                        out_dtype=out_dtype, **kw)


def attention(q, k, v, *, causal: bool = True, softmax_scale=None,
              backend: Optional[str] = None, **kw) -> jnp.ndarray:
    return get_impl("attention", backend)(q, k, v, causal=causal,
                                          softmax_scale=softmax_scale, **kw)


def paged_attention(q, k_pages, v_pages, block_tables, qpos, *,
                    softmax_scale=None, kv_split: Optional[int] = None,
                    pages_per_step: Optional[int] = None,
                    backend: Optional[str] = None, **kw) -> jnp.ndarray:
    """Attention over a block-table-indexed KV page pool.

    q (B, Hq, S, D) against k/v pages (P, Hkv, page_size, D) addressed
    through ``block_tables`` (B, NP), with causal visibility over
    absolute positions ``qpos[b] + i`` (write-before-attend).  S == 1 is
    the decode step, S > 1 a chunked-prefill step — one op serves both,
    which is what lets the serving engine admit mixed prefill/decode
    batches over one shared pool.

    ``kv_split`` / ``pages_per_step`` — the kernel-level reuse-factor
    knob (see :func:`repro.kernels.flash_attention.choose_kv_split`):
    the Pallas lowering cuts each slot's block table into ``kv_split``
    parallel flash-decoding partitions merged by a log-sum-exp combine,
    fetching ``pages_per_step`` pages per grid step.  ``None`` = pick
    from the cached cost model.  The ``ref`` backend is knob-invariant
    by construction: it only switches to the explicit split recurrence
    when a knob is set > 1 (the oracle the kernel is tested against).
    """
    if kv_split is not None:
        kw["kv_split"] = kv_split
    if pages_per_step is not None:
        kw["pages_per_step"] = pages_per_step
    return get_impl("paged_attention", backend)(
        q, k_pages, v_pages, block_tables, qpos,
        softmax_scale=softmax_scale, **kw)


def sample_tokens(logits, temperature, top_k, key=None, *,
                  backend: Optional[str] = None) -> jnp.ndarray:
    """Per-slot next-token draw: (B, V) logits -> (B,) int32 ids.

    ``temperature`` (B,) f32 (<= 0 means greedy) and ``top_k`` (B,) i32
    (<= 0 means unrestricted) are *per slot*, so one fused decode batch
    can mix greedy and sampled requests.  Deterministic in ``key``
    across jit/scan boundaries — see :mod:`repro.kernels.sampling`.
    """
    return get_impl("sample_tokens", backend)(logits, temperature, top_k,
                                              key)


def verify_tokens(logits, draft, temperature, top_k, key=None, *,
                  backend: Optional[str] = None):
    """Speculative acceptance rule: (B, S, V) target logits over a
    drafted block × (B, S-1) draft ids -> (next_token (B,),
    n_advance (B,) in [1, S]).

    Greedy slots (temperature <= 0) accept the longest draft prefix
    that matches the argmax chain — committed output is byte-identical
    to non-speculative decode.  Sampled slots run point-mass rejection
    sampling, preserving the temperature/top-k output distribution.
    Deterministic in ``key`` across jit/scan boundaries — see
    :mod:`repro.kernels.speculative`.
    """
    return get_impl("verify_tokens", backend)(logits, draft, temperature,
                                              top_k, key)
