"""The yardstick's arithmetic: chip peaks, model operations and bytes.

Every count here is worked out from a configuration file's sizes and
the lengths the harness sent, never from the program's compiled code,
so a change to the program cannot move it.

Peaks are keyed by ``device_kind`` as JAX names the chip.  A device
that is not in the table is an error, not a default.
"""

from __future__ import annotations

import dataclasses

__all__ = ["Peaks", "PEAKS", "peaks", "matmul_params", "total_params",
           "attention_flops", "paged_decode_bytes", "kv_bytes_per_token"]


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float       # FLOP/s per chip
    hbm_bytes_s: float      # bytes/s per chip
    source: str


#: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16, 393 TOP/s
#: in int8, 16 GB of HBM at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, hbm_bytes_s=819e9,
                         source="cloud.google.com/tpu/docs/v5e"),
}

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "int8": 1}


def peaks(device_kind: str) -> Peaks:
    """The peaks row of ``device_kind``; KeyError for an unknown chip."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)})") from None


def _layer_matmul_params(m: dict) -> int:
    d, hd = m["d_model"], m["head_dim"]
    q, kv = m["n_heads"] * hd, m["n_kv_heads"] * hd
    attn = d * q + 2 * d * kv + q * d
    mlp = d * m["d_ff"] * (3 if m.get("mlp_gated", True) else 2)
    return attn + mlp


def matmul_params(m: dict) -> int:
    """Parameters that take part in a matrix product for every token:
    each layer's projections and the LM head (the input embedding is a
    gather and does not count)."""
    return m["n_layers"] * _layer_matmul_params(m) + m["vocab"] * m["d_model"]


def total_params(m: dict) -> int:
    """Every parameter held: layers (biases and norm scales included),
    input embedding, final norm and an untied LM head."""
    d, hd = m["d_model"], m["head_dim"]
    per = _layer_matmul_params(m) + 2 * d
    if m.get("qkv_bias"):
        per += (m["n_heads"] + 2 * m["n_kv_heads"]) * hd
    embed = m["vocab"] * d * (1 if m.get("tie_embeddings") else 2)
    return m["n_layers"] * per + embed + d


def attention_flops(m: dict, context: int) -> int:
    """Causal attention FLOPs of one token that attends to ``context``
    keys (itself included): QK^T and PV, 2 FLOPs per multiply-add, in
    every layer and query head."""
    return 4 * m["n_layers"] * m["n_heads"] * m["head_dim"] * int(context)


def kv_bytes_per_token(m: dict, pool_dtype: str) -> int:
    """Bytes of K and V that one cached token holds over all layers."""
    return (2 * m["n_layers"] * m["n_kv_heads"] * m["head_dim"]
            * DTYPE_BYTES[pool_dtype])


def paged_decode_bytes(m: dict, pool_dtype: str, context: int) -> int:
    """HBM bytes the paged decode kernel must read for one live lane at
    one step: the K and V of its ``context`` cached tokens in every
    layer, at the pool's dtype.  Linear in ``context``, so a sum of
    contexts gives the bytes of many lane-steps."""
    return kv_bytes_per_token(m, pool_dtype) * int(context)
