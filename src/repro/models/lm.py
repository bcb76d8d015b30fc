"""Decoder-only language models: dense (yi/gemma/glm4/command-r) and MoE
(deepseek-v2 with MLA + shared experts, olmoe).

The layer stack is organised as (optional) leading dense layers followed by
the homogeneous body — each run of identical blocks is one ``lax.scan``
over stacked params, so the HLO is depth-independent.  Decode maintains a
per-layer KV cache scanned alongside the params (MLA uses the latent cache;
GQA the standard (B, Hkv, S, Dh) pair).

Paged decode rides the split-KV kernel: every layer's ``gqa_apply`` call
resolves ``ctx.kv_split``/``ctx.pages_per_step`` against its block table,
so one engine-level knob tunes the whole stack (and speculative
verification, which is just an S = k+1 call of the same path).  MLA's
absorbed decode scores against the gathered latent instead — the latent
has no per-head pages to split (the paged MLA pool is (P, ps, lora)).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..nn.attention import (gqa_cache_spec, gqa_paged_cache_spec,
                            mla_cache_spec, mla_paged_cache_spec)
from ..nn.blocks import (dense_block_apply, dense_block_init, moe_block_apply,
                         moe_block_init, norm_apply, norm_init, scan_apply,
                         stack_init)
from ..nn.context import DEFAULT_CTX, QuantContext
from ..nn.embedding import embed, embedding_init, unembed
from ..nn.linear import linear, linear_init
from .common import cross_entropy
from .config import ModelConfig

__all__ = ["init", "forward", "loss", "init_cache", "init_paged_cache",
           "prefill", "prefill_first_tokens", "decode_step"]


def _split_layers(cfg: ModelConfig) -> Tuple[int, int]:
    """(n_dense, n_moe) leading-dense split."""
    if cfg.moe is None:
        return cfg.n_layers, 0
    k = cfg.moe.first_k_dense
    return k, cfg.n_layers - k


def init(rng, cfg: ModelConfig, *, dtype=jnp.float32):
    ks = jax.random.split(rng, 4)
    n_dense, n_moe = _split_layers(cfg)
    params = {"embed": embedding_init(ks[0], cfg.vocab, cfg.d_model,
                                      dtype=dtype),
              "final_norm": norm_init(cfg)}
    if not cfg.tie_embeddings:
        params["head"] = linear_init(ks[3], cfg.d_model, cfg.vocab,
                                     dtype=dtype)
    if n_dense:
        params["dense"] = stack_init(
            ks[1], n_dense, lambda k: dense_block_init(k, cfg, dtype=dtype))
    if n_moe:
        params["moe"] = stack_init(
            ks[2], n_moe, lambda k: moe_block_init(k, cfg, dtype=dtype))
    return params


def _dense_body(cfg, ctx, cache_pos):
    def body(p_l, x, cache_l):
        x2, new_c = dense_block_apply(p_l, x, cfg, ctx, cache=cache_l,
                                      cache_pos=cache_pos)
        return x2, new_c, jnp.zeros((), jnp.float32)
    return body


def _moe_body(cfg, ctx, cache_pos):
    def body(p_l, x, cache_l):
        x2, new_c, aux = moe_block_apply(p_l, x, cfg, ctx, cache=cache_l,
                                         cache_pos=cache_pos)
        return x2, new_c, aux
    return body


def _hidden(params, tokens: jnp.ndarray, cfg: ModelConfig, ctx: QuantContext,
            cache, cache_pos):
    """tokens (B, S) → (hidden states after the last layer (B, S, d),
    new_cache, aux_loss): everything of :func:`forward` but the final
    norm and the LM head."""
    x = embed(params["embed"], tokens, ctx, scale_by_dim=cfg.embed_scale)
    n_dense, n_moe = _split_layers(cfg)
    remat = cfg.remat if cache is None else "none"
    unroll = ctx.scan_unroll
    aux = jnp.zeros((), jnp.float32)
    new_cache = {}

    if n_dense:
        c = cache.get("dense") if cache else None
        x, nc, a = scan_apply(params["dense"], x,
                              _dense_body(cfg, ctx, cache_pos), remat=remat,
                              unroll=unroll, per_layer=c)
        new_cache["dense"], aux = nc, aux + a
    if n_moe:
        c = cache.get("moe") if cache else None
        x, nc, a = scan_apply(params["moe"], x,
                              _moe_body(cfg, ctx, cache_pos), remat=remat,
                              unroll=unroll, per_layer=c)
        new_cache["moe"], aux = nc, aux + a
    return x, (new_cache if cache is not None else None), aux


def _logits(params, x: jnp.ndarray, cfg: ModelConfig, ctx: QuantContext):
    """Final norm and LM head: hidden states (..., d) → logits (..., V)."""
    x = norm_apply(cfg, params["final_norm"], x)
    if cfg.tie_embeddings:
        logits = unembed(params["embed"], x, ctx)
    else:
        logits = linear(params["head"], x, ctx, path="head")
    from ..dist.constrain import constrain
    return constrain(logits, "dp", None, "tp")


def forward(params, tokens: jnp.ndarray, cfg: ModelConfig,
            ctx: QuantContext = DEFAULT_CTX, *, cache=None,
            cache_pos: Optional[jnp.ndarray] = None):
    """tokens (B, S) → (logits (B, S, V), new_cache, aux_loss)."""
    x, new_cache, aux = _hidden(params, tokens, cfg, ctx, cache, cache_pos)
    return _logits(params, x, cfg, ctx), new_cache, aux


def loss(params, batch, cfg: ModelConfig, ctx: QuantContext = DEFAULT_CTX):
    logits, _, aux = forward(params, batch["tokens"], cfg, ctx)
    ce, metrics = cross_entropy(logits, batch["labels"])
    total = ce
    if cfg.moe is not None:
        total = total + cfg.moe.aux_loss_weight * aux
        metrics["moe_aux"] = aux
    metrics["loss"] = total
    return total, metrics


# -- serving ------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16):
    n_dense, n_moe = _split_layers(cfg)

    def one(_):
        if cfg.attn_kind == "mla":
            return mla_cache_spec(cfg.mla, batch, max_len, dtype)
        return gqa_cache_spec(cfg.attn_dims(), batch, max_len, dtype)

    cache = {}
    if n_dense:
        cache["dense"] = jax.vmap(one)(jnp.arange(n_dense))
    if n_moe:
        cache["moe"] = jax.vmap(one)(jnp.arange(n_moe))
    return cache


def init_paged_cache(cfg: ModelConfig, batch: int, num_pages: int,
                     page_size: int, table_width: int, dtype=jnp.bfloat16):
    """Paged serving cache: per-layer KV pages + per-layer block tables.

    The page *pool* is per layer ((L, P+1, ...) leaves — every layer
    needs its own KV rows) but the page *assignment* is shared: one
    host-side allocation covers all layers, and the engine broadcasts
    the (B, NP) block table across the layer axis
    (:func:`repro.models.api.set_block_table`), so logical token ``t``
    of a slot lives at the same physical page index in every layer.
    """
    n_dense, n_moe = _split_layers(cfg)

    def one(_):
        if cfg.attn_kind == "mla":
            return mla_paged_cache_spec(cfg.mla, batch, num_pages,
                                        page_size, table_width, dtype)
        return gqa_paged_cache_spec(cfg.attn_dims(), batch, num_pages,
                                    page_size, table_width, dtype)

    cache = {}
    if n_dense:
        cache["dense"] = jax.vmap(one)(jnp.arange(n_dense))
    if n_moe:
        cache["moe"] = jax.vmap(one)(jnp.arange(n_moe))
    return cache


# slot invalidation / merge: dense cache leaves are (layers, B, ...), so
# the generic axis-1 implementations in models.api apply; the paged
# cache has NO batch-indexed KV state to zero (a retired slot's pages
# become unreachable the moment the engine resets its block table), so
# the generic paged no-op in models.api applies too (no hook here).
def prefill(params, tokens: jnp.ndarray, cache, cfg: ModelConfig,
            ctx: QuantContext = DEFAULT_CTX, *, pos=None,
            full_logits: bool = False):
    """Run prompt tokens through the model, filling the cache.

    ``pos`` (B,): per-slot start positions for chunked prefill (None =
    whole prompt from 0).  ``full_logits=True`` returns logits at every
    position of this chunk instead of only the last.
    """
    b = tokens.shape[0]
    start = jnp.zeros((b,), jnp.int32) if pos is None else pos
    logits, new_cache, _ = forward(params, tokens, cfg, ctx, cache=cache,
                                   cache_pos=start)
    return (logits if full_logits else logits[:, -1:]), new_cache


def prefill_first_tokens(params, tokens: jnp.ndarray, cache,
                         cfg: ModelConfig, ctx: QuantContext = DEFAULT_CTX,
                         *, pos: jnp.ndarray, last: jnp.ndarray):
    """Chunked prefill that returns each slot's greedy first token.

    ``last`` (B,) int32: each slot's row of its last prompt token in this
    chunk.  That row of the hidden states is gathered before the final
    norm and the LM head, so the head runs on B rows, not on B x chunk,
    and no (B, chunk, V) array is made.  Returns ((B,) int32 argmax, ties
    to the lowest index, new_cache).
    """
    x, new_cache, _ = _hidden(params, tokens, cfg, ctx, cache, pos)
    rows = jnp.take_along_axis(x, last[:, None, None], axis=1)
    logits = _logits(params, rows, cfg, ctx)
    return jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32), new_cache


def decode_step(params, tokens: jnp.ndarray, cache, pos: jnp.ndarray,
                cfg: ModelConfig, ctx: QuantContext = DEFAULT_CTX):
    """One decode step.  tokens (B, 1); pos (B,) current cache length."""
    logits, new_cache, _ = forward(params, tokens, cfg, ctx, cache=cache,
                                   cache_pos=pos)
    return logits, new_cache
