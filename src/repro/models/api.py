"""Family registry: uniform interface over the five model families.

Every family module exposes::

    init(rng, cfg, *, dtype)                        -> params
    loss(params, batch, cfg, ctx)                   -> (scalar, metrics)
    init_cache(cfg, batch, max_len, dtype)          -> cache pytree
    prefill(params, <tokens|batch>, cache, cfg, ctx)-> (last_logits, cache)
    decode_step(params, tokens, cache, pos, cfg, ctx)-> (logits, cache)

``batch`` layouts (see repro.data): lm/ssm/hybrid use {"tokens",
"labels"}; encdec adds "enc_input"; vlm adds "img_embed".
"""

from __future__ import annotations

from types import ModuleType

from . import encdec, hybrid, lm, ssm_lm, vlm
from .config import ModelConfig

__all__ = ["get_family", "FAMILIES", "init_paged_cache_fn",
           "set_block_table", "copy_pages_fn", "spec_state_fn",
           "spec_restore_fn"]

FAMILIES = {
    "lm": lm,
    "encdec": encdec,
    "ssm": ssm_lm,
    "hybrid": hybrid,
    "vlm": vlm,
}


def get_family(cfg: ModelConfig) -> ModuleType:
    try:
        return FAMILIES[cfg.family]
    except KeyError:
        raise KeyError(f"unknown family {cfg.family!r} "
                       f"(have {sorted(FAMILIES)})") from None


def loss_fn(params, batch, cfg: ModelConfig, ctx):
    """Family-dispatched training loss."""
    fam = get_family(cfg)
    return fam.loss(params, batch, cfg, ctx)


def prefill_fn(params, batch, cache, cfg: ModelConfig, ctx, *,
               pos=None, full_logits: bool = False):
    """Family-dispatched prefill.

    ``pos``: optional (B,) start positions — the chunked-prefill regime
    (each call ingests one prompt chunk; the KV cache continues from
    ``pos`` instead of 0).  ``full_logits=True`` returns logits for every
    chunk position instead of only the last one, so each sequence's true
    last-token logits can be read when prompts end mid-chunk: the
    drafter's prefill and ``chip_smoke``'s logit comparison take them
    (``train.step.build_prefill_step``); the serving engine's prefill
    program uses :func:`prefill_first_tokens_fn` instead.
    """
    fam = get_family(cfg)
    if cfg.family in ("encdec", "vlm"):
        return fam.prefill(params, batch, cache, cfg, ctx, pos=pos,
                           full_logits=full_logits)
    return fam.prefill(params, batch["tokens"], cache, cfg, ctx, pos=pos,
                       full_logits=full_logits)


def prefill_first_tokens_fn(params, batch, cache, cfg: ModelConfig, ctx, *,
                            pos, last):
    """Family-dispatched chunked prefill that returns each slot's greedy
    first token, a (B,) int32, from its row ``last`` of the chunk, with
    the new cache.  Families with chunked prefill
    (:func:`supports_chunked_prefill`) apply the LM head to those B rows
    only."""
    return get_family(cfg).prefill_first_tokens(
        params, batch["tokens"], cache, cfg, ctx, pos=pos, last=last)


def decode_fn(params, tokens, cache, pos, cfg: ModelConfig, ctx):
    """Family-dispatched single decode step.

    Pure in (cache, pos) with a shape/dtype-stable cache pytree, so it
    can be threaded as a ``lax.scan`` carry — the contract
    ``build_decode_loop`` relies on for the fused multi-token decode.
    """
    return get_family(cfg).decode_step(params, tokens, cache, pos, cfg, ctx)


def supports_chunked_prefill(cfg: ModelConfig) -> bool:
    """Families whose cache continues across prefill calls (attention
    KV); recurrent-state families rebuild from one call's tokens."""
    return cfg.family == "lm"


def init_paged_cache_fn(cfg: ModelConfig, batch: int, num_pages: int,
                        page_size: int, table_width: int, dtype):
    """Family-dispatched paged serving cache (see each family's
    ``init_paged_cache``): KV leaves become shared page pools +
    layer-tiled block tables; recurrent state stays dense."""
    fam = get_family(cfg)
    if not hasattr(fam, "init_paged_cache"):
        raise NotImplementedError(
            f"family {cfg.family!r} has no paged serving cache; serve it "
            f"with a dense engine (paged=False)")
    return fam.init_paged_cache(cfg, batch, num_pages, page_size,
                                table_width, dtype)


def _is_paged(cache) -> bool:
    """A serving cache is paged iff any subtree carries a block table."""
    import jax
    return any(
        getattr(p[-1], "key", None) == "block_table"
        for p, _ in jax.tree_util.tree_flatten_with_path(cache)[0])


def set_block_table(cache, bt):
    """Write the engine's (B, NP) block table into every paged subtree.

    Page *assignment* is a host-side decision (the free-list allocator);
    this is the one channel by which it reaches the device: each
    ``block_table`` leaf (layer- or group-tiled to (L, B, NP)) is
    replaced by a broadcast of the new table.  Pages themselves are
    never touched — retiring a slot is just this table edit plus a
    host-side free-list append, O(pages) instead of O(max_len) zeroing.
    """
    import jax
    import jax.numpy as jnp
    bt = jnp.asarray(bt, jnp.int32)

    def repl(path, leaf):
        if getattr(path[-1], "key", None) == "block_table":
            return jnp.broadcast_to(bt, leaf.shape)
        return leaf

    return jax.tree_util.tree_map_with_path(repl, cache)


def copy_pages_fn(cache, src, dst):
    """Copy physical pages ``src`` -> ``dst`` in every page-pool leaf.

    The copy-on-write primitive for prefix caching: a slot about to
    write into a page other consumers still reference gets its own
    physical copy first.  Every page-pool leaf carries the page axis at
    position 1 — (layers_or_groups, num_pages+1, ...) — for KV and int8
    scale leaves alike, so one gather/scatter covers all of them; block
    tables and recurrent state are untouched (re-targeting the table is
    the caller's host-side edit).  ``src``/``dst`` may be scalars or
    equal-length id vectors.
    """
    import jax
    import jax.numpy as jnp
    src = jnp.atleast_1d(jnp.asarray(src, jnp.int32))
    dst = jnp.atleast_1d(jnp.asarray(dst, jnp.int32))

    def cp(path, leaf):
        if any(getattr(k, "key", None) == "pages" for k in path):
            return leaf.at[:, dst].set(leaf[:, src])
        return leaf

    return jax.tree_util.tree_map_with_path(cp, cache)


def invalidate_fn(cache, slot, cfg: ModelConfig):
    """Zero one slot's serving state (KV rows / recurrent state) so a
    recycled slot can never observe its previous occupant.

    The shared implementation zeroes batch-axis 1 — the (layers, B, ...)
    layout every uniform cache uses (lm KV stacks, ssm state stacks).
    A family whose cache mixes batch axes overrides via its own
    ``invalidate_slot`` hook (hybrid: grouped ssm states are
    (G, k, B, ...)).  A fully paged cache (lm) is returned unchanged:
    its KV pages carry no batch axis, and the retired slot's pages are
    unreachable once the engine resets its block table row.
    """
    fam = get_family(cfg)
    if hasattr(fam, "invalidate_slot"):
        return fam.invalidate_slot(cache, slot)
    if _is_paged(cache):
        return cache
    import jax
    return jax.tree_util.tree_map(lambda c: c.at[:, slot].set(0), cache)


def spec_state_fn(cache, cfg: ModelConfig):
    """The *recurrent* part of a serving cache, batch axis leading.

    Speculative decoding's multi-token advance runs the target over a
    drafted block and then rewinds to the accepted prefix.  KV rows
    rewind for free — a scalar ``pos`` edit makes the rejected rows
    unreachable (write-before-attend: the next block overwrites them
    before any query can attend them).  Recurrent state cannot rewind:
    it already *consumed* the rejected tokens.  This hook returns the
    subtree that must be checkpointed per block position (None for
    pure-KV families), with every leaf transposed batch-first so the
    per-slot checkpoint gather after verification is one uniform
    ``t[n_advance - 1, arange(B)]`` regardless of each family's native
    batch axis.  :func:`spec_restore_fn` is its inverse.
    """
    fam = get_family(cfg)
    if hasattr(fam, "spec_state"):
        return fam.spec_state(cache)
    return None                       # lm: KV-only, pos rewind suffices


def spec_restore_fn(cache, state, cfg: ModelConfig):
    """Write a batch-leading recurrent checkpoint back into ``cache``.

    ``state`` is a (possibly per-slot-gathered) pytree in the layout
    :func:`spec_state_fn` produced; families that checkpoint nothing
    return the cache unchanged.
    """
    fam = get_family(cfg)
    if hasattr(fam, "spec_restore"):
        return fam.spec_restore(cache, state)
    return cache


def merge_slot_fn(new_cache, old_cache, slot, cfg: ModelConfig):
    """``old_cache`` with only ``slot``'s lane taken from ``new_cache``.

    The per-slot prefill isolation primitive: the looped prefill runs
    full-batch decode calls, which advance EVERY lane's state on
    recurrent families (even for pad-token inputs) — restoring the
    other lanes afterwards keeps a slot's prefill exactly equivalent to
    a solo prefill and leaves mid-generation neighbours untouched.
    Batch-axis dispatch as in :func:`invalidate_fn`.
    """
    fam = get_family(cfg)
    if hasattr(fam, "merge_slot"):
        return fam.merge_slot(new_cache, old_cache, slot)
    import jax
    return jax.tree_util.tree_map(
        lambda n, o: o.at[:, slot].set(n[:, slot]), new_cache, old_cache)
