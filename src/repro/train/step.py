"""Train/serve step builders: microbatched gradient accumulation, remat,
optimizer fusion, optional compressed cross-pod gradient reduction.

``build_train_step`` returns a pure function ``(state, batch) -> (state,
metrics)`` ready for ``jax.jit`` with the sharding pytrees from
``repro.dist.sharding``.  Gradient accumulation is a ``lax.scan`` over
microbatch slices — the standard memory lever for the big train shapes
(live activations scale with B/microbatches, while the scan keeps HLO size
constant); XLA overlaps each microbatch's backward collectives with the
next microbatch's compute (latency hiding — see EXPERIMENTS.md §Perf).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from ..core.qtypes import FixedPointType
from ..models.api import loss_fn
from ..models.config import ModelConfig
from ..nn.context import QuantContext
from ..optim import OptConfig, adamw_init, adamw_update

__all__ = ["init_state", "build_train_step", "build_serve_step",
           "build_prefill_step", "build_decode_loop",
           "build_spec_decode_loop", "LOOP_BUILDS"]

#: fused-loop build telemetry: every call of a loop *builder* is one
#: trace-and-compile when the result is jitted, so re-jit bugs (e.g. an
#: adaptive knob thrashing the spec loop cache) show up here long before
#: they show up in walltime.  Tests assert the count stays bounded by
#: the number of distinct (block, k) keys; reset by assigning zeros.
LOOP_BUILDS = {"decode": 0, "spec": 0}


def init_state(rng, cfg: ModelConfig, *, dtype=jnp.float32,
               opt_cfg: OptConfig = OptConfig()):
    from ..models.api import get_family
    params = get_family(cfg).init(rng, cfg, dtype=dtype)
    return {"params": params, "opt": adamw_init(params, opt_cfg),
            "step": jnp.zeros((), jnp.int32)}


def _tree_add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


def _tree_scale(a, s):
    return jax.tree_util.tree_map(lambda t: t * s, a)


def build_train_step(cfg: ModelConfig, ctx: QuantContext, *,
                     lr_fn: Callable, opt_cfg: OptConfig = OptConfig(),
                     microbatches: int = 1,
                     grad_specs=None) -> Callable:
    """(state, batch) -> (state, metrics).

    ``batch`` leaves have leading dim B; with ``microbatches`` > 1 they are
    reshaped to (M, B/M, …) and scanned, accumulating f32 gradients.

    ``grad_specs``: optional PartitionSpec pytree matching the params.
    Under the ``grad_specs`` perf flag (§Perf H1), per-microbatch gradients
    and the accumulator are constrained to the parameter sharding, so the
    cross-data reduction lowers as a reduce-scatter into sharded
    accumulators instead of a full-gradient all-reduce every microbatch.
    """
    grad_of = jax.value_and_grad(lambda p, mb: loss_fn(p, mb, cfg, ctx),
                                 has_aux=True)

    def _pin(grads):
        from ..dist.constrain import current_mesh
        from ..dist.options import flags
        mesh = current_mesh()
        if grad_specs is None or mesh is None or not flags().grad_specs:
            return grads
        from jax.sharding import NamedSharding
        return jax.tree_util.tree_map(
            lambda g, s: jax.lax.with_sharding_constraint(
                g, NamedSharding(mesh, s)), grads, grad_specs)

    def compute_grads(params, batch):
        if microbatches == 1:
            (loss, metrics), grads = grad_of(params, batch)
            return _pin(grads), metrics

        def split(t):
            return t.reshape(microbatches, t.shape[0] // microbatches,
                             *t.shape[1:])

        mbatch = jax.tree_util.tree_map(split, batch)
        g0 = _pin(jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params))
        m0 = {"loss": jnp.zeros((), jnp.float32),
              "accuracy": jnp.zeros((), jnp.float32)}

        def body(carry, mb):
            gacc, macc = carry
            (loss, metrics), grads = grad_of(params, mb)
            gacc = _pin(_tree_add(gacc, _pin(jax.tree_util.tree_map(
                lambda g: g.astype(jnp.float32), grads))))
            macc = {"loss": macc["loss"] + metrics["loss"],
                    "accuracy": macc["accuracy"] + metrics["accuracy"]}
            return (gacc, macc), None

        (gsum, msum), _ = jax.lax.scan(body, (g0, m0), mbatch)
        inv = 1.0 / microbatches
        return _tree_scale(gsum, inv), _tree_scale(msum, inv)

    def train_step(state, batch):
        grads, metrics = compute_grads(state["params"], batch)
        lr = lr_fn(state["step"])
        new_params, new_opt, om = adamw_update(grads, state["opt"],
                                               state["params"], lr, opt_cfg)
        metrics = dict(metrics)
        metrics.update(om)
        metrics["lr"] = lr
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        return new_state, metrics

    return train_step


def build_serve_step(cfg: ModelConfig, ctx: QuantContext) -> Callable:
    """(params, cache, tokens (B,1), pos (B,)) -> (logits, new_cache)."""
    from ..models.api import decode_fn

    def serve_step(params, cache, tokens, pos):
        return decode_fn(params, tokens, cache, pos, cfg, ctx)

    return serve_step


def build_decode_loop(cfg: ModelConfig, ctx: QuantContext,
                      steps: int) -> Callable:
    """Device-resident decode: ``steps`` serve steps in ONE ``lax.scan``.

    The per-token serving loop pays a host↔device round trip per
    generated token (jit dispatch + blocking argmax readback + Python
    slot bookkeeping).  This builder fuses N steps into a single jitted
    call: the model step, the sampling draw, the per-slot position
    advance, and the EOS/length stopping decision all stay on device;
    the host syncs once per N-token block.

    Returned callable::

        decode_loop(params, cache, tokens, pos, live, stop_pos,
                    sample_params, key, step0, eos_id)
            -> (cache, tokens, pos, live, block_tokens, block_live, fault)

    * ``tokens`` (B, 1) i32 — each slot's next input token.
    * ``pos`` (B,) i32 — current cache position per slot.  With a
      *paged* cache the carry additionally threads the block-table
      leaves unchanged: page *assignment* is a host decision made at
      admission (the engine allocates a request's whole token budget up
      front), so the device loop never calls back into the allocator —
      each step's KV write resolves ``pos`` through the table it was
      launched with, and dead lanes resolve to the trash page.  The
      split-KV knob (``ctx.kv_split``/``ctx.pages_per_step``) is
      *static* loop configuration the same way: the builder closes
      over ``ctx``, so every scanned step runs the kernel at the
      engine-resolved split — no per-step re-dispatch, one compiled
      loop per (block size, split) point.
    * ``live`` (B,) bool — slots that are generating; dead slots are
      frozen (token/pos held, emissions masked) exactly as the per-token
      engine freezes them, so a block is bit-equivalent to N single
      steps.
    * ``stop_pos`` (B,) i32 — a slot's ``live`` drops once its position
      reaches this bound (prompt_len + gen budget).
    * ``sample_params`` — {"temperature": (B,) f32, "top_k": (B,) i32};
      temperature <= 0 is greedy (see repro.kernels.sampling).
    * ``key``/``step0`` — PRNG base and global step offset; step ``i``
      draws with ``fold_in(key, step0 + i)``, so any split of a
      generation into blocks consumes identical randomness
      (``step_many(2); step_many(3)`` == ``step_many(5)``).  ``key``
      may be None when every slot is greedy: sampling then skips the
      top-k sorts and noise generation entirely (greedy consumes no
      PRNG state, so switching between the two compiled variants never
      shifts the stream).
    * ``eos_id`` i32 scalar — sampling it kills the slot (-1 disables).

    ``block_tokens``/``block_live`` (steps, B): the token each slot
    *emitted* at each step (its input token, matching ``Engine.step``'s
    append-then-advance order) and whether the slot was live then.

    ``fault`` (B,) bool is the abort/status lane: a live slot whose
    logits come back non-finite (poisoned cache, kernel NaN) is frozen
    *on device* — its faulted step commits nothing (position/token held,
    emission masked) and the flag rides back with the block, so the host
    engine learns of device-side corruption from the block result itself
    and can restore-and-replay or fail the slot with its valid prefix.
    For finite logits the lane is identically False and the emitted
    stream is unchanged.
    """
    from ..kernels.ops import sample_tokens
    from ..models.api import decode_fn

    LOOP_BUILDS["decode"] += 1

    def decode_loop(params, cache, tokens, pos, live, stop_pos,
                    sample_params, key, step0, eos_id):
        temperature = sample_params["temperature"]
        top_k = sample_params["top_k"]

        def body(carry, i):
            cache, tok, pos, live, fault = carry
            logits, new_cache = decode_fn(params, tok, cache, pos, cfg, ctx)
            last = logits[:, -1].astype(jnp.float32)
            bad = live & ~jnp.all(jnp.isfinite(last), axis=-1)
            ok = live & ~bad
            step_key = (None if key is None
                        else jax.random.fold_in(key, step0 + i))
            nxt = sample_tokens(last, temperature, top_k, step_key,
                                backend=ctx.backend)
            emitted, emit_live = tok[:, 0], ok
            new_pos = jnp.where(ok, pos + 1, pos)
            new_tok = jnp.where(ok, nxt, tok[:, 0])[:, None]
            new_live = ok & (nxt != eos_id) & (new_pos < stop_pos)
            return (new_cache, new_tok, new_pos, new_live, fault | bad), \
                (emitted, emit_live)

        fault0 = jnp.zeros_like(live)
        (cache, tokens, pos, live, fault), (block_tokens, block_live) = \
            jax.lax.scan(body, (cache, tokens, pos, live, fault0),
                         jnp.arange(steps, dtype=jnp.int32))
        return cache, tokens, pos, live, block_tokens, block_live, fault

    return decode_loop


def build_spec_decode_loop(cfg: ModelConfig, ctx: QuantContext, steps: int,
                           k: int, *, drafter="ngram", ngram: int = 2,
                           draft_cfg: Optional[ModelConfig] = None,
                           draft_ctx: Optional[QuantContext] = None
                           ) -> Callable:
    """Speculative decode: ``steps`` draft→verify rounds in ONE scan.

    Each round proposes ``k`` tokens per slot, runs the target model
    ONCE over all k + 1 block positions (the de-specialization payoff:
    verification *is* a k+1-token chunked-prefill call — the dense
    einsum path or ``paged_attention`` handle S > 1 natively, so no
    bespoke verify forward exists; on the kernel path that call runs at
    the same ``ctx.kv_split``/``ctx.pages_per_step`` split-KV point as
    plain decode, closed over from the builder's ``ctx``), accepts the
    longest agreeing prefix via the
    :func:`repro.kernels.ops.verify_tokens` op, and advances each slot
    by its accepted length.  Greedy slots emit the target's
    exact argmax stream (byte-identical to the non-speculative engine);
    sampled slots preserve the temperature/top-k distribution through
    point-mass rejection sampling.

    Rollback is family-aware (:func:`repro.models.api.spec_state_fn`):

    * KV families (lm, dense or paged) rewind by the scalar ``pos``
      edit alone — rejected rows are overwritten by the next block's
      writes before any query can attend them (write-before-attend),
      and pages were allocated for the full token budget at admission,
      so the allocator and block tables are untouched.
    * Recurrent families (ssm, hybrid's mamba lanes) cannot un-consume
      a token: their verification runs as a k+1-step inner scan that
      checkpoints the recurrent leaves per position, and the committed
      checkpoint is gathered per slot after verification.

    ``drafter`` selects the proposal source:

    * ``"ngram"`` — prompt-lookup self-speculation (default; no second
      model).  The loop threads a ``hist`` (B, H) committed-token
      buffer and drafts by copying the continuation of the most recent
      match of the trailing ``ngram`` tokens.
    * ``(draft_cfg, draft_ctx)`` via the keyword args with
      ``drafter="model"`` — a second (smaller) model drafts greedily;
      any ``configs/*`` model sharing the target's vocab works.  Its
      cache is threaded through the carry and rolled back with the
      same family-aware machinery (it runs k + 1 draft steps so a
      fully-accepted round leaves it exactly one token behind the new
      input, like the target).
    * a callable ``(hist, tok, pos) -> (B, k) drafts`` — test hook for
      adversarial/custom proposal sources.

    Signature (ngram/callable)::

        spec_loop(params, cache, tokens, pos, live, stop_pos,
                  sample_params, key, step0, eos_id, hist)
            -> (cache, tokens, pos, live, hist,
                block_tokens, block_live, accepted, fault)

    ``fault`` (B,) is the same abort/status lane as the plain decode
    loop: a round whose verify logits are non-finite commits nothing
    for the affected slot (position/token held, emissions and history
    writes masked, accepted = 0) and flags it for the host.

    model drafter replaces the trailing ``hist`` with
    ``(draft_params, draft_cache)`` and returns the advanced (rolled
    back) ``draft_cache`` in ``hist``'s slot.

    ``block_tokens``/``block_live`` are (steps * (k+1), B) in
    chronological order — each round contributes its k + 1 block slots,
    masked down to the committed prefix of live lanes.  ``accepted``
    (steps, B) counts the drafts that survived each round (0..k, 0 for
    dead lanes); committed tokens per live round = accepted + 1, so a
    fully-rejecting drafter still advances every slot — speculation
    degrades to plain decode, never below it.

    PRNG: round ``i`` folds ``step0 + i`` exactly like the plain decode
    loop, so block splits consume identical randomness
    (``step_many(2); step_many(3)`` == ``step_many(5)``); greedy-only
    batches pass ``key=None`` and consume none.  The sampled *stream*
    intentionally differs from the non-speculative engine's (different
    randomness consumption per emitted token) — only its distribution
    is preserved.
    """
    from ..kernels.ops import verify_tokens
    from ..kernels.speculative import draft_ngram
    from ..models.api import (decode_fn, get_family, spec_restore_fn,
                              spec_state_fn)

    LOOP_BUILDS["spec"] += 1
    s_blk = k + 1
    has_rec = hasattr(get_family(cfg), "spec_state")
    model_draft = drafter == "model"
    if model_draft:
        assert draft_cfg is not None, "model drafter needs draft_cfg"
        draft_ctx = draft_ctx or ctx
        draft_has_rec = hasattr(get_family(draft_cfg), "spec_state")

    def spec_forward(params, seq, cache, pos):
        """Target logits over the block + cache with rollback handles.

        Returns (logits (B, S, V), new_cache, ckpts): ``ckpts`` is None
        for pure-KV families (chunked call, pos rewind) or the stacked
        (S, B, ...) recurrent checkpoints (per-token inner scan).
        """
        if not has_rec:
            logits, new_cache = decode_fn(params, seq, cache, pos, cfg, ctx)
            return logits, new_cache, None

        def body(c, j):
            tok_j = jax.lax.dynamic_slice_in_dim(seq, j, 1, axis=1)
            lg, nc = decode_fn(params, tok_j, c, pos + j, cfg, ctx)
            return nc, (lg[:, 0], spec_state_fn(nc, cfg))

        new_cache, (lgs, ckpts) = jax.lax.scan(body, cache,
                                               jnp.arange(s_blk))
        return jnp.moveaxis(lgs, 0, 1), new_cache, ckpts

    def draft_with_model(draft_params, dcache, tok, pos):
        """k+1 greedy draft steps (the extra step keeps the drafter's
        consumed-token count able to cover a fully-accepted round)."""
        def body(carry, j):
            dc, t = carry
            lg, dc = decode_fn(draft_params, t, dc, pos + j,
                               draft_cfg, draft_ctx)
            nxt = jnp.argmax(lg[:, -1], axis=-1).astype(jnp.int32)[:, None]
            ck = spec_state_fn(dc, draft_cfg) if draft_has_rec else None
            return (dc, nxt), (nxt[:, 0], ck)

        (dc_fin, _), (toks, dckpts) = jax.lax.scan(
            body, (dcache, tok), jnp.arange(s_blk))
        return jnp.moveaxis(toks, 0, 1)[:, :k], dc_fin, dckpts

    def spec_loop(params, cache, tokens, pos, live, stop_pos,
                  sample_params, key, step0, eos_id, *aux):
        temperature = sample_params["temperature"]
        top_k = sample_params["top_k"]
        if model_draft:
            draft_params, draft_cache = aux
            carry_aux = draft_cache
        else:
            (carry_aux,) = aux                      # hist (B, H)
        b = tokens.shape[0]
        lane = jnp.arange(b)
        jdraft = jnp.arange(k)

        def body(carry, i):
            cache, tok, pos, live, aux, fault = carry
            # -- draft ------------------------------------------------
            if model_draft:
                drafts, aux, dckpts = draft_with_model(draft_params, aux,
                                                       tok, pos)
            elif callable(drafter):
                aux = aux.at[lane, pos].set(tok[:, 0])
                drafts = drafter(aux, tok, pos).astype(jnp.int32)
            else:
                drafts, aux = draft_ngram(aux, tok, pos, k, ngram)
            # -- verify: ONE target pass over the whole block ---------
            seq = jnp.concatenate([tok, drafts], axis=1)     # (B, k+1)
            logits, new_cache, ckpts = spec_forward(params, seq, cache,
                                                    pos)
            bad = live & ~jnp.all(jnp.isfinite(logits.astype(jnp.float32)),
                                  axis=(1, 2))
            ok = live & ~bad
            step_key = (None if key is None
                        else jax.random.fold_in(key, step0 + i))
            next_tok, n_adv = verify_tokens(
                logits.astype(jnp.float32), drafts, temperature, top_k,
                step_key, backend=ctx.backend)
            # -- truncate: a committed EOS draft or the slot's token
            # budget ends the round early; the held token then matches
            # what sequential decode would hold (the first uncommitted
            # chain token, which IS the corresponding draft)
            any_eos = jnp.any(drafts == eos_id, axis=1)
            first_eos = jnp.argmax(drafts == eos_id, axis=1)     # (B,)
            limit = jnp.where(any_eos, first_eos + 1, s_blk + 1)
            # n_fin >= 1 (the clip floor) makes `pos` monotonically
            # NONDECREASING across rounds: "rewind" only discards the
            # speculative tail [pos + n_fin, pos + k + 1), never a row
            # below the committed watermark.  Prefix caching leans on
            # exactly this — a page the engine published to the prefix
            # index because `(depth+1)*page_size <= pos` held can never
            # be un-committed by a later rejection, so shared pages stay
            # immutable for every slot that maps them.
            n_fin = jnp.clip(jnp.minimum(jnp.minimum(n_adv, limit),
                                         stop_pos - pos), 1, s_blk)
            next_tok = jnp.where(n_fin < n_adv,
                                 drafts[lane, n_fin - 1], next_tok)
            # -- family-aware rollback of recurrent state -------------
            if ckpts is not None:
                sel = jax.tree_util.tree_map(lambda t: t[n_fin - 1, lane],
                                             ckpts)
                new_cache = spec_restore_fn(new_cache, sel, cfg)
            if model_draft and draft_has_rec:
                dsel = jax.tree_util.tree_map(lambda t: t[n_fin - 1, lane],
                                              dckpts)
                aux = spec_restore_fn(aux, dsel, draft_cfg)
            # -- commit: accepted drafts join the history buffer ------
            if not model_draft:
                widx = jnp.clip(pos[:, None] + 1 + jdraft[None, :],
                                0, aux.shape[1] - 1)
                held = jnp.take_along_axis(aux, widx, axis=1)
                wmask = ok[:, None] & (jdraft[None, :]
                                       < n_fin[:, None] - 1)
                aux = aux.at[lane[:, None], widx].set(
                    jnp.where(wmask, drafts, held))
            committed = jnp.arange(s_blk)[None, :] < n_fin[:, None]
            emit_live = ok[:, None] & committed              # (B, k+1)
            new_pos = jnp.where(ok, pos + n_fin, pos)
            new_tok = jnp.where(ok, next_tok, tok[:, 0])[:, None]
            new_live = ok & (next_tok != eos_id) & (new_pos < stop_pos)
            accepted = jnp.where(ok, n_fin - 1, 0)
            return (new_cache, new_tok, new_pos, new_live, aux,
                    fault | bad), (seq, emit_live, accepted)

        fault0 = jnp.zeros_like(live)
        (cache, tokens, pos, live, carry_aux, fault), \
            (toks, emits, accepted) = \
            jax.lax.scan(body, (cache, tokens, pos, live, carry_aux,
                                fault0),
                         jnp.arange(steps, dtype=jnp.int32))
        # (steps, B, k+1) -> chronological (steps*(k+1), B)
        block_tokens = toks.transpose(0, 2, 1).reshape(steps * s_blk, -1)
        block_live = emits.transpose(0, 2, 1).reshape(steps * s_blk, -1)
        return (cache, tokens, pos, live, carry_aux,
                block_tokens, block_live, accepted, fault)

    return spec_loop


def build_prefill_step(cfg: ModelConfig, ctx: QuantContext, *,
                       first_token: bool = False) -> Callable:
    """(params, batch, cache, pos) -> (chunk_logits, cache).

    The chunked-prefill step: ``pos`` gives each slot's current cache
    position and the returned logits cover every chunk position (so
    ragged prompt ends can be read per slot).  Pass ``pos=None`` for a
    whole-prompt prefill from position 0.  This full-logits form serves
    the drafter's prefill and ``chip_smoke``'s logit comparison.

    ``first_token=True`` builds the serving engine's form instead:
    (params, batch, cache, pos, last) -> (first_tokens, cache).  ``last``
    (B,) int32 is each slot's row of its last prompt token within this
    chunk (any row in ``[0, chunk)`` for a slot whose prompt does not end
    here); the step gathers that row of the chunk's final hidden states,
    applies the final norm and the LM head to those B rows only, and
    returns their greedy argmax as a (B,) int32, so neither the device
    nor the host holds the (B, chunk, vocab) logits.  Ties go to the
    lowest index, as in ``np.argmax``.  Both forms keep the jitted name
    ``prefill_step``, by which the benchmark finds the program in a
    device trace.

    Cache-layout agnostic: with a paged cache the chunk's K/V scatter
    through each slot's block table instead of a dense row range, and
    writes past a slot's allocation (the dense layout's margin rows)
    land on the shared trash page.  Same step function, same jit — the
    layout is carried entirely by the cache pytree.
    """
    from ..models.api import prefill_first_tokens_fn, prefill_fn

    if not first_token:
        def prefill_step(params, batch, cache, pos=None):
            return prefill_fn(params, batch, cache, cfg, ctx, pos=pos,
                              full_logits=True)
        return prefill_step

    def prefill_step(params, batch, cache, pos, last):
        return prefill_first_tokens_fn(params, batch, cache, cfg, ctx,
                                       pos=pos, last=last)

    return prefill_step
