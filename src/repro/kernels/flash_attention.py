"""Pallas TPU kernel: blocked online-softmax (flash) attention, GQA-aware.

Beyond-paper performance layer (recorded separately in EXPERIMENTS.md
§Perf): prefill attention is the dominant compute term at 32k context, and
a blocked online-softmax keeps the (Sq × Skv) logits out of HBM entirely —
the working set per grid step is one (bq, d) query block, one (bk, d)
key/value block, and (bq, d)+(bq, 1)×2 f32 scratch in VMEM.

GQA is honoured structurally: K/V keep their ``Hkv`` head axis and the
BlockSpec index map folds the query head onto its KV group
(``h // group``) — grouped KV is *never* broadcast-materialized, which is
the whole point of GQA's cache-size savings.

Causality is handled at two granularities: whole (iq, ik) blocks strictly
above the diagonal are skipped via ``pl.when`` (no MXU work, no VMEM
traffic), and the diagonal blocks apply an elementwise mask.  Padded tail
positions (wrapper pads Sq/Skv to block multiples) are masked with the
same mechanism.

Head dim should be a multiple of 128 for exact MXU tiling; other sizes
(e.g. MLA's 192) are still correct — Mosaic pads the lane dimension.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_pallas", "paged_attention_pallas",
           "paged_attention_xla", "combine_splits", "choose_kv_split",
           "auto_pages_per_step", "get_cost_constants",
           "set_cost_constants"]

_NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            sq: int, skv: int, bq: int, bk: int, nk: int, causal: bool,
            scale: float):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # absolute positions; queries sit at the tail of the kv context
    q_off = skv - sq

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bk, d)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # (bq, bk)

        qpos = q_off + iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < skv                                    # kv padding
        if causal:
            mask = mask & (qpos >= kpos)
        logits = jnp.where(mask, logits, _NEG)

        m_prev = m_ref[...]                                  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
        p = jnp.exp(logits - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)                  # (bk, d)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if causal:
        # skip blocks strictly above the diagonal: no kv position in this
        # block is visible to any query in the q block
        visible = (q_off + iq * bq + (bq - 1)) >= (ik * bk)
        pl.when(visible)(_compute)
    else:
        _compute()

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "softmax_scale", "bq",
                                             "bk", "interpret"))
def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                           causal: bool = True,
                           softmax_scale: float | None = None,
                           bq: int = 128, bk: int = 128,
                           interpret: bool = False) -> jnp.ndarray:
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D); Hq % Hkv == 0."""
    b, hq, sq, d = q.shape
    _, hkv, skv, dv = k.shape[0], k.shape[1], k.shape[2], v.shape[3]
    assert hq % hkv == 0 and dv == d
    group = hq // hkv
    scale = softmax_scale if softmax_scale is not None else float(1.0 / np.sqrt(d))

    bq = min(bq, sq)
    bk = min(bk, skv)
    pq = (-sq) % bq
    pk = (-skv) % bk
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0))) if pq else q
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0))) if pk else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0))) if pk else v
    nq = qp.shape[2] // bq
    nk = kp.shape[2] // bk

    out = pl.pallas_call(
        functools.partial(_kernel, sq=sq, skv=skv, bq=bq, bk=bk, nk=nk,
                          causal=causal, scale=scale),
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bb, h, iq, ik: (bb, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bb, h, iq, ik: (bb, h // group, ik, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bb, h, iq, ik: (bb, h // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda bb, h, iq, ik: (bb, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running denom
            pltpu.VMEM((bq, d), jnp.float32),   # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qp, kp, vp)

    return out[:, :, :sq] if pq else out


# ===========================================================================
# Paged attention: block-table-indexed KV pages (decode + chunked prefill)
# ===========================================================================
def _paged_kernel(bt_ref, qpos_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, s: int, ps: int, npages: int,
                  scale: float):
    """Grid (B, Hkv, NP): online softmax over one sequence's pages.

    The q block holds all ``group * s`` query rows of one (batch, kv
    head) pair, folded group-major — row ``r`` is query position
    ``qpos[b] + r % s`` of head group member ``r // s``.  The k/v
    blocks are one physical page each, DMA'd via the scalar-prefetched
    block table (``bt_ref``) — the kernel never sees a contiguous
    cache, which is the entire point: block-table position ``ip``
    covers logical kv positions ``[ip*ps, (ip+1)*ps)`` wherever the
    page physically lives.
    """
    b = pl.program_id(0)
    ip = pl.program_id(2)

    @pl.when(ip == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    rows = q_ref.shape[2]
    qpos0 = qpos_ref[b]

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (rows, d)
        k = k_ref[0, 0].astype(jnp.float32)                  # (ps, d)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # (rows, ps)

        # absolute positions: query row r sits at qpos0 + r % s; kv
        # column c of block-table entry ip is logical position ip*ps + c
        r = jax.lax.broadcasted_iota(jnp.int32, (rows, ps), 0)
        qp = qpos0 + jax.lax.rem(r, s)
        kvpos = ip * ps + jax.lax.broadcasted_iota(jnp.int32, (rows, ps), 1)
        mask = kvpos <= qp                                   # write-before-attend
        logits = jnp.where(mask, logits, _NEG)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
        p = jnp.exp(logits - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)                  # (ps, d)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    # skip pages wholly beyond the last visible position (no MXU work,
    # no VMEM traffic) — the paged analogue of the causal block skip
    pl.when(ip * ps <= qpos0 + (s - 1))(_compute)

    @pl.when(ip == npages - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _paged_attention_unsplit(q: jnp.ndarray, k_pages: jnp.ndarray,
                             v_pages: jnp.ndarray, block_tables: jnp.ndarray,
                             qpos: jnp.ndarray, *,
                             softmax_scale: float | None = None,
                             interpret: bool = False) -> jnp.ndarray:
    """The original one-page-per-step lowering (``kv_split=1``,
    ``pages_per_step=1``).  Kept verbatim: the split dispatcher routes
    the (1, 1) knob point here so it reproduces the pre-split kernel
    byte-for-byte."""
    b, hq, s, d = q.shape
    p_, hkv, ps, _ = k_pages.shape
    np_ = block_tables.shape[1]
    assert hq % hkv == 0
    group = hq // hkv
    rows = group * s
    scale = (softmax_scale if softmax_scale is not None
             else float(1.0 / np.sqrt(d)))

    # fold query heads group-major onto their kv head: (B, Hkv, G*S, D)
    qf = q.reshape(b, hkv, group, s, d).reshape(b, hkv, rows, d)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, np_),
        in_specs=[
            pl.BlockSpec((1, 1, rows, d),
                         lambda bb, h, ip, bt, qp: (bb, h, 0, 0)),
            pl.BlockSpec((1, 1, ps, d),
                         lambda bb, h, ip, bt, qp: (bt[bb, ip], h, 0, 0)),
            pl.BlockSpec((1, 1, ps, d),
                         lambda bb, h, ip, bt, qp: (bt[bb, ip], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rows, d),
                               lambda bb, h, ip, bt, qp: (bb, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),   # running max
            pltpu.VMEM((rows, 1), jnp.float32),   # running denom
            pltpu.VMEM((rows, d), jnp.float32),   # output accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, s=s, ps=ps, npages=np_,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(block_tables, jnp.int32), jnp.asarray(qpos, jnp.int32),
      qf, k_pages, v_pages)

    return out.reshape(b, hkv, group, s, d).reshape(b, hq, s, d)


# ===========================================================================
# Split-KV paged attention: flash-decoding partials + log-sum-exp combine
# ===========================================================================
def _paged_split_kernel(bt_ref, qpos_ref, q_ref, *refs, s: int, ps: int,
                        t: int, nt: int, scale: float):
    """Grid (B, Hkv, kv_split, NT): per-partition online-softmax partials.

    Flash-decoding layout: each slot's block table is cut into
    ``kv_split`` contiguous partitions of ``nt`` *tiles* (a tile is
    ``t = pages_per_step`` consecutive block-table entries, DMA'd as
    ``t`` concurrent page fetches and concatenated in VMEM — the
    pipeline double-buffers them across grid steps).  The partition
    axis is a *parallel* grid dimension: partitions never share
    scratch, so long-context decode stops being one serial page chain.
    Each partition emits its raw online-softmax state — ``acc`` (the
    un-normalized weighted V sum), ``m`` (running max) and ``l``
    (running denominator) — and :func:`combine_splits` merges them in a
    second log-sum-exp stage.

    Masking is identical to :func:`_paged_kernel`: tile entry ``base +
    j`` covers logical kv positions ``[(base+j)*ps, (base+j+1)*ps)``,
    visibility is ``kvpos <= qpos[b] + r % s``, and tiles wholly beyond
    the last visible position are skipped (dead partitions keep their
    init state — ``m = -1e30, l = 0`` — which the combine maps to
    exactly-zero weight, so trash-page garbage and dead lanes cannot
    leak into any partition's sum).
    """
    b = pl.program_id(0)
    sp = pl.program_id(2)
    it = pl.program_id(3)
    k_refs, v_refs = refs[:t], refs[t:2 * t]
    acc_o, m_o, l_o = refs[2 * t:2 * t + 3]
    m_s, l_s, acc_s = refs[2 * t + 3:]

    @pl.when(it == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, _NEG)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    rows = q_ref.shape[2]
    qpos0 = qpos_ref[b]
    base = (sp * nt + it) * t      # first block-table entry of this tile

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (rows, d)
        k = jnp.concatenate(
            [kr[0, 0].astype(jnp.float32) for kr in k_refs], axis=0)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # (rows, t*ps)

        r = jax.lax.broadcasted_iota(jnp.int32, (rows, t * ps), 0)
        qp = qpos0 + jax.lax.rem(r, s)
        kvpos = base * ps + jax.lax.broadcasted_iota(
            jnp.int32, (rows, t * ps), 1)
        mask = kvpos <= qp                                   # write-before-attend
        logits = jnp.where(mask, logits, _NEG)

        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
        p = jnp.exp(logits - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_s[...] = alpha * l_s[...] + jnp.sum(p, axis=1, keepdims=True)
        v = jnp.concatenate(
            [vr[0, 0].astype(jnp.float32) for vr in v_refs], axis=0)
        acc_s[...] = alpha * acc_s[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_s[...] = m_new

    # the paged analogue of the causal block skip, per tile
    pl.when(base * ps <= qpos0 + (s - 1))(_compute)

    @pl.when(it == nt - 1)
    def _finish():
        acc_o[0, 0, 0] = acc_s[...]
        m_o[0, 0, 0] = m_s[...]
        l_o[0, 0, 0] = l_s[...]


def combine_splits(acc: jnp.ndarray, m: jnp.ndarray, l: jnp.ndarray):
    """Log-sum-exp merge of per-partition online-softmax partials.

    ``acc`` (kv_split, ..., rows, d), ``m``/``l`` (kv_split, ..., rows,
    1) — partition axis leading.  Returns the merged ``(acc*, m*, l*)``
    such that ``acc* / max(l*, eps)`` equals the unsplit online softmax
    over the concatenated partitions.

    This is THE combine formula: the Pallas wrapper and the ``ref.py``
    oracle both call it (shared-formula rule — a re-derived but
    last-ulp-different merge would break the fused≡ref exact-match
    contract).  Dead partitions (``m = -1e30, l = 0`` init state —
    nothing visible, e.g. a trash-page-only tail) contribute
    ``exp(-1e30 - m*) = 0`` weight; if *every* partition is dead the
    caller's ``max(l*, eps)`` guard maps the output to exactly 0, the
    same convention as the unsplit kernel's dead-lane output.
    """
    m_star = jnp.max(m, axis=0)                         # (..., rows, 1)
    alpha = jnp.exp(m - m_star[None])                   # (split, ..., rows, 1)
    l_star = jnp.sum(alpha * l, axis=0)
    acc_star = jnp.sum(alpha * acc, axis=0)
    return acc_star, m_star, l_star


#: relative latency units of the split cost model: one multi-page tile
#: (DMA + MXU pass) vs one partition's extra combine traffic.  Coarse on
#: purpose — the model only has to rank splits, not predict walltime
#: (rule4ml's lesson: a cheap learned/analytic ranker beats hand-tuning).
#: These are the *analytic defaults*; ``set_cost_constants`` installs
#: values fitted from measured latencies (launch/autotune.py) without
#: the ranking formula changing shape.
_ANALYTIC_COST_CONSTANTS = {
    "tile_cost": 4.0,        # one multi-page tile: DMA + MXU pass
    "combine_cost": 1.0,     # one partition's extra combine traffic
    "target_lanes": 512.0,   # grid lanes that saturate the pipeline
}
_TILE_COST = _ANALYTIC_COST_CONSTANTS["tile_cost"]
_COMBINE_COST = _ANALYTIC_COST_CONSTANTS["combine_cost"]
_TARGET_LANES = _ANALYTIC_COST_CONSTANTS["target_lanes"]


def get_cost_constants() -> dict:
    """Current split cost-model constants (a copy; mutate via
    :func:`set_cost_constants`)."""
    return {"tile_cost": _TILE_COST, "combine_cost": _COMBINE_COST,
            "target_lanes": _TARGET_LANES}


def set_cost_constants(tile_cost: float | None = None,
                       combine_cost: float | None = None,
                       target_lanes: float | None = None) -> dict:
    """Install cost-model constants (``None`` = reset to the analytic
    default) and invalidate every cached ``choose_kv_split`` decision.

    This is the seam the autotuner uses: ``launch/autotune.py`` fits
    tile/combine costs from measured ``paged_attention`` latencies and
    installs them here, so *every* downstream auto split — fused decode
    loops, spec verify, direct kernel calls — re-ranks under the fitted
    model with no call-site changes.  Returns the constants now in
    effect.
    """
    global _TILE_COST, _COMBINE_COST, _TARGET_LANES
    _TILE_COST = float(tile_cost) if tile_cost is not None \
        else _ANALYTIC_COST_CONSTANTS["tile_cost"]
    _COMBINE_COST = float(combine_cost) if combine_cost is not None \
        else _ANALYTIC_COST_CONSTANTS["combine_cost"]
    _TARGET_LANES = float(target_lanes) if target_lanes is not None \
        else _ANALYTIC_COST_CONSTANTS["target_lanes"]
    choose_kv_split.cache_clear()       # decisions depend on the constants
    return get_cost_constants()


@functools.lru_cache(maxsize=None)
def choose_kv_split(seq_len: int, pages: int, hkv: int, *, batch: int = 1,
                    pages_per_step: int = 1) -> int:
    """Pick ``kv_split`` from a cached analytic latency model.

    The serving-side reuse-factor selector (the paper's knob, chosen
    rule4ml-style from a cost model instead of hand-tuning): modeled
    decode latency of a split is its serial tile chain plus the
    per-partition combine overhead,

        cost(split) = ceil(tiles / split) * TILE + split * COMBINE,

    minimized over power-of-two splits — with an occupancy guard: once
    ``batch * hkv * split`` already saturates the pipeline's parallel
    lanes, further splitting only buys combine overhead, so deeper
    candidates are skipped.  The *boundary* candidate — the first split
    whose predecessor saturates — is still costed before the guard
    fires (an earlier revision broke out before costing it, pinning
    every ``lanes >= target`` geometry to ``split=1`` no matter how
    long the tile chain was).  Ties break toward the smaller split
    (fewer partials in HBM).  Cached per shape tuple — the engine
    resolves it once per cache geometry, not per step.

    ``seq_len`` (the table capacity in tokens, ``pages * page_size`` at
    every current call site) is part of the knob's public shape key but
    not yet a cost term: it is reserved for hardware-fitted constants
    (ROADMAP: fit TILE/COMBINE from measured TPU latency curves, where
    absolute context length sets the DMA/compute balance).
    """
    pages = max(1, int(pages))
    t = max(1, int(pages_per_step))
    tiles = -(-pages // t)
    lanes = max(1, int(batch) * max(1, int(hkv)))
    best, best_cost = 1, None
    split = 1
    while split <= tiles:
        cost = (-(-tiles // split)) * _TILE_COST + split * _COMBINE_COST
        if best_cost is None or cost < best_cost:
            best, best_cost = split, cost
        if split > 1 and lanes * (split // 2) >= _TARGET_LANES:
            # saturated: deeper splits only add combine overhead (this
            # boundary candidate was costed above, not skipped — the
            # old guard broke one candidate too early).
            break
        split *= 2
    return best


def auto_pages_per_step(page_size: int, pages: int) -> int:
    """Default multi-page tile: enough consecutive pages per grid step
    to feed the MXU a ~128-row K/V operand (one full systolic pass),
    capped by the table width."""
    return max(1, min(128 // max(1, int(page_size)), max(1, int(pages))))


def _resolve_knobs(np_: int, ps: int, hkv: int, batch: int,
                   kv_split, pages_per_step):
    """One resolution rule for every lowering (and the engine mirrors
    it): explicit values clamp to the table; an *auto* tile additionally
    shrinks to honour an *explicit* split (otherwise a tile that
    swallows the whole table would silently clamp a requested
    ``kv_split`` back to 1); an auto split comes from the cost model at
    the resolved tile.  Returns ``(pages_per_step, kv_split)``.
    """
    if pages_per_step is None:
        if kv_split is not None and int(kv_split) == 1:
            # the documented regression baseline: an explicit split of 1
            # alone means "today's serial page chain, byte-identical" —
            # an auto tile would route through the split kernel (same
            # math, different float association).  Tiling WITH split=1
            # is still reachable by pinning pages_per_step explicitly.
            t = 1
        else:
            t = auto_pages_per_step(ps, np_)
            if kv_split is not None and int(kv_split) > 1:
                t = min(t, max(1, -(-np_ // int(kv_split))))
    else:
        t = max(1, min(int(pages_per_step), np_))
    tiles = -(-np_ // t)
    if kv_split is None:
        split = choose_kv_split(np_ * ps, np_, hkv, batch=batch,
                                pages_per_step=t)
    else:
        split = max(1, int(kv_split))
    return t, min(split, tiles)


@functools.partial(jax.jit, static_argnames=("softmax_scale", "interpret",
                                             "kv_split", "pages_per_step"))
def paged_attention_pallas(q: jnp.ndarray, k_pages: jnp.ndarray,
                           v_pages: jnp.ndarray, block_tables: jnp.ndarray,
                           qpos: jnp.ndarray, *,
                           softmax_scale: float | None = None,
                           kv_split: int | None = None,
                           pages_per_step: int | None = None,
                           interpret: bool = False) -> jnp.ndarray:
    """Block-table-indexed flash attention over a shared KV page pool.

    Shapes as :func:`repro.kernels.ref.paged_attention_ref` (the
    numerics oracle): q (B, Hq, S, D), pages (P, Hkv, ps, D), block
    tables (B, NP) int32, qpos (B,) int32.  S == 1 is the decode step;
    S > 1 a prefill chunk whose K/V were already scattered into the
    pages (write-before-attend: ``qpos + S <= NP * page_size`` is the
    op contract — every query position fits the table).  GQA is
    honoured structurally — the page BlockSpec folds the query head
    onto its KV group and each page is fetched once per (batch, kv
    head), never broadcast to Hq.  Block tables ride in SMEM via scalar
    prefetch (``PrefetchScalarGridSpec``) so every page DMA address is
    known before the kernel body runs.

    ``kv_split`` / ``pages_per_step`` are the kernel's reuse-factor
    knob (None = choose from the cached cost model): the block table is
    cut into ``kv_split`` parallel partitions whose flash-decoding
    partials merge in a log-sum-exp combine stage
    (:func:`combine_splits`), and each grid step DMAs a tile of
    ``pages_per_step`` consecutive table entries instead of one —
    double-buffered by the Pallas pipeline — so decode latency stops
    scaling with the serial page chain.  ``kv_split=1,
    pages_per_step=1`` routes through the original kernel unchanged
    (byte-for-byte identical results).
    """
    b, hq, s, d = q.shape
    p_, hkv, ps, _ = k_pages.shape
    np_ = block_tables.shape[1]
    assert hq % hkv == 0

    t, split = _resolve_knobs(np_, ps, hkv, b, kv_split, pages_per_step)
    tiles = -(-np_ // t)

    if split == 1 and t == 1:
        return _paged_attention_unsplit(q, k_pages, v_pages, block_tables,
                                        qpos, softmax_scale=softmax_scale,
                                        interpret=interpret)

    group = hq // hkv
    rows = group * s
    scale = (softmax_scale if softmax_scale is not None
             else float(1.0 / np.sqrt(d)))
    qf = q.reshape(b, hkv, group, s, d).reshape(b, hkv, rows, d)

    # pad the table so every partition holds exactly nt full tiles; pad
    # entries point at page 0 — always a valid DMA target, and always
    # masked (their logical positions are >= NP*ps > qpos + s - 1 by
    # the op contract above)
    nt = -(-tiles // split)
    np_pad = split * nt * t
    bt = jnp.asarray(block_tables, jnp.int32)
    if np_pad > np_:
        bt = jnp.pad(bt, ((0, 0), (0, np_pad - np_)))

    def _page_spec(j):
        return pl.BlockSpec(
            (1, 1, ps, d),
            lambda bb, h, sp, it, bt, qp, j=j:
                (bt[bb, (sp * nt + it) * t + j], h, 0, 0))

    def _out_spec(last):
        return pl.BlockSpec(
            (1, 1, 1, rows, last),
            lambda bb, h, sp, it, bt, qp: (sp, bb, h, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, split, nt),
        in_specs=[pl.BlockSpec((1, 1, rows, d),
                               lambda bb, h, sp, it, bt, qp: (bb, h, 0, 0))]
                 + [_page_spec(j) for j in range(t)] * 2,
        out_specs=[_out_spec(d), _out_spec(1), _out_spec(1)],
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),   # running max
            pltpu.VMEM((rows, 1), jnp.float32),   # running denom
            pltpu.VMEM((rows, d), jnp.float32),   # output accumulator
        ],
    )
    acc, m, l = pl.pallas_call(
        functools.partial(_paged_split_kernel, s=s, ps=ps, t=t, nt=nt,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((split, b, hkv, rows, d), jnp.float32),
            jax.ShapeDtypeStruct((split, b, hkv, rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((split, b, hkv, rows, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(bt, jnp.asarray(qpos, jnp.int32), qf,
      *([k_pages] * t), *([v_pages] * t))

    acc_star, _, l_star = combine_splits(acc, m, l)
    out = acc_star / jnp.maximum(l_star, 1e-30)
    return out.astype(q.dtype).reshape(b, hkv, group, s, d) \
              .reshape(b, hq, s, d)


@functools.partial(jax.jit, static_argnames=("softmax_scale", "kv_split",
                                             "pages_per_step"))
def paged_attention_xla(q: jnp.ndarray, k_pages: jnp.ndarray,
                        v_pages: jnp.ndarray, block_tables: jnp.ndarray,
                        qpos: jnp.ndarray, *,
                        softmax_scale: float | None = None,
                        kv_split: int | None = None,
                        pages_per_step: int | None = None) -> jnp.ndarray:
    """The split-KV *schedule* lowered through plain XLA (no Pallas).

    The third lowering of the op (ref = semantics, pallas = TPU, this =
    portable schedule model): a ``lax.scan`` whose carried state is the
    online-softmax ``(m, l, acc)`` triple and whose step processes one
    ``pages_per_step``-page tile of EVERY partition at once — the
    partition axis rides as a batch dimension, so the serial dependence
    chain is ``ceil(tiles / kv_split)`` scan steps instead of the
    unsplit kernel's one-step-per-page chain.  ``kv_split=1,
    pages_per_step=1`` is therefore the faithful executable model of
    the serial kernel's latency (one page per dependence-chain step),
    which is what the long-context bench measures split-KV against on
    CPU hosts — where interpret-mode Pallas walltime measures the
    interpreter, not the schedule.  Shares :func:`combine_splits` and
    the masking convention with the kernel and the ref oracle.
    """
    b, hq, s, d = q.shape
    p_, hkv, ps, _ = k_pages.shape
    np_ = block_tables.shape[1]
    assert hq % hkv == 0
    group = hq // hkv
    rows = group * s
    scale = (softmax_scale if softmax_scale is not None
             else float(1.0 / np.sqrt(d)))

    t, split = _resolve_knobs(np_, ps, hkv, b, kv_split, pages_per_step)
    tiles = -(-np_ // t)
    nt = -(-tiles // split)
    np_pad = split * nt * t
    bt = jnp.asarray(block_tables, jnp.int32)
    if np_pad > np_:
        bt = jnp.pad(bt, ((0, 0), (0, np_pad - np_)))
    bt4 = bt.reshape(b, split, nt, t)

    qf = (q.reshape(b, hkv, group, s, d).reshape(b, hkv, rows, d)
          .astype(jnp.float32) * scale)
    qp_rows = (jnp.asarray(qpos, jnp.int32)[:, None]
               + jnp.arange(rows, dtype=jnp.int32) % s)       # (B, rows)
    base_sp = jnp.arange(split, dtype=jnp.int32) * (nt * t * ps)

    def body(carry, it):
        m, l, acc = carry
        idx = jax.lax.dynamic_index_in_dim(bt4, it, axis=2,
                                           keepdims=False)    # (B, S, t)
        k = k_pages[idx].transpose(0, 1, 3, 2, 4, 5) \
            .reshape(b, split, hkv, t * ps, d).astype(jnp.float32)
        v = v_pages[idx].transpose(0, 1, 3, 2, 4, 5) \
            .reshape(b, split, hkv, t * ps, d).astype(jnp.float32)
        logits = jnp.einsum("bhrd,bshkd->bshrk", qf, k,
                            preferred_element_type=jnp.float32)
        kvpos = (base_sp[:, None] + it * (t * ps)
                 + jnp.arange(t * ps, dtype=jnp.int32)[None, :])  # (S, K)
        mask = (kvpos[None, :, None, None, :]
                <= qp_rows[:, None, None, :, None])
        logits = jnp.where(mask, logits, _NEG)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = alpha * acc + jnp.einsum("bshrk,bshkd->bshrd", p, v,
                                       preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    init = (jnp.full((b, split, hkv, rows, 1), _NEG, jnp.float32),
            jnp.zeros((b, split, hkv, rows, 1), jnp.float32),
            jnp.zeros((b, split, hkv, rows, d), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(body, init,
                                  jnp.arange(nt, dtype=jnp.int32))
    # partition axis leading, as combine_splits expects
    acc_star, _, l_star = combine_splits(acc.transpose(1, 0, 2, 3, 4),
                                         m.transpose(1, 0, 2, 3, 4),
                                         l.transpose(1, 0, 2, 3, 4))
    out = acc_star / jnp.maximum(l_star, 1e-30)
    return out.astype(q.dtype).reshape(b, hkv, group, s, d) \
              .reshape(b, hq, s, d)
