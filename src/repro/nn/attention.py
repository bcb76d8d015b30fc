"""Attention modules: GQA/MQA/MHA, cross-attention, MLA — prefill & decode.

Three execution regimes per module:

* **prefill / training** — full-sequence attention.  Dispatches to the
  flash Pallas kernel (``repro.kernels.attention``) unless the context
  routes softmax through constant tables (``ctx.use_lut``), in which case
  the einsum path with :func:`repro.nn.activations.softmax` is used so the
  paper's LUT-exp is exercised end to end.
* **decode** — single-token step against a pre-allocated KV cache
  (``dynamic_update_slice`` at ``pos``); O(S) einsums, no kernel needed.
  The *paged* decode regime scatters K/V through per-slot block tables
  instead and, on the kernel path, runs split-KV flash-decoding: the
  ``ctx.kv_split``/``ctx.pages_per_step`` knob partitions each slot's
  page chain into parallel online-softmax lanes merged by a
  log-sum-exp combine (``repro.kernels.flash_attention``).
* **cross** — encoder-decoder attention (whisper, llama-vision); KV come
  from the encoder stream and are position-encoding-free.

MLA (deepseek-v2) is implemented in its two canonical forms: *naive* for
prefill (materialize per-head K/V from the latent, use flash attention)
and *absorbed* for decode (score directly against the 512-dim latent
cache + shared 64-dim RoPE key — the cache is (S, 576) per token
regardless of the 128 heads, which is MLA's entire point).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from ..core.qtypes import QTensor
from ..dist.constrain import constrain, kernel_map, splits
from .activations import softmax
from .context import DEFAULT_CTX, QuantContext, kernel_path
from .linear import linear, linear_init
from .norms import rmsnorm, rmsnorm_init
from .rope import apply_rope


def _constrain_heads(t: jnp.ndarray, role: str = "q") -> jnp.ndarray:
    """Pin (B, H, S, D): TP on heads when divisible; fallbacks depend on
    the ``sp_attn`` perf flag.

    Head-count sharding is the Megatron-native layout.  When heads don't
    divide the model axis (MQA/GQA with kv ≤ 8 on 16-way TP):

    * baseline: head-dim sharding (attention contractions become psums of
      full logits — measured pathological for MQA at 32k, see §Perf H2);
    * ``sp_attn``: sequence-parallel — queries shard their *seq* axis,
      K/V replicate (they are small precisely because Hkv is small), and
      every chunk's logits stay local.
    """
    from ..dist.constrain import current_mesh
    from ..dist.options import flags
    mesh = current_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return t
    tp = mesh.shape["model"]
    if t.shape[1] % tp == 0:
        return constrain(t, "dp", "tp", None, None)
    if flags().sp_attn and t.shape[2] > 1:
        if role == "q":
            return constrain(t, "dp", None, "tp", None)
        return constrain(t, "dp", None, None, None)   # replicate K/V
    return constrain(t, "dp", None, None, "tp")

__all__ = ["AttnDims", "gqa_init", "gqa_apply", "gqa_cache_spec",
           "gqa_paged_cache_spec", "gqa_project_kv", "MLADims", "mla_init",
           "mla_apply", "mla_cache_spec", "mla_paged_cache_spec",
           "paged_kernel_reads", "select_lanes"]


def paged_kernel_reads(ctx: QuantContext, pages, *,
                       causal: bool = True) -> bool:
    """Whether attention over a paged cache whose page pools are
    ``pages`` (a layer's ``cache["pages"]``, or its keys) runs the
    block-table Pallas kernel under ``ctx``; otherwise the pages are
    gathered into a contiguous view and attended by einsum.

    The kernel reads float GQA head pages (``k``/``v``) under a causal
    mask only: int8 pages (with ``k_scale``/``v_scale``) and MLA latent
    pages (``ckv``/``krope``) are always gathered.  The attention modules
    dispatch on this and :func:`repro.nn.context.lowerings` reports it.
    """
    return (set(pages) == {"k", "v"} and causal
            and (kernel_path(ctx) or ctx.force_paged_kernel))


def gqa_project_kv(p, kv_src: jnp.ndarray, d: "AttnDims",
                   ctx: "QuantContext" = DEFAULT_CTX, *, path: str = "attn"):
    """Project cross-attention K/V once (prefill) → (B, Hkv, Skv, Dh)."""
    b, skv, _ = kv_src.shape
    k = linear(p["wk"], kv_src, ctx, path=f"{path}/wk")
    v = linear(p["wv"], kv_src, ctx, path=f"{path}/wv")
    k = k.reshape(b, skv, d.n_kv_heads, d.head_dim).transpose(0, 2, 1, 3)
    v = v.reshape(b, skv, d.n_kv_heads, d.head_dim).transpose(0, 2, 1, 3)
    return k, v


# ===========================================================================
# GQA / MQA / MHA / cross-attention
# ===========================================================================
@dataclasses.dataclass(frozen=True)
class AttnDims:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    rope_fraction: float = 1.0   # glm4 uses 0.5
    use_rope: bool = True        # whisper uses absolute embeddings instead
    qkv_bias: bool = False       # glm4 uses qkv bias
    causal: bool = True


def gqa_init(rng, d: AttnDims, *, dtype=jnp.float32):
    ks = jax.random.split(rng, 4)
    return {
        "wq": linear_init(ks[0], d.d_model, d.n_heads * d.head_dim,
                          bias=d.qkv_bias, dtype=dtype),
        "wk": linear_init(ks[1], d.d_model, d.n_kv_heads * d.head_dim,
                          bias=d.qkv_bias, dtype=dtype),
        "wv": linear_init(ks[2], d.d_model, d.n_kv_heads * d.head_dim,
                          bias=d.qkv_bias, dtype=dtype),
        "wo": linear_init(ks[3], d.n_heads * d.head_dim, d.d_model,
                          dtype=dtype),
    }


def gqa_cache_spec(d: AttnDims, batch: int, max_len: int, dtype=jnp.bfloat16):
    """KV cache pytree: K and V of shape (B, Hkv, S_max, Dh).

    ``dtype=jnp.int8`` selects the quantized cache: int8 payload plus
    per-(token, head) bf16 scales — the paper's parametric quantization
    applied to the serving cache (2× HBM capacity/traffic on the K/V
    stream vs bf16).
    """
    shape = (batch, d.n_kv_heads, max_len, d.head_dim)
    cache = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if dtype == jnp.int8:
        sshape = (batch, d.n_kv_heads, max_len, 1)
        cache["k_scale"] = jnp.zeros(sshape, jnp.bfloat16)
        cache["v_scale"] = jnp.zeros(sshape, jnp.bfloat16)
    return cache


def gqa_paged_cache_spec(d: AttnDims, batch: int, num_pages: int,
                         page_size: int, table_width: int,
                         dtype=jnp.bfloat16):
    """Paged KV cache: a shared pool of fixed-size pages + block tables.

    The de-specialized layout (vs :func:`gqa_cache_spec`'s per-slot
    ``max_len`` buffers): K/V rows live in ``num_pages`` pages of
    ``page_size`` tokens each, shared by every slot, and
    ``block_table[b, j]`` names the physical page holding slot ``b``'s
    logical tokens ``[j*page_size, (j+1)*page_size)``.  One extra
    *trash page* (physical index ``num_pages``) absorbs writes from
    lanes with no allocation — dead lanes' held-token decode writes and
    chunked-prefill margin writes land there instead of needing
    per-slot margin rows.  Unset table entries point at it.

    ``dtype=jnp.int8`` pages the quantized cache: int8 payload pages
    plus per-(token, head) bf16 scale pages, exactly mirroring the
    dense int8 layout so paged and dense serving quantize identically.
    """
    shape = (num_pages + 1, d.n_kv_heads, page_size, d.head_dim)
    pages = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if dtype == jnp.int8:
        sshape = (num_pages + 1, d.n_kv_heads, page_size, 1)
        pages["k_scale"] = jnp.zeros(sshape, jnp.bfloat16)
        pages["v_scale"] = jnp.zeros(sshape, jnp.bfloat16)
    return {"pages": pages,
            "block_table": jnp.full((batch, table_width), num_pages,
                                    jnp.int32)}


def select_lanes(cache, lanes: jnp.ndarray):
    """The paged ``cache`` as a call over the lanes ``lanes`` (W,) int32
    sees it: every ``block_table`` leaf (..., B, NP) narrowed to those
    rows, in that order.  An index past the last lane is a pad row: its
    table is all trash page (the pool's last page), so its writes land
    there alone.  Page pools carry no lane axis and pass through whole.
    A dense cache has no table to narrow and is refused."""
    if not isinstance(cache, dict):
        raise ValueError("a lane selection narrows block tables; a dense "
                         "cache has none")
    if "block_table" not in cache:
        return {k: select_lanes(c, lanes) for k, c in cache.items()}
    bt = cache["block_table"]
    lead = bt.ndim - 2                   # stacked layers lead both leaves
    trash = jax.tree_util.tree_leaves(cache["pages"])[0].shape[lead] - 1
    return dict(cache, block_table=jnp.take(bt, lanes, axis=lead,
                                            mode="fill", fill_value=trash))


def _page_coords(bt: jnp.ndarray, pos: jnp.ndarray, s: int, page_size: int):
    """(physical page, in-page row) for tokens written at pos..pos+s-1.

    Positions beyond the table clamp to its last entry — engine layouts
    size the table to cover every reachable position, so the clamp only
    guards compiler-visible out-of-range lanes (it can never alias a
    live page: clamped entries are trash-page defaults).
    """
    tpos = pos[:, None] + jnp.arange(s)[None, :]            # (B, s)
    idx = jnp.clip(tpos // page_size, 0, bt.shape[1] - 1)
    return jnp.take_along_axis(bt, idx, axis=1), tpos % page_size


def _paged_write(pages: jnp.ndarray, page: jnp.ndarray, row: jnp.ndarray,
                 u: jnp.ndarray) -> jnp.ndarray:
    """Scatter new tokens' K/V into their pages.

    ``pages`` (P, Hkv, ps, X); ``page``/``row`` (B, s); ``u``
    (B, Hkv, s, X).  Distinct lanes never share a (page, row) pair —
    the allocator hands each slot disjoint pages — except on the trash
    page, whose contents are never observed.
    """
    return pages.at[page, :, row].set(
        u.transpose(0, 2, 1, 3).astype(pages.dtype))


def _paged_gather(pages: jnp.ndarray, bt: jnp.ndarray) -> jnp.ndarray:
    """Materialize a slot-contiguous view (B, Hkv, NP*ps, X) of the pages.

    The jnp lowering (CPU/ref path): physically gathers the block
    table's pages in logical order.  The Pallas kernel
    (:func:`repro.kernels.flash_attention.paged_attention_pallas`)
    instead DMAs pages on demand and never materializes this view.
    """
    g = pages[bt]                                  # (B, NP, Hkv, ps, X)
    b, np_, h, ps, x = g.shape
    return g.transpose(0, 2, 1, 3, 4).reshape(b, h, np_ * ps, x)


def _quantize_kv(u: jnp.ndarray):
    """(B, H, s, Dh) → int8 payload + per-(token, head) scale."""
    amax = jnp.max(jnp.abs(u.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(u.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.bfloat16)


def _head_specs(b: int, hkv: int):
    """Logical (batch, head) split of a kernel's q/k/v operands: batch
    over the data axes and heads over the model axis where each divides.
    Query and kv heads split together (or not at all), so every shard
    keeps whole GQA groups."""
    return ("dp" if splits("dp", b) else None,
            "tp" if splits("tp", hkv) else None)


def _flash_kernel(q, k, v, *, causal: bool, softmax_scale=None):
    """Flash Pallas kernel, run per (batch, head) shard of the mesh."""
    from ..kernels.ops import attention as flash
    dp, tp = _head_specs(q.shape[0], k.shape[1])
    s = P(dp, tp)
    return kernel_map(
        lambda q, k, v: flash(q, k, v, causal=causal,
                              softmax_scale=softmax_scale, backend="pallas"),
        q, k, v, in_specs=(s, s, s), out_specs=s)


def _paged_kernel(q, k_pages, v_pages, bt, pos, ctx: QuantContext):
    """Block-table paged Pallas kernel, run per (batch, head) shard.

    The page pool is replicated (``dist.sharding.cache_specs``): each
    shard reads its own kv heads of it and the block-table rows of its
    own batch lanes.
    """
    from ..kernels.ops import paged_attention
    dp, tp = _head_specs(q.shape[0], k_pages.shape[1])
    return kernel_map(
        lambda q, kp, vp, bt, pos: paged_attention(
            q, kp, vp, bt, pos, kv_split=ctx.kv_split,
            pages_per_step=ctx.pages_per_step, backend="pallas"),
        q, k_pages, v_pages, bt, pos,
        in_specs=(P(dp, tp), P(None, tp), P(None, tp), P(dp), P(dp)),
        out_specs=P(dp, tp))


def _einsum_attention(q, k, v, *, causal: bool, ctx: QuantContext,
                      mask: Optional[jnp.ndarray] = None):
    """(B,Hq,Sq,D) × (B,Hkv,Skv,D) attention with GQA folding, f32 softmax.

    ``mask``: optional (B, Sq, Skv) boolean visibility mask; when given it
    replaces the static causal mask (cache/decode regime).
    """
    b, hq, sq, dh = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    dv = v.shape[-1]                     # MLA: dv != dh is legal
    g = hq // hkv
    cd = ctx.compute_dtype               # bf16 operands, f32 accumulation
    qg = q.reshape(b, hkv, g, sq, dh)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg.astype(cd), k.astype(cd),
                        preferred_element_type=jnp.float32) * (dh ** -0.5)
    if mask is not None:
        logits = jnp.where(mask[:, None, None], logits, -1e30)
    elif causal and sq > 1:
        qpos = jnp.arange(sq)[:, None] + (skv - sq)
        cmask = qpos >= jnp.arange(skv)[None, :]
        logits = jnp.where(cmask[None, None, None], logits, -1e30)
    w = softmax(logits, ctx, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", w.astype(cd), v.astype(cd),
                     preferred_element_type=jnp.float32)
    return out.reshape(b, hq, sq, dv).astype(q.dtype)


def _cache_mask(pos: jnp.ndarray, s: int, max_len: int,
                causal: bool) -> jnp.ndarray:
    """(B, s, max_len) visibility for queries written at pos..pos+s-1."""
    qpos = pos[:, None] + jnp.arange(s)[None, :]          # (B, s)
    kvpos = jnp.arange(max_len)[None, None, :]
    if causal:
        return kvpos <= qpos[:, :, None]
    return kvpos < (pos[:, None, None] + s)


#: above this many query positions, prefill/train attention switches from
#: the monolithic einsum (O(Sq·Skv) live logits) to the chunked scan.
CHUNK_THRESHOLD = 2048


def _chunked_attention(q, k, v, *, causal: bool, ctx: QuantContext,
                       chunk: int = 512):
    """Memory-bounded attention: ``lax.scan`` over query chunks.

    The GSPMD-friendly twin of the flash Pallas kernel (einsums partition
    over batch/heads; the scan keeps live logits at (B, H, chunk, Skv)).
    Each chunk is wrapped in ``jax.checkpoint`` so the backward pass
    recomputes one chunk's logits at a time instead of storing Sq·Skv —
    same memory shape as flash attention's recompute strategy.
    """
    b, hq, sq, dh = q.shape
    skv = k.shape[2]
    pad = (-sq) % chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nc = q.shape[2] // chunk
    qs = q.reshape(b, hq, nc, chunk, dh).transpose(2, 0, 1, 3, 4)
    q_off = skv - sq

    @jax.checkpoint
    def chunk_fn(q_c, idx):
        out = _einsum_attention_chunk(q_c, k, v, idx, chunk, q_off,
                                      causal, ctx)
        return out

    def body(_, x):
        q_c, idx = x
        return None, chunk_fn(q_c, idx)

    _, outs = jax.lax.scan(body, None, (qs, jnp.arange(nc)))
    out = outs.transpose(1, 2, 0, 3, 4).reshape(b, hq, nc * chunk, -1)
    return out[:, :, :sq]


def _einsum_attention_chunk(q_c, k, v, idx, chunk, q_off, causal, ctx):
    b, hq, bq, dh = q_c.shape
    hkv, skv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    g = hq // hkv
    cd = ctx.compute_dtype               # bf16 operands, f32 accumulation
    qg = q_c.reshape(b, hkv, g, bq, dh)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg.astype(cd), k.astype(cd),
                        preferred_element_type=jnp.float32) * (dh ** -0.5)
    qpos = q_off + idx * chunk + jnp.arange(bq)
    if causal:
        mask = qpos[:, None] >= jnp.arange(skv)[None, :]
        logits = jnp.where(mask[None, None, None], logits, -1e30)
    w = softmax(logits, ctx, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", w.astype(cd), v.astype(cd),
                     preferred_element_type=jnp.float32)
    return out.reshape(b, hq, bq, dv).astype(q_c.dtype)


def gqa_apply(p, x: jnp.ndarray, d: AttnDims, ctx: QuantContext = DEFAULT_CTX,
              *, positions: Optional[jnp.ndarray] = None,
              kv_input: Optional[jnp.ndarray] = None,
              cached_kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
              cache=None, cache_pos: Optional[jnp.ndarray] = None,
              path: str = "attn") -> Tuple[jnp.ndarray, Optional[dict]]:
    """Self- or cross-attention over ``x`` (B, S, D_model).

    ``kv_input``: encoder stream for cross-attention (keys/values source).
    ``cached_kv``: precomputed cross K/V (B, Hkv, Skv, Dh) — decode path
    reuses the prefill-time projections instead of recomputing them.
    ``cache``/``cache_pos``: decode regime — update the cache at
    ``cache_pos`` and attend over the prefix.  Returns (y, new_cache).
    """
    b, s, _ = x.shape
    if cached_kv is not None:
        q = linear(p["wq"], x, ctx, path=f"{path}/wq")
        q = q.reshape(b, s, d.n_heads, d.head_dim).transpose(0, 2, 1, 3)
        k, v = cached_kv
        y = _einsum_attention(q, k, v, causal=False, ctx=ctx)
        y = y.transpose(0, 2, 1, 3).reshape(b, s, d.n_heads * d.head_dim)
        return linear(p["wo"], y, ctx, path=f"{path}/wo"), None

    kv_src = kv_input if kv_input is not None else x
    skv = kv_src.shape[1]

    q = linear(p["wq"], x, ctx, path=f"{path}/wq")
    q = q.reshape(b, s, d.n_heads, d.head_dim)
    k = linear(p["wk"], kv_src, ctx, path=f"{path}/wk")
    k = k.reshape(b, skv, d.n_kv_heads, d.head_dim)
    v = linear(p["wv"], kv_src, ctx, path=f"{path}/wv")
    v = v.reshape(b, skv, d.n_kv_heads, d.head_dim)

    if d.use_rope and kv_input is None:
        if positions is None:
            positions = jnp.arange(s)[None, :]
            if cache_pos is not None:
                positions = positions + cache_pos[:, None]
        q = apply_rope(q.transpose(0, 2, 1, 3), positions[:, None],
                       theta=d.rope_theta, fraction=d.rope_fraction
                       ).transpose(0, 2, 1, 3)
        k = apply_rope(k.transpose(0, 2, 1, 3), positions[:, None],
                       theta=d.rope_theta, fraction=d.rope_fraction
                       ).transpose(0, 2, 1, 3)

    q = _constrain_heads(q.transpose(0, 2, 1, 3), "q")  # (B, Hq, S, Dh)
    k = _constrain_heads(k.transpose(0, 2, 1, 3), "kv")
    v = _constrain_heads(v.transpose(0, 2, 1, 3), "kv")

    new_cache = None
    if cache is not None and "pages" in cache:
        # paged decode / chunked prefill: scatter K/V into the slot's
        # pages (write-before-attend), then attend through the block
        # table.  No per-slot margin rows exist — out-of-allocation
        # writes land on the trash page via the table defaults.
        pages, bt = cache["pages"], cache["block_table"]
        zeros = jnp.zeros((b,), jnp.int32) if cache_pos is None else cache_pos
        page, row = _page_coords(bt, zeros, s, pages["k"].shape[2])
        cd = ctx.compute_dtype
        if paged_kernel_reads(ctx, pages, causal=d.causal):
            # TPU path: block-table-indexed flash kernel — pages are
            # DMA'd on demand, the contiguous view never exists.
            # ctx.kv_split / ctx.pages_per_step ride through here:
            # the kernel partitions the block table into parallel
            # flash-decoding lanes (None = cost-model auto).
            # ``force_paged_kernel`` drives the same kernel in
            # interpret mode off-TPU (CPU conformance suites).
            pages = {"k": _paged_write(pages["k"], page, row, k),
                     "v": _paged_write(pages["v"], page, row, v)}
            y = _paged_kernel(q, pages["k"], pages["v"], bt, zeros, ctx)
        elif "k_scale" in pages:        # int8 pages + scale pages
            kq, ks = _quantize_kv(k)
            vq, vs = _quantize_kv(v)
            pages = {"k": _paged_write(pages["k"], page, row, kq),
                     "v": _paged_write(pages["v"], page, row, vq),
                     "k_scale": _paged_write(pages["k_scale"], page, row, ks),
                     "v_scale": _paged_write(pages["v_scale"], page, row, vs)}
            ck = (_paged_gather(pages["k"], bt).astype(cd)
                  * _paged_gather(pages["k_scale"], bt).astype(cd))
            cv = (_paged_gather(pages["v"], bt).astype(cd)
                  * _paged_gather(pages["v_scale"], bt).astype(cd))
            mask = _cache_mask(zeros, s, ck.shape[2], d.causal)
            y = _einsum_attention(q, ck, cv, causal=False, ctx=ctx, mask=mask)
        else:
            pages = {"k": _paged_write(pages["k"], page, row, k),
                     "v": _paged_write(pages["v"], page, row, v)}
            ck = _paged_gather(pages["k"], bt)
            cv = _paged_gather(pages["v"], bt)
            mask = _cache_mask(zeros, s, ck.shape[2], d.causal)
            y = _einsum_attention(q, ck, cv, causal=False, ctx=ctx, mask=mask)
        new_cache = {"pages": pages, "block_table": bt}
    elif cache is not None:
        # decode (s == 1) or chunked prefill: write K/V at cache_pos
        zeros = jnp.zeros((b,), jnp.int32) if cache_pos is None else cache_pos
        def write(c, u):
            return jax.vmap(lambda cc, uu, i: jax.lax.dynamic_update_slice(
                cc, uu.astype(cc.dtype), (0, i, 0)))(c, u, zeros)

        quantized = "k_scale" in cache
        if quantized:  # int8 cache: quantize the new tokens' K/V
            kq, ks = _quantize_kv(k)
            vq, vs = _quantize_kv(v)
            new_cache = {"k": write(cache["k"], kq),
                         "v": write(cache["v"], vq),
                         "k_scale": write(cache["k_scale"], ks),
                         "v_scale": write(cache["v_scale"], vs)}
            ck = (new_cache["k"].astype(ctx.compute_dtype)
                  * new_cache["k_scale"].astype(ctx.compute_dtype))
            cv = (new_cache["v"].astype(ctx.compute_dtype)
                  * new_cache["v_scale"].astype(ctx.compute_dtype))
        else:
            ck = write(cache["k"], k)
            cv = write(cache["v"], v)
            new_cache = {"k": ck, "v": cv}
        from ..dist.options import flags
        from ..dist.constrain import current_mesh
        mesh = current_mesh()
        if (flags().seq_kv and mesh is not None
                and "model" in mesh.axis_names
                and d.n_kv_heads % mesh.shape["model"] != 0):
            # §Perf H3: sequence-sharded cache; queries replicate (tiny)
            ck = constrain(ck, "dp", None, "tp", None)
            cv = constrain(cv, "dp", None, "tp", None)
            q = constrain(q, "dp", None, None, None)
        mask = _cache_mask(zeros, s, ck.shape[2], d.causal)
        y = _einsum_attention(q, ck, cv, causal=False, ctx=ctx, mask=mask)
    else:
        causal = d.causal and kv_input is None
        if kernel_path(ctx):
            # TPU execution path: the flash Pallas kernel, run inside
            # shard_map over the batch/head shards (_flash_kernel)
            y = _flash_kernel(q, k, v, causal=causal)
        elif max(s, skv) > CHUNK_THRESHOLD:
            y = _chunked_attention(q, k, v, causal=causal, ctx=ctx)
        else:
            y = _einsum_attention(q, k, v, causal=causal, ctx=ctx)

    y = y.transpose(0, 2, 1, 3).reshape(b, s, d.n_heads * d.head_dim)
    return linear(p["wo"], y, ctx, path=f"{path}/wo"), new_cache


# ===========================================================================
# MLA (deepseek-v2 multi-head latent attention)
# ===========================================================================
@dataclasses.dataclass(frozen=True)
class MLADims:
    d_model: int
    n_heads: int
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0

    @property
    def qk_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim


def mla_init(rng, d: MLADims, *, dtype=jnp.float32):
    ks = jax.random.split(rng, 5)
    h = d.n_heads
    return {
        "wq_a": linear_init(ks[0], d.d_model, d.q_lora_rank, dtype=dtype),
        "q_norm": rmsnorm_init(d.q_lora_rank, dtype),
        "wq_b": linear_init(ks[1], d.q_lora_rank, h * d.qk_dim, dtype=dtype),
        "wkv_a": linear_init(ks[2], d.d_model,
                             d.kv_lora_rank + d.qk_rope_dim, dtype=dtype),
        "kv_norm": rmsnorm_init(d.kv_lora_rank, dtype),
        "wkv_b": linear_init(ks[3], d.kv_lora_rank,
                             h * (d.qk_nope_dim + d.v_head_dim), dtype=dtype),
        "wo": linear_init(ks[4], h * d.v_head_dim, d.d_model, dtype=dtype),
    }


def mla_cache_spec(d: MLADims, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Latent cache: compressed KV (B, S, kv_lora) + shared RoPE key.

    int8 requests fall back to bf16: the MLA latent *is* the cache
    compression (576 B/token vs GQA's KB/token), and the normed latent is
    precision-sensitive (§Arch-applicability).
    """
    if dtype == jnp.int8:
        dtype = jnp.bfloat16
    return {"ckv": jnp.zeros((batch, max_len, d.kv_lora_rank), dtype),
            "krope": jnp.zeros((batch, max_len, d.qk_rope_dim), dtype)}


def mla_paged_cache_spec(d: MLADims, batch: int, num_pages: int,
                         page_size: int, table_width: int,
                         dtype=jnp.bfloat16):
    """Paged MLA latent cache: (P+1, page_size, kv_lora / rope) pages.

    Same pool/table/trash-page scheme as :func:`gqa_paged_cache_spec`;
    the latent has no head axis, so a page row is one token's compressed
    KV.  int8 falls back to bf16 exactly as the dense spec does (the
    latent *is* the compression)."""
    if dtype == jnp.int8:
        dtype = jnp.bfloat16
    return {"pages": {
                "ckv": jnp.zeros((num_pages + 1, page_size,
                                  d.kv_lora_rank), dtype),
                "krope": jnp.zeros((num_pages + 1, page_size,
                                    d.qk_rope_dim), dtype)},
            "block_table": jnp.full((batch, table_width), num_pages,
                                    jnp.int32)}


def _mla_qkv(p, x, d: MLADims, ctx, positions, path):
    b, s, _ = x.shape
    h = d.n_heads
    q = linear(p["wq_b"], rmsnorm(p["q_norm"],
                                  linear(p["wq_a"], x, ctx, path=f"{path}/wq_a")),
               ctx, path=f"{path}/wq_b").reshape(b, s, h, d.qk_dim)
    q_nope, q_rope = q[..., :d.qk_nope_dim], q[..., d.qk_nope_dim:]
    q_rope = apply_rope(q_rope.transpose(0, 2, 1, 3), positions[:, None],
                        theta=d.rope_theta).transpose(0, 2, 1, 3)

    kv_a = linear(p["wkv_a"], x, ctx, path=f"{path}/wkv_a")
    ckv = rmsnorm(p["kv_norm"], kv_a[..., :d.kv_lora_rank])
    krope = apply_rope(kv_a[..., None, d.kv_lora_rank:].transpose(0, 2, 1, 3),
                       positions[:, None], theta=d.rope_theta
                       ).transpose(0, 2, 1, 3)[:, :, 0]   # (B, S, rope_dim)
    return q_nope, q_rope, ckv, krope


def mla_apply(p, x: jnp.ndarray, d: MLADims, ctx: QuantContext = DEFAULT_CTX,
              *, positions: Optional[jnp.ndarray] = None,
              cache=None, cache_pos: Optional[jnp.ndarray] = None,
              path: str = "attn") -> Tuple[jnp.ndarray, Optional[dict]]:
    b, s, _ = x.shape
    h = d.n_heads
    if positions is None:
        positions = jnp.arange(s)[None, :] + (
            cache_pos[:, None] if cache_pos is not None else 0)
    q_nope, q_rope, ckv, krope = _mla_qkv(p, x, d, ctx, positions, path)
    # wkv_b is consumed raw (reshaped into absorbed-form einsums, not via
    # linear()); a pre-quantized QTensor from ptq_params is dequantized
    # once here — still zero calibrate/round work per forward.
    w_b = p["wkv_b"]["w"]
    if isinstance(w_b, QTensor):
        w_b = w_b.dequantize(ctx.compute_dtype)
    wkv_b = w_b.reshape(d.kv_lora_rank, h,
                        d.qk_nope_dim + d.v_head_dim)
    w_uk = wkv_b[..., :d.qk_nope_dim]       # (lora, H, qk_nope)
    w_uv = wkv_b[..., d.qk_nope_dim:]       # (lora, H, v_dim)

    if cache is None:
        # ---- prefill / training: naive form, per-head K/V materialized
        k_nope = jnp.einsum("bsl,lhd->bshd", ckv.astype(jnp.float32),
                            w_uk.astype(jnp.float32)).astype(x.dtype)
        v = jnp.einsum("bsl,lhd->bshd", ckv.astype(jnp.float32),
                       w_uv.astype(jnp.float32)).astype(x.dtype)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(krope[:, :, None],
                                      (b, s, h, d.qk_rope_dim))], axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        qT, kT, vT = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        qT = _constrain_heads(qT, "q")
        kT = _constrain_heads(kT, "kv")
        vT = _constrain_heads(vT, "kv")
        if kernel_path(ctx):
            # flash kernel wants dv == dqk: zero-pad V and slice after
            pad = d.qk_dim - d.v_head_dim
            vp = jnp.pad(vT, ((0, 0), (0, 0), (0, 0), (0, pad)))
            y = _flash_kernel(qT, kT, vp, causal=True,
                              softmax_scale=d.qk_dim ** -0.5)
            y = y[..., :d.v_head_dim]
        elif s > CHUNK_THRESHOLD:
            y = _chunked_attention(qT, kT, vT, causal=True, ctx=ctx)
        else:
            y = _einsum_attention(qT, kT, vT, causal=True, ctx=ctx)
        y = y.transpose(0, 2, 1, 3).reshape(b, s, h * d.v_head_dim)
        return linear(p["wo"], y, ctx, path=f"{path}/wo"), None

    # ---- decode: absorbed form against the latent cache -------------------
    zeros = jnp.zeros((b,), jnp.int32) if cache_pos is None else cache_pos
    if "pages" in cache:
        # paged latent: scatter this chunk's rows into the slot's pages,
        # score against the gathered logical view (write-before-attend);
        # the block-table kernel does not read latent pages
        pages, bt = cache["pages"], cache["block_table"]
        assert not paged_kernel_reads(ctx, pages)
        page, row = _page_coords(bt, zeros, s, pages["ckv"].shape[1])
        pages = {"ckv": pages["ckv"].at[page, row].set(
                     ckv.astype(pages["ckv"].dtype)),
                 "krope": pages["krope"].at[page, row].set(
                     krope.astype(pages["krope"].dtype))}
        cckv = pages["ckv"][bt].reshape(b, -1, d.kv_lora_rank)
        ckrope = pages["krope"][bt].reshape(b, -1, d.qk_rope_dim)
        new_cache = {"pages": pages, "block_table": bt}
    else:
        cckv = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
            c, u.astype(c.dtype), (i, 0)))(cache["ckv"], ckv, zeros)
        ckrope = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
            c, u.astype(c.dtype), (i, 0)))(cache["krope"], krope, zeros)
        new_cache = {"ckv": cckv, "krope": ckrope}

    # absorb W_uk into the query: q_abs (B, s, H, lora)
    cd = ctx.compute_dtype
    q_abs = jnp.einsum("bshd,lhd->bshl", q_nope.astype(cd),
                       w_uk.astype(cd),
                       preferred_element_type=jnp.float32)
    logits = (jnp.einsum("bshl,btl->bhst", q_abs.astype(cd),
                         cckv.astype(cd),
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bshr,btr->bhst", q_rope.astype(cd),
                           ckrope.astype(cd),
                           preferred_element_type=jnp.float32)
              ) * (d.qk_dim ** -0.5)
    mask = _cache_mask(zeros, s, cckv.shape[1], True)      # (B, s, T)
    logits = jnp.where(mask[:, None], logits, -1e30)       # (B, H, s, T)
    w = softmax(logits, ctx, axis=-1)
    lat = jnp.einsum("bhst,btl->bshl", w.astype(cd), cckv.astype(cd),
                     preferred_element_type=jnp.float32)
    y = jnp.einsum("bshl,lhd->bshd", lat.astype(cd), w_uv.astype(cd),
                   preferred_element_type=jnp.float32)
    y = y.reshape(b, s, h * d.v_head_dim).astype(x.dtype)
    return linear(p["wo"], y, ctx, path=f"{path}/wo"), new_cache
