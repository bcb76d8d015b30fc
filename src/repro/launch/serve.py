"""Serving entrypoint: batched chunked prefill + device-resident decode
with continuous batching.

The paper's deployment scenario — a *quantized inference accelerator* —
realized at framework level, as a fused quantized dense pipeline:

* **Weights are quantized once, at engine construction** — ``--quant
  int8`` runs :func:`repro.core.quantize.ptq_params` over the parameter
  tree before it is device_put, so every serving step consumes
  :class:`~repro.core.qtypes.QTensor` weights directly.  Zero
  ``calibrate_scale``/``round`` ops on weights per token (the hls4ml
  model-conversion contract; only activations are quantized per step).
* **Fused kernel epilogue** — with ``--lut``, linear + bias + LUT
  activation execute as one ``qmatmul`` Pallas launch (see
  :mod:`repro.kernels.qmatmul`), one HBM pass instead of three.
* **Batched chunked prefill** — prompt ingestion runs through
  ``build_prefill_step``: all fresh slots advance together, one
  full-batch model call per ``prefill_chunk`` tokens, i.e.
  O(prompt_len / chunk) steps total instead of O(prompt_len) decode
  steps *per slot*.  Each call argmaxes the slots' last prompt rows on
  the device and hands back only their first tokens.  Slots
  mid-generation are untouched: their chunk writes land in a reserved
  cache margin (see ``Engine``) and their positions do not advance.
* **Device-resident decode loop** — generation runs through
  ``build_decode_loop``: ``step_many(n)`` executes n decode steps inside
  ONE ``lax.scan`` jit call — model step, per-slot sampling (greedy /
  temperature / top-k, see :mod:`repro.kernels.sampling`), per-slot
  position advance, and EOS/length stopping all stay on device.  The
  host syncs once per n-token block (to retire finished slots and refill
  them) instead of once per token: 1/n jit dispatches and host round
  trips per generated token vs ``step()``.
* **Continuous batching** — a finished sequence's slot is refilled by
  the next queued request without draining the batch; freed slots are
  refilled *together* so their prompts share prefill batches too.
* **Paged KV cache** (``--paged``) — the de-specialization step applied
  to serving memory: instead of every slot owning a dense ``max_len``
  KV allocation, K/V rows live in a shared pool of fixed-size pages
  (``--page-size`` tokens each, ``--num-pages`` total) and each request
  holds exactly the pages its token budget needs, addressed through a
  per-slot block table.  Admission is metered by *used* tokens, not
  worst-case ones: ``submit()`` queues a request, and ``step_many``
  admits waiting requests the moment a freed lane plus freed pages
  cover them — ``finish()`` returns pages to the free list in O(pages)
  (a block-table edit) instead of zeroing ``max_len`` cache rows.
  Dense mode still wins at tiny batches (no gather/table indirection,
  one request never fragments); paged mode wins the moment mixed-length
  traffic leaves dense slots half empty.
* **Split-KV flash decoding** (``--kv-split`` / ``--pages-per-step``) —
  the reuse-factor knob applied to the last serial hot path: on the
  kernel path each slot's page chain is cut into ``kv_split`` parallel
  online-softmax partitions (merged by a log-sum-exp combine) and each
  grid step DMAs a ``pages_per_step``-page tile, double-buffered —
  long-context decode latency stops scaling with the page chain.
  ``auto`` (default) picks both from a cached rule4ml-style cost model
  (:func:`repro.kernels.flash_attention.choose_kv_split`); the resolved
  pair is reported in ``Engine.stats()``.  ``--kv-split 1
  --pages-per-step 1`` is byte-identical to the pre-split kernel.
* **Speculative decoding** (``--spec``) — the draft→verify pipeline on
  top of the de-specialized attention path: a drafter proposes
  ``--spec-k`` tokens per live slot (prompt-lookup self-speculation by
  default; ``--spec-draft <arch>`` drafts with a second model) and the
  target model verifies ALL of them with one forward pass —
  verification is just a k+1-token chunked-prefill call, dense einsum
  or ``paged_attention``, the same op either way.  Acceptance runs
  device-resident (:func:`repro.kernels.ops.verify_tokens` inside the
  fused scan): greedy streams are byte-identical to the
  non-speculative engine, sampled streams keep their exact
  temperature/top-k distribution via point-mass rejection sampling.
  Rewind on rejection is a scalar ``pos`` edit for KV families (pages
  were allocated for the full budget at admission — allocator and
  block tables untouched); recurrent families checkpoint-and-restore
  their state per block position (see ``models.api.spec_state_fn``).
  The speculation depth ``k`` is the serving-side reuse factor:
  deeper speculation = fewer target passes on predictable streams,
  more wasted verify positions on incompressible ones.

Usage (CPU-scale)::

    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --smoke \
        --requests 16 --batch 4 --prompt-len 32 --gen-len 16 \
        --quant int8 --decode-block 8 --paged --page-size 16 --spec
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import os
import time
from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..configs import get_config
from ..data.pipeline import SyntheticLM
from ..dist.constrain import use_mesh
from ..dist.sharding import cache_specs, named, param_specs
from ..ft import StragglerMonitor
from ..models.api import (copy_pages_fn, get_family, init_paged_cache_fn,
                          invalidate_fn, merge_slot_fn, set_block_table,
                          spec_restore_fn, spec_state_fn,
                          supports_chunked_prefill)
from ..nn.context import QuantContext, lowerings
from ..train.step import (build_decode_loop, build_prefill_step,
                          build_serve_step, build_spec_decode_loop)
from .lifecycle import (PriorityClass, RequestStatus, coerce_priority,
                        normalize_class_quotas, normalize_slo_targets,
                        request_row, validate_request)
from .compile_cache import configure_compile_cache
from .lifecycle import now as _now
from .mesh import make_local_mesh
from .paging import PageAllocator
from .prefix import PREFIX_OWNER, ROOT, PrefixIndex
from .train import build_ctx

#: the counters whose change over one admission sweep a traced
#: ``engine.admit`` span carries as its metadata, so a reader of the
#: trace can sum them over any window of it
ADMIT_SPAN_COUNTERS = ("admitted", "queue_wait_s", "prefill_rows",
                       "prefill_tokens")


def _snap(a: np.ndarray) -> jnp.ndarray:
    """Host→device snapshot of engine-mutable numpy state.

    The engine mutates ``pos``/``tokens``/``live`` in place right after
    dispatching a step.  Handing the numpy buffer itself to jax races
    the *asynchronous* host copy — ``jnp.array``'s copy=True is not a
    synchronous defensive copy on the CPU backend, so under load the
    transfer can read the buffer AFTER the host mutated it (observed:
    the per-token prefill loop nondeterministically produced garbage
    first tokens).  A fresh ``.copy()`` that nothing ever mutates is
    safe regardless of whether jax aliases or copies it.
    """
    return jnp.asarray(a.copy())


class DeviceFault(RuntimeError):
    """The fused block's fault lane flagged slots (non-finite logits on
    device — poisoned cache, kernel NaN).  Raised inside ``step_many``
    so the recovery loop can restore-and-replay; without a recovery
    path the flagged slots are failed with their valid prefix."""

    def __init__(self, slots):
        slots = tuple(int(s) for s in slots)
        super().__init__(f"device fault lane flagged slots {list(slots)}")
        self.slots = slots


def _copy_record(r: dict) -> dict:
    """Queue-record copy for snapshots: the mutable ``outputs`` list is
    deep-copied; spilled page payloads / recurrent lanes are immutable
    after the spill and ride by reference."""
    r2 = dict(r)
    if r2.get("outputs"):
        r2["outputs"] = list(r2["outputs"])
    return r2


class Engine:
    """Slot-based continuous batching engine over prefill/decode steps.

    Decoding is device-resident: ``step_many(n)`` runs n fused decode
    steps (one jit call, one host sync); ``step()`` is the n=1 special
    case, kept as the per-token baseline.  Per-slot sampling parameters
    (``temperature``/``top_k``), generation budgets (``stop_pos``) and
    the EOS id live in the engine and are threaded through the loop, so
    greedy and sampled requests share one batch.

    Cache layout note: the KV cache is allocated with ``prefill_chunk``
    margin rows beyond ``max_len``.  During a mid-flight refill the
    chunked prefill runs full-batch, so slots that are still generating
    receive (ignored) writes at their current position; the margin
    guarantees those writes can never clamp back into valid rows, and
    the per-slot visibility mask (`kvpos <= qpos`) keeps them invisible
    until decode overwrites them.
    """

    def __init__(self, cfg, ctx, params, mesh, *, batch: int, max_len: int,
                 kv_bits=None, prefill_chunk: int = 16, eos_id: int = -1,
                 seed: int = 0, paged: bool = False, page_size: int = 16,
                 num_pages: Optional[int] = None, kv_split="auto",
                 pages_per_step="auto", prefix_cache: bool = False,
                 autotune: str = "off", spec: bool = False,
                 spec_k: int = 4, spec_draft=None, spec_ngram: int = 2,
                 drafter_fn=None, preempt: bool = False,
                 preempt_after: int = 2, shed_threshold=None,
                 slo_targets=None, class_quotas=None, fault_injector=None,
                 recover=None, max_replays: int = 8, straggler=None,
                 clock=None, durable_dir=None, snapshot_every: int = 8):
        self.cfg, self.ctx, self.mesh = cfg, ctx, mesh
        self.batch, self.max_len = batch, max_len
        self.prefill_chunk = max(1, prefill_chunk)
        # chunked prefill needs per-call cache continuation; only the
        # attention-cache families support that (SSM state is rebuilt
        # from the tokens of one call).
        self.chunked = supports_chunked_prefill(cfg)
        fam = get_family(cfg)
        self.params = params
        cache_dtype = jnp.int8 if kv_bits == 8 else jnp.float32
        margin = self.prefill_chunk if self.chunked else 0
        # speculative decoding: the verification block writes k+1 KV
        # rows starting at a (possibly held, up to max_len) position, so
        # the margin must absorb spec_k + 1 rows beyond the cache bound
        # exactly as it absorbs chunked-prefill overshoot
        self.spec, self.spec_k = bool(spec), max(1, int(spec_k))
        self.spec_ngram = max(1, int(spec_ngram))
        # -- unified autotuner (rule4ml for the engine) ----------------
        # "off" is the legacy path bit for bit: explicit kwarg > ctx >
        # the analytic cost model, no decode-block resolution, no
        # online spec_k adaptation.  "analytic"/"fitted" resolve the
        # whole knob vector through launch/autotune.py — same grid,
        # hand-set vs least-squares-fitted weights — and adapt spec_k
        # from measured acceptance.  Knobs the caller pins explicitly
        # always win over the resolver.
        self.autotune = str(autotune)
        if self.autotune not in ("off", "analytic", "fitted"):
            raise ValueError(
                f"autotune={autotune!r}: expected 'off' (legacy "
                f"defaults), 'analytic' (resolver on hand-set "
                f"constants) or 'fitted' (resolver on measured fit)")
        self._autotune_est = None
        self._spec_adapter = None
        self._spec_k_init = self.spec_k
        self._last_spec_obs = (0, 0)
        self.decode_block: Optional[int] = None
        if self.autotune != "off":
            from .autotune import (SpecKAdapter, WorkloadShape,
                                   load_estimator, resolve)
            self._autotune_est = load_estimator(self.autotune)
            self._autotune_resolve = resolve
            self._autotune_shape_cls = WorkloadShape
            if self.spec:
                # adapt within [1, construction spec_k]: the KV margin
                # and drafting history are sized for the initial k, so
                # it is the cap — pass a generous --spec-k and let the
                # adapter find the efficient depth under it
                self._spec_adapter = SpecKAdapter(k_init=self.spec_k,
                                                  k_max=self.spec_k)
        self.drafter_fn = drafter_fn            # test hook (custom drafts)
        if not self.spec and (spec_draft is not None
                              or drafter_fn is not None):
            raise ValueError(
                "spec_draft/drafter_fn were given but spec=False — a "
                "drafter without speculation would silently never run; "
                "pass spec=True")
        if self.spec:
            margin = max(margin, self.spec_k + 2)
        self.paged = bool(paged)
        if class_quotas and not paged:
            raise ValueError(
                "class_quotas need the paged cache: quotas partition the "
                "page pool, and dense slots have no pool to partition")
        if self.paged:
            ps = max(1, int(page_size))
            if num_pages is None:
                # dense-equivalent HBM budget by default; the win comes
                # from passing a smaller pool (or a bigger batch)
                num_pages = -(-(batch * max_len) // ps)
            self.allocator = PageAllocator(num_pages, ps,
                                           class_quotas=class_quotas)
            self._trash = num_pages          # reserved garbage page id
            # table width covers every reachable write position: decode
            # holds a dead lane at pos <= max_len, chunked prefill's
            # margin writes reach max_len + margin - 1
            width = -(-(max_len + max(margin, 1)) // ps)
            self.block_tables = np.full((batch, width), self._trash,
                                        np.int32)
            self._slot_pages: Dict[int, List[int]] = {}
            #: host table edited but not yet written into the cache —
            #: finish() defers the device write so a retire sweep costs
            #: ONE table upload, flushed by the next consumer
            self._bt_dirty = False
            self.cache = init_paged_cache_fn(cfg, batch, num_pages, ps,
                                             width, cache_dtype)
        else:
            self.cache = fam.init_cache(cfg, batch, max_len + margin,
                                        cache_dtype)
        # -- prefix caching (the reuse-factor move on cache CONTENTS):
        # committed, page-aligned prompt pages are published to a hash
        # index and mapped read-only into later requests that share the
        # prefix — admission of a hit allocates only the suffix's pages
        # and prefills only the suffix's tokens.  Needs the page pool
        # (sharing is block-table indirection) and chunkable prefill
        # (a recurrent family's state is sequential: nothing can be
        # skipped), so the flag is accepted everywhere but inert for
        # ssm/hybrid — one engine API, no per-family forks.
        self.prefix_cache = False
        if prefix_cache:
            if not self.paged:
                raise ValueError(
                    "prefix_cache=True needs the paged cache: prefix "
                    "reuse IS page sharing (dense lanes have no pages "
                    "to share)")
            self.prefix_cache = self.chunked
        if self.prefix_cache:
            self.prefix_index = PrefixIndex(self.allocator.page_size)
            #: slot -> index pages mapped read-only into its table
            #: (the slot holds one refcount on each; table layout is
            #: shared entries first, then the slot's private pages)
            self._slot_shared: Dict[int, List[int]] = {}
            #: slot -> (chunks published/matched so far, chain key of
            #: the last one) — where _publish_committed resumes
            self._pub: Dict[int, tuple] = {}
            # donated like _invalidate: a CoW copy edits pages in place,
            # it must not materialize a second full pool
            self._copy_page = jax.jit(copy_pages_fn, donate_argnums=(0,))
        # split-KV reuse-factor knob: resolve once per cache geometry
        # (explicit engine kwarg > ctx setting > cached cost model) and
        # thread through the context so the fused decode loop AND the
        # speculative verify pass hand the same split to the kernel.
        # On non-TPU hosts the paged model path is gather+einsum, so
        # the knob is telemetry-only there — but it is resolved
        # identically so `Engine.stats()` reports what a TPU run of
        # this exact geometry would execute.
        self.kv_split = self.pages_per_step = None
        if self.paged:
            from ..kernels.flash_attention import _resolve_knobs
            width = self.block_tables.shape[1]
            req_t = (int(pages_per_step)
                     if pages_per_step not in (None, "auto")
                     else ctx.pages_per_step)
            req_s = (int(kv_split) if kv_split not in (None, "auto")
                     else ctx.kv_split)
            hkv = getattr(cfg, "n_kv_heads", 0) or getattr(
                cfg, "n_heads", 1)
            if self.autotune != "off":
                # construction-time resolution of the whole knob
                # vector: estimator argmin over the (tile, split) grid
                # fills whatever the caller left on auto; explicit
                # kwargs/ctx pins pass through untouched
                kv = self._autotune_resolve(
                    self._autotune_shape_cls(
                        pages=width, page_size=ps, hkv=max(1, hkv),
                        batch=batch, gen_len=max_len, spec=self.spec),
                    self._autotune_est)
                req_t = kv.pages_per_step if req_t is None else req_t
                req_s = kv.kv_split if req_s is None else req_s
                self.decode_block = kv.decode_block
            t, split = _resolve_knobs(width, ps, max(1, hkv), batch,
                                      req_s, req_t)
            self.kv_split, self.pages_per_step = split, t
            ctx = dataclasses.replace(ctx, kv_split=split,
                                      pages_per_step=t)
            self.ctx = ctx
        if self.autotune != "off" and not self.paged:
            # dense cache: no kv knobs, but block size and spec depth
            # are still the resolver's to pick
            kv = self._autotune_resolve(
                self._autotune_shape_cls(pages=0, page_size=1, hkv=1,
                                         batch=batch, gen_len=max_len,
                                         spec=self.spec),
                self._autotune_est)
            self.decode_block = kv.decode_block
        c_sh = named(cache_specs(self.cache, mesh), mesh)
        self.cache = jax.device_put(self.cache, c_sh)
        #: cache sharding, kept for snapshot restore (the fused loops
        #: donate their cache argument, so restore re-device_puts)
        self._cache_sh = c_sh
        self.decode = jax.jit(build_serve_step(cfg, ctx))
        self.prefill = jax.jit(build_prefill_step(cfg, ctx,
                                                  first_token=True))
        #: per-block-size cache of jitted fused decode loops
        self._loops: Dict[int, callable] = {}
        #: per-block-size cache of jitted speculative draft→verify loops
        self._spec_loops: Dict[int, callable] = {}
        # -- speculative drafting state --------------------------------
        #: committed-token history per slot (prompt + accepted
        #: generations at their absolute positions) — the prompt-lookup
        #: drafter's corpus; threaded through the spec loop carry
        self.hist = np.zeros((batch, max_len + self.spec_k + 2), np.int32)
        self.draft = None
        if self.spec and spec_draft is not None:
            d_cfg, d_params, d_ctx = spec_draft
            if d_cfg.vocab != cfg.vocab:
                raise ValueError(
                    f"draft model vocab {d_cfg.vocab} != target vocab "
                    f"{cfg.vocab}; drafts would be meaningless")
            self.draft = (d_cfg, d_params, d_ctx or ctx)
            self.draft_chunked = supports_chunked_prefill(d_cfg)
            d_margin = max(self.prefill_chunk if self.draft_chunked else 0,
                           self.spec_k + 2)
            # the drafter's cache is always dense: it holds one model's
            # worth of rows and is rolled back by pos/checkpoints, never
            # paged (paging meters the TARGET's admission, not drafts)
            self.draft_cache = get_family(d_cfg).init_cache(
                d_cfg, batch, max_len + d_margin, jnp.float32)
            self._draft_decode = jax.jit(build_serve_step(d_cfg,
                                                          self.draft[2]))
            self._draft_prefill = jax.jit(build_prefill_step(
                d_cfg, self.draft[2]))
            self._draft_invalidate = jax.jit(
                lambda cache, slot: invalidate_fn(cache, slot, d_cfg),
                donate_argnums=(0,))
            self._draft_merge = jax.jit(
                lambda new, old, slot: merge_slot_fn(new, old, slot, d_cfg),
                donate_argnums=(1,))
        # donated so XLA updates the cache in place — invalidating a slot
        # on finish() must not copy the whole KV cache per request
        self._invalidate = jax.jit(
            lambda cache, slot: invalidate_fn(cache, slot, cfg),
            donate_argnums=(0,))
        # old cache donated: the merge result is old with one lane
        # replaced, so XLA updates it in place
        self._merge = jax.jit(
            lambda new, old, slot: merge_slot_fn(new, old, slot, cfg),
            donate_argnums=(1,))
        self.pos = np.zeros((batch,), np.int32)
        self.live = np.zeros((batch,), bool)
        self.tokens = np.zeros((batch, 1), np.int32)
        #: lanes known zeroed since the last decode touched them — a
        #: fresh engine starts all-clean, finish() re-cleans its slot,
        #: any decode block dirties every lane (decode advances dead
        #: lanes' recurrent state too); admission only invalidates
        #: lanes that are actually dirty (deferred refills), not ones
        #: finish() just zeroed.
        self._clean = np.ones((batch,), bool)
        #: per-slot sampling params; temperature <= 0 = greedy,
        #: top_k <= 0 = unrestricted (see repro.kernels.sampling)
        self.temperature = np.zeros((batch,), np.float32)
        self.top_k = np.zeros((batch,), np.int32)
        #: per-slot position bound: live drops when pos reaches it
        self.stop_pos = np.full((batch,), max_len, np.int32)
        self.eos_id = int(eos_id)
        self._key = jax.random.PRNGKey(seed)
        self._gen_step = 0          # global decode-step counter (PRNG)
        self.outputs: List[Optional[list]] = [None] * batch
        self.done: List[list] = []
        #: FIFO admission queue (see submit/try_admit): requests wait
        #: here until a lane AND (paged) enough free pages exist
        self.waiting: deque = deque()
        #: aggregate serving counters (peak concurrency, admissions,
        #: generated tokens, decode walltime, speculation acceptance);
        #: per-request rows land in ``request_log`` — see :meth:`stats`.
        #: Admission and transfer counts: ``queue_wait_s`` sums (admission
        #: call - submit) over admitted requests; ``prefill_calls`` counts
        #: runs of the prefill program, ``prefill_rows`` the lane x token
        #: rows they computed, ``prefill_tokens`` the prompt tokens they
        #: ingested (suffixes, under prefix hits); over the lanes with
        #: prompt rows in a call, ``prefill_kv_tokens`` sums each lane's
        #: context at the chunk's end (the K/V tokens its rows read) and
        #: ``prefill_attn_pairs`` its causal query-key pairs;
        #: ``host_fetch_bytes`` counts the prefill first tokens and
        #: decode-block outputs copied to the host (see :meth:`_fetch`).
        self.counters = {"peak_live": 0, "admitted": 0, "gen_tokens": 0,
                         "queue_wait_s": 0.0, "prefill_calls": 0,
                         "prefill_rows": 0, "prefill_tokens": 0,
                         "prefill_kv_tokens": 0, "prefill_attn_pairs": 0,
                         "host_fetch_bytes": 0,
                         "decode_s": 0.0, "verify_steps": 0,
                         "draft_accepted": 0, "preemptions": 0,
                         "cancellations": 0, "timeouts": 0, "failures": 0,
                         "replays": 0, "spilled_pages": 0,
                         "shed_spec_rounds": 0, "straggler_blocks": 0,
                         "prefix_hits": 0, "prefix_hit_pages": 0,
                         "prefix_tokens_saved": 0, "cow_copies": 0,
                         "spec_k_rejits": 0, "recoveries": 0}
        #: one dict per retired request: ttft_s, gen_tokens, decode_s
        self.request_log: List[dict] = []
        self._req_meta: Dict[int, dict] = {}    # slot -> live request row
        # -- request-lifecycle robustness layer -------------------------
        self.preempt = bool(preempt)
        if self.preempt and not self.paged:
            raise ValueError(
                "preempt=True needs the paged cache: preempt-and-spill "
                "is a page-pool mechanism (dense slots have nothing to "
                "spill — every lane already owns its max_len rows)")
        if self.preempt and self.draft is not None:
            raise ValueError(
                "preempt=True with a model drafter is unsupported: the "
                "draft cache is a dense lane that cannot be spilled "
                "through the page pool (use ngram self-speculation)")
        self.preempt_after = max(1, int(preempt_after))
        self.shed_threshold = (None if shed_threshold is None
                               else float(shed_threshold))
        # -- SLO priority classes ---------------------------------------
        #: per-class targets driving the shed knobs; when set, pressure
        #: is defined by SLO risk (a class behind its TTFT / tok-per-s
        #: target) instead of the fixed pool-occupancy constant
        self.slo_targets = normalize_slo_targets(slo_targets)
        #: per-class lifecycle counters (admissions, terminal exits,
        #: preemptions, shed rounds, straggler attribution) — the
        #: aggregate ``counters`` keep their engine-wide totals
        self.class_counters = {c: self._fresh_class_row()
                               for c in PriorityClass}
        self.fault_injector = fault_injector
        #: restore-and-replay on block faults; defaults on whenever a
        #: fault injector is attached (chaos runs want recovery)
        self._recover = (bool(recover) if recover is not None
                         else fault_injector is not None)
        self.max_replays = int(max_replays)
        self.straggler = (StragglerMonitor() if straggler is None
                          else straggler)
        self.clock = _now if clock is None else clock
        self._t_start = self.clock()        # uptime_s origin
        #: journal records the hot standby has not applied yet; ``None``
        #: until a fleet heartbeat feeds it (standalone engines have no
        #: standby to lag), like ``decode_tok_per_s`` when unmeasurable
        self.journal_lag_records = None
        #: terminal request outcomes: req_id -> {"status", "tokens"}
        self.results: Dict[int, dict] = {}
        self._next_id = 0
        self._round = 0             # decode-block counter (chaos schedule)
        self._injected_slow = False
        self._slow_penalty = 1.0    # synthetic straggler seconds (CI)
        #: per-class (req id, blocked admission sweeps): each class's
        #: blocked head escalates independently — a REALTIME head's
        #: count must not reset because a BATCH record got admitted
        self._head_blocked: Dict[PriorityClass, tuple] = {}
        # -- durable serving state (crash-safe warm restart) ------------
        # With ``durable_dir`` every externally-driven state transition
        # (submit / direct add / explicit admit / decode block / cancel
        # / finish / retire) is journaled write-ahead through a fsync'd
        # BlobLog, and a full snapshot (cache pages, allocator order,
        # prefix index, queue, journal cursor) lands every
        # ``snapshot_every`` blocks.  ``Engine.recover(directory)``
        # rebuilds a killed engine: restore the newest snapshot, then
        # re-execute the journal tail — deterministic replay, so
        # recovered greedy streams are byte-identical to uninterrupted
        # ones.  Constructing WITH durable_dir starts a NEW run
        # (truncates any previous journal); recovering an old run goes
        # through ``recover`` on an engine built without it.
        self._journal = None
        self._jmute = 0             # >0: nested/replayed calls don't log
        self._durable_dir = None
        if int(snapshot_every) < 0:
            raise ValueError(
                f"snapshot_every must be >= 0 (got {snapshot_every}); "
                f"0 disables periodic snapshots, a negative period has "
                f"no meaning")
        self.snapshot_every = int(snapshot_every)
        self._durable_step = 0
        self._blocks_since_snap = 0
        if durable_dir is not None:
            from ..checkpoint.store import BlobLog
            os.makedirs(durable_dir, exist_ok=True)
            self._durable_dir = str(durable_dir)
            self._journal = BlobLog(os.path.join(durable_dir,
                                                 "journal.log"), fresh=True)

    # -- priority / journal plumbing ----------------------------------------
    @staticmethod
    def _fresh_class_row() -> dict:
        return {"admitted": 0, "completed": 0, "preemptions": 0,
                "cancellations": 0, "timeouts": 0, "failures": 0,
                "shed_rounds": 0, "straggler_blocks": 0}

    def _class_count(self, cls, key: str, n: int = 1) -> None:
        self.class_counters[coerce_priority(cls)][key] += n

    @contextlib.contextmanager
    def _journal_scope(self, *record, ahead: bool = False):
        """Journal one externally-driven transition.

        Appends ``record`` only at the OUTERMOST call — transitions a
        journaled call makes internally (step_many's admission sweep,
        retire's finishes, a replayed event) are consequences of the
        recorded one and re-derive deterministically on replay, so
        logging them too would double-apply.

        ``ahead=True`` (decode blocks) appends write-ahead — the block
        mutates donated device state, so a crash mid-block must find
        the commitment already durable and re-execute it.  The default
        appends on *success*: a call that raised at the validation
        boundary never happened, and replaying it would just re-raise
        into :meth:`recover`."""
        log = self._journal is not None and self._jmute == 0
        if log and ahead:
            self._journal.append(record)
        self._jmute += 1
        try:
            yield
        except BaseException:
            log = False
            raise
        finally:
            self._jmute -= 1
            if log and not ahead:
                self._journal.append(record)

    # -- request admission --------------------------------------------------
    def add_request(self, slot: int, prompt: np.ndarray, **kw):
        """Prefill one request into ``slot``."""
        self.add_requests({slot: prompt}, **kw)

    def add_requests(self, requests: Dict[int, np.ndarray], *,
                     gen_len: Optional[int] = None,
                     temperature=None, top_k=None, deadline_s=None,
                     priority=None, _t_submit=None, _ids=None,
                     _deadlines=None, _prefix=None):
        """Prefill several fresh slots together (batched chunked prefill).

        Prompts are ingested in full-batch chunks of ``prefill_chunk``
        tokens — O(max_prompt_len / chunk) model calls for the whole
        group.  An empty prompt is treated as a single pad/BOS token
        (id 0) so the first generated token is always defined.

        ``gen_len`` bounds generation per admitted request (``stop_pos =
        prompt_len + gen_len``; None = run to the cache bound).
        ``temperature``/``top_k``/``gen_len`` set the admitted slots'
        parameters: a scalar applies to all of them, a ``{slot: value}``
        dict sets them per request.

        A prompt longer than ``max_len`` is rejected (ValueError): the
        cache cannot hold it, and clamp-writing its tail into the last
        rows would silently serve a truncated request; every prompt and
        sampling parameter passes :func:`~.lifecycle.validate_request`
        (out-of-vocab / non-integer token ids, negative temperature or
        top_k are caller bugs, rejected at the boundary).  In paged
        mode the request's full token budget (``min(prompt_len +
        gen_len, max_len)`` rows) is allocated here; direct calls raise
        MemoryError when the pool is short — queue through
        :meth:`submit` to wait for pages instead (with ``preempt=True``
        running victims are spilled first and MemoryError is the last
        resort).

        ``deadline_s`` (scalar or ``{slot: v}``) sets a TTL from now;
        the request times out at the first block boundary past it,
        returning its partial output with status TIMED_OUT.

        ``priority`` (scalar or ``{slot: v}``; class enum, name or int
        value — see :class:`~.lifecycle.PriorityClass`) tags each
        admitted request's SLO class for victim selection, per-class
        telemetry and SLO-driven shedding; default STANDARD.
        """
        with self._journal_scope(
                "add", {"requests": {int(s): np.asarray(p)
                                     for s, p in requests.items()},
                        "gen_len": gen_len, "temperature": temperature,
                        "top_k": top_k, "deadline_s": deadline_s,
                        "priority": priority}):
            return self._add_requests(
                requests, gen_len=gen_len, temperature=temperature,
                top_k=top_k, deadline_s=deadline_s, priority=priority,
                _t_submit=_t_submit, _ids=_ids, _deadlines=_deadlines,
                _prefix=_prefix)

    def _add_requests(self, requests: Dict[int, np.ndarray], *,
                      gen_len=None, temperature=None, top_k=None,
                      deadline_s=None, priority=None, _t_submit=None,
                      _ids=None, _deadlines=None, _prefix=None):
        with TraceAnnotation("engine.admit.pages"):
            t_call = self.clock()
            reqs = {int(s): validate_request(p, vocab=self.cfg.vocab,
                                             temperature=temperature,
                                             top_k=top_k, priority=priority)
                    for s, p in requests.items()}
            if deadline_s is not None:
                # validated as the dict-or-scalar it is: every entry checked
                # on its own (collapsing to min() crashed on mixed None
                # entries and pinned the whole batch to the tightest TTL in
                # the validation error path)
                validate_request([], vocab=self.cfg.vocab,
                                 deadline_s=deadline_s)
            for s, p in reqs.items():
                if p.shape[0] > self.max_len:
                    raise ValueError(
                        f"prompt of {p.shape[0]} tokens does not fit the "
                        f"cache (max_len={self.max_len}); refusing to "
                        f"clamp-write the tail")
                if p.size == 0:
                    reqs[s] = np.zeros((1,), np.int32)
            if not reqs:
                return

            def per_slot(v, s, default):
                if v is None:
                    return default
                return v.get(s, default) if isinstance(v, dict) else v

            def stop_of(s, plen):
                return self._token_budget(plen, per_slot(gen_len, s, None))

            prefix_of: Dict[int, dict] = {}
            if self.paged:
                # one page allocation covers the request's whole budget, so
                # the block table is static for its lifetime (the fused
                # decode loop never needs a mid-block allocator callback).
                # Feasibility is checked for the whole group BEFORE touching
                # any allocator state, so a failed admission leaves the
                # engine exactly as it was.
                held: Dict[int, List[int]] = {}
                if self.prefix_cache:
                    # match each prompt's longest committed prefix and take
                    # a reference on the hit pages IMMEDIATELY (before any
                    # eviction/preemption below can run): a held page has
                    # refcount >= 2 and is untouchable by the eviction
                    # sweep.  try_admit matched+shared at pop time and
                    # passes its holds through ``_prefix``; either way this
                    # call owns them and must release them on failure.
                    for s, p in reqs.items():
                        info = (_prefix or {}).get(s)
                        h = None
                        if info is None:
                            info = self._match_prefix(p)
                            h = info["shared"] + (
                                [info["cow"]] if info["cow"] is not None
                                else [])
                            if h:
                                self.allocator.share(h)
                        prefix_of[s] = info
                        held[s] = h if h is not None else (
                            info["shared"] + ([info["cow"]]
                                              if info["cow"] is not None
                                              else []))
                cls_of = {s: coerce_priority(per_slot(priority, s, None))
                          for s in reqs}
                floor = min(cls_of.values())
                needs = {s: self.allocator.pages_for(stop_of(s, p.shape[0]))
                         - len(prefix_of[s]["shared"] if s in prefix_of
                               else ())
                         for s, p in reqs.items()}
                recyclable = sum(len(self._slot_pages.get(s, ()))
                                 for s in reqs)

                def short():
                    return (sum(needs.values())
                            - self.allocator.free_pages - recyclable)

                if short() > 0 and self.prefix_cache:
                    # cold index entries yield before any running request
                    # does — dropping unreferenced cached prefixes is free
                    # (class floor: a cached chunk more important than every
                    # request being admitted stays)
                    self.prefix_index.evict(
                        self.allocator, short(),
                        floor=floor if self.allocator.class_quotas else None)
                if short() > 0 and self.preempt:
                    # graceful degradation instead of MemoryError: spill
                    # running victims until the admission fits — but only
                    # victims at or below the most important class being
                    # admitted (a BATCH add must never spill REALTIME work)
                    self._preempt_until(sum(needs.values()) - recyclable,
                                        exclude=set(reqs), floor=floor)
                if short() > 0:
                    for h in held.values():
                        if h:
                            self.allocator.free(h)      # release the match
                    raise MemoryError(
                        f"page pool exhausted: admission needs "
                        f"{sum(needs.values())} pages, free "
                        f"{self.allocator.free_pages} of "
                        f"{self.allocator.num_pages} (queue through submit() "
                        f"to wait for pages)")
                if self.allocator.class_quotas:
                    # group quota preflight BEFORE any state moves (same
                    # atomicity rule as the pool check above): count the
                    # pages the recycle loop below will release as credit
                    needs_cls: Dict[PriorityClass, int] = {}
                    for s in reqs:
                        needs_cls[cls_of[s]] = (needs_cls.get(cls_of[s], 0)
                                                + needs[s])
                    release = [p for s in reqs for p in
                               (self._slot_shared.get(s, [])
                                if self.prefix_cache else [])
                               + self._slot_pages.get(s, [])]
                    freed, uncharge = self.allocator.release_credit(release)
                    qmsg = self.allocator.quota_violation(
                        needs_cls, freed=freed, uncharge=uncharge)
                    if qmsg is not None:
                        for h in held.values():
                            if h:
                                self.allocator.free(h)
                        raise MemoryError(
                            f"class quota exceeded: {qmsg} (queue through "
                            f"submit() to wait)")
                for s in reqs:
                    # direct slot-addressed admission over a slot that still
                    # holds pages (no finish() in between) recycles them
                    if self.prefix_cache:
                        self.allocator.free(self._slot_shared.pop(s, []))
                        self._pub.pop(s, None)
                    if s in self._slot_pages:
                        self.allocator.free(self._slot_pages.pop(s))
                for s in reqs:
                    info = prefix_of.get(s)
                    shared = info["shared"] if info else []
                    pages = self.allocator.alloc(needs[s], owner=s,
                                                 cls=cls_of[s])
                    self._slot_pages[s] = pages
                    self.block_tables[s, :] = self._trash
                    self.block_tables[s, :len(shared)] = shared
                    self.block_tables[s, len(shared):len(shared)
                                      + len(pages)] = pages
                    if self.prefix_cache:
                        self._slot_shared[s] = list(shared)
                        self._pub[s] = ((info["depth"], info["key"])
                                        if info else (0, ROOT))
                    if info and info["cow"] is not None:
                        # full-prompt hit: the boundary page still receives
                        # this slot's writes (last prompt row + decode), so
                        # it is copy-on-write duplicated into the slot's
                        # first private page before anything runs
                        self.cache = self._copy_page(
                            self.cache, jnp.int32(info["cow"]),
                            jnp.int32(pages[0]))
                        self.allocator.free([info["cow"]])
                        self.counters["cow_copies"] += 1
                    if info and (info["shared"] or info["cow"] is not None):
                        self.counters["prefix_hits"] += 1
                        self.counters["prefix_hit_pages"] += (
                            len(shared)
                            + (1 if info["cow"] is not None else 0))
                        self.counters["prefix_tokens_saved"] += info["start"]
                self._flush_block_tables()

            # a recycled slot may have idled for whole blocks since
            # finish(): decode advances dead lanes too (the held pad token
            # drives recurrent state forward), so zero each such lane NOW —
            # prefill must start from clean state, not from whatever
            # accumulated while the slot sat empty.  (Chunked-prefill
            # garbage writes into a clean lane don't dirty it: the
            # visibility mask + decode's write-before-attend keep those
            # rows unobservable, the same invariant as the cache margin.)
            for s in reqs:
                if not self._clean[s]:
                    self.cache = self._invalidate(self.cache, jnp.int32(s))
                    if self.draft is not None:
                        # the draft scan advances dead lanes too, so the
                        # drafter's recurrent/KV lane is just as dirty
                        self.draft_cache = self._draft_invalidate(
                            self.draft_cache, jnp.int32(s))
            starts = {s: info["start"] for s, info in prefix_of.items()
                      if info["start"]}
        with TraceAnnotation("engine.prefill"):
            if self.chunked:
                first = self._prefill_chunked(reqs, starts)
            else:
                first = self._prefill_looped(reqs)
        if self.spec and self.draft is not None:
            self._prefill_draft(reqs)
        t_first = self.clock()
        for s, p in reqs.items():
            self.pos[s] = p.shape[0]
            self.live[s] = True
            self.outputs[s] = []
            self.tokens[s, 0] = first[s]
            self._clean[s] = False          # lane now holds the prompt
            self.temperature[s] = per_slot(temperature, s, 0.0)
            self.top_k[s] = per_slot(top_k, s, 0)
            self.stop_pos[s] = stop_of(s, p.shape[0])
            # drafting corpus + per-request telemetry: TTFT is measured
            # from submit() when the request came through the queue,
            # else from this call's start (direct slot-addressed adds)
            self.hist[s, :] = 0
            self.hist[s, :p.shape[0]] = p
            # a queued request waited from submit() to this call; a
            # direct add did not wait
            t_sub = (_t_submit or {}).get(s, t_call)
            self.counters["queue_wait_s"] += t_call - t_sub
            rid = (_ids or {}).get(s)
            if rid is None:
                rid = self._mint_id()
            if _deadlines is not None and s in _deadlines:
                dl = _deadlines[s]
            else:
                d = per_slot(deadline_s, s, None)
                dl = None if d is None else t_call + float(d)
            cls = coerce_priority(per_slot(priority, s, None))
            self._req_meta[s] = {"id": rid, "ttft_s": t_first - t_sub,
                                 "queue_s": t_call - t_sub,
                                 "t_admit": t_first, "deadline": dl,
                                 "priority": cls}
            self._class_count(cls, "admitted")
        self.counters["admitted"] += len(reqs)
        self.counters["peak_live"] = max(self.counters["peak_live"],
                                         int(self.live.sum()))
        if self.prefix_cache:
            # publish the fresh prompts' full pages NOW so requests
            # admitted in the very next sweep already hit
            for s in reqs:
                self._publish_committed(s)

    def _flush_block_tables(self):
        """Write the host block tables into the cache pytree (one upload
        covering every table edit since the last flush).

        The ``.copy()`` is the same jit-boundary rule as ``_snap``:
        ``self.block_tables`` is mutated in place by finish()/admission
        right after dispatch, and on the CPU backend jax may alias the
        numpy buffer into the async transfer instead of copying it."""
        self.cache = set_block_table(self.cache, self.block_tables.copy())
        self._bt_dirty = False

    # -- prefix caching ------------------------------------------------------
    def _match_prefix(self, prompt: np.ndarray) -> dict:
        """Plan a prompt's admission against the prefix index.

        Returns ``start`` (first suffix token to prefill), ``shared``
        (index pages to map read-only at table entries 0..len-1),
        ``cow`` (an index page to duplicate into the slot's first
        private page, or None), and ``depth``/``key`` (how far down the
        chain the match reached — where this slot's own publication
        will resume).  The planner does NOT move refcounts; callers
        share the returned pages while the plan is in flight.

        A full-prompt hit still prefills the last prompt token: the
        engine needs its logits (the first generated token), and its
        KV row — plus every decode write after it — lands in the final
        matched page, so that page is planned as the CoW duplicate
        rather than a read-only mapping.
        """
        ps = self.allocator.page_size
        plen = int(prompt.shape[0])
        m, pages, key = self.prefix_index.match(prompt)
        if m == 0:
            return {"start": 0, "shared": [], "cow": None,
                    "depth": 0, "key": ROOT}
        if m * ps == plen:
            return {"start": plen - 1, "shared": pages[:-1],
                    "cow": pages[-1], "depth": m, "key": key}
        return {"start": m * ps, "shared": pages, "cow": None,
                "depth": m, "key": key}

    def _publish_committed(self, slot: int) -> None:
        """Publish ``slot``'s fully-committed pages to the prefix index.

        A page is publishable once every one of its rows is below the
        slot's committed watermark — ``(depth+1)*page_size <= pos``.
        Safe under speculative decode: rewind is a pos edit whose
        accepted count is clipped to >= 1 (see build_spec_decode_loop),
        so ``pos`` never decreases and the condition can only keep
        holding — a published page is never un-committed.  Chunks whose
        chain key is already indexed (a concurrent same-prefix stream
        published first) are skipped; the slot's duplicate page simply
        stays private.  Publication transfers allocator ownership to
        :data:`PREFIX_OWNER` and moves the page to the slot's shared
        list, so a later finish()/preempt() decrements instead of
        freeing — O(new chunks), no device work.
        """
        ps = self.allocator.page_size
        depth, parent = self._pub.get(slot, (0, ROOT))
        pos = int(self.pos[slot])
        while (depth + 1) * ps <= pos:
            chunk = self.hist[slot, depth * ps:(depth + 1) * ps]
            key = self.prefix_index.chain_key(parent, chunk)
            if key in self.prefix_index:
                self.prefix_index.touch(key)
            else:
                page = int(self.block_tables[slot, depth])
                assert page != self._trash \
                    and page in self._slot_pages.get(slot, ()), \
                    "publishable chunk not backed by a private page"
                self.allocator.share([page])
                self.allocator.transfer([page], PREFIX_OWNER)
                self._slot_pages[slot].remove(page)
                self._slot_shared[slot].append(page)
                meta = self._req_meta.get(slot)
                self.prefix_index.put(
                    key, parent, chunk, page, depth,
                    cls=meta["priority"] if meta else None)
            depth, parent = depth + 1, key
        self._pub[slot] = (depth, parent)

    def _cow_guard(self) -> None:
        """Belt-and-braces copy-on-write sweep before a decode block.

        By construction no block ever writes a shared page — decode and
        spec-verify write at positions >= ``pos``, and every table entry
        from ``pos // page_size`` on is slot-private (the suffix pages
        allocated at admission; published pages all sit below the
        committed watermark).  If a future writer path breaks that
        proof, this sweep duplicates the offending page instead of
        corrupting every other consumer, and the ``cow_copies`` counter
        records that it fired.
        """
        ps = self.allocator.page_size
        dirty = False
        for s in range(self.batch):
            if self.outputs[s] is None:
                continue                    # empty lane: all-trash table
            row = self.block_tables[s]
            for e in range(int(self.pos[s]) // ps, row.shape[0]):
                page = int(row[e])
                if (page == self._trash
                        or self.allocator.refcount(page) <= 1
                        or page in self._slot_pages.get(s, ())):
                    continue
                meta = self._req_meta.get(s)
                fresh = self.allocator.alloc(
                    1, owner=s, cls=meta["priority"] if meta else None)[0]
                self.cache = self._copy_page(self.cache, jnp.int32(page),
                                             jnp.int32(fresh))
                self.block_tables[s, e] = fresh
                self._slot_pages[s].append(fresh)
                if page in self._slot_shared.get(s, ()):
                    self._slot_shared[s].remove(page)
                self.allocator.free([page])     # drop this slot's hold
                self.counters["cow_copies"] += 1
                dirty = True
        if dirty:
            self._flush_block_tables()

    # -- admission queue ----------------------------------------------------
    def _mint_id(self) -> int:
        rid = self._next_id
        self._next_id += 1
        return rid

    def submit(self, prompt: np.ndarray, *, gen_len: Optional[int] = None,
               temperature: float = 0.0, top_k: int = 0,
               deadline_s: Optional[float] = None, priority=None) -> int:
        """Queue a request; returns its request id.

        The id keys every later lifecycle interaction —
        :meth:`cancel`, :meth:`status`, and the terminal entry in
        ``results`` (status + whatever tokens the request committed).

        Admission happens inside :meth:`step_many` (and via
        :meth:`try_admit`): a request leaves the queue the moment a
        lane is free AND — in paged mode — the free list covers its
        token budget, i.e. the instant earlier requests' freed pages
        add up, not when a whole dense slot's ``max_len`` would.

        ``deadline_s`` is a TTL from submission: past it, the request
        is timed out at the next block boundary (queued or running)
        and its partial output lands in ``results`` — no exception.

        ``priority`` (class enum / name / int value, default STANDARD)
        sets the request's SLO class: the queue serves the most
        important non-empty class first (FIFO within a class, no
        skipping past a page-blocked higher-class head), victims spill
        in BATCH→STANDARD→REALTIME order, and per-class SLO targets
        (``slo_targets``) drive graceful degradation.  The class never
        changes *what* a request generates — only when.
        """
        prompt = validate_request(prompt, vocab=self.cfg.vocab,
                                  temperature=temperature, top_k=top_k,
                                  deadline_s=deadline_s, priority=priority)
        if prompt.shape[0] > self.max_len:
            raise ValueError(
                f"prompt of {prompt.shape[0]} tokens does not fit the "
                f"cache (max_len={self.max_len})")
        t = self.clock()
        req = {"id": self._mint_id(), "prompt": prompt, "gen_len": gen_len,
               "temperature": temperature, "top_k": top_k,
               "t_submit": t, "priority": coerce_priority(priority),
               "deadline": None if deadline_s is None
               else t + float(deadline_s)}
        if self.paged:
            need = self.allocator.pages_for(self._budget(req))
            if need > self.allocator.num_pages:
                # would head-of-line block the FIFO forever
                raise ValueError(
                    f"request needs {need} pages but the pool only has "
                    f"{self.allocator.num_pages}; raise num_pages or "
                    f"lower gen_len")
            cap = self.allocator.cap_pages(req["priority"])
            if cap is not None and need > cap:
                # same head-of-line-forever shape, quota edition
                raise ValueError(
                    f"request needs {need} pages but class "
                    f"{req['priority'].name.lower()} is capped at {cap} "
                    f"of {self.allocator.num_pages}; raise the cap or "
                    f"lower gen_len")
        self.waiting.append(req)
        if self._journal is not None and self._jmute == 0:
            # journaled with the minted id so replay can assert the
            # deterministic re-mint matches; deadline_s rides RELATIVE —
            # perf_counter values don't survive a process, so a
            # recovered request's TTL restarts at recovery (the
            # conservative reading of "its clock died with the process")
            self._journal.append(("submit", {
                "id": req["id"], "prompt": prompt, "gen_len": gen_len,
                "temperature": temperature, "top_k": top_k,
                "deadline_s": deadline_s,
                "priority": req["priority"].name.lower()}))
        return req["id"]

    def status(self, req_id: int):
        """Lifecycle status of a request id (None = unknown id)."""
        if req_id in self.results:
            return self.results[req_id]["status"]
        for r in self.waiting:
            if r["id"] == req_id:
                return (RequestStatus.PREEMPTED if r.get("resume")
                        else RequestStatus.QUEUED)
        for m in self._req_meta.values():
            if m["id"] == req_id:
                return RequestStatus.RUNNING
        return None

    def cancel(self, req_id: int) -> bool:
        """Cancel by request id, wherever the request currently is.

        Queued (fresh or preempted): removed from the queue, terminal
        CANCELLED with whatever tokens it had committed (a preempted
        record's spilled payload is simply dropped).  Running: its lane
        finishes NOW with the partial output — pages freed, the lane
        admits the next request at the coming block boundary.  Unknown
        or already-terminal ids return False."""
        with self._journal_scope("cancel", int(req_id)):
            for i, r in enumerate(self.waiting):
                if r["id"] == req_id:
                    del self.waiting[i]
                    self._finalize_queued(r, RequestStatus.CANCELLED)
                    return True
            for s, m in list(self._req_meta.items()):
                if m["id"] == req_id:
                    self.live[s] = False
                    self.finish(s, status=RequestStatus.CANCELLED)
                    return True
            return False

    def _finalize_queued(self, rec: dict, status: RequestStatus) -> None:
        """Terminal outcome for a request that never (re)occupied a
        lane: results entry only — ``done`` tracks lane streams."""
        self.results[rec["id"]] = {"status": status,
                                   "tokens": list(rec.get("outputs") or [])}
        if status is RequestStatus.TIMED_OUT:
            self.counters["timeouts"] += 1
            self._class_count(self._rec_priority(rec), "timeouts")
        elif status is RequestStatus.CANCELLED:
            self.counters["cancellations"] += 1
            self._class_count(self._rec_priority(rec), "cancellations")

    def _sweep_deadlines(self) -> None:
        """TTL check at the block boundary — the engine's only safe
        cancellation point (slots change hands between blocks, never
        inside one).  Expired queued requests finalize without a lane;
        expired running ones finish with their partial output."""
        t = self.clock()
        expired = [r for r in self.waiting
                   if r.get("deadline") is not None and t >= r["deadline"]]
        if expired:
            gone = {id(r) for r in expired}
            self.waiting = deque(r for r in self.waiting
                                 if id(r) not in gone)
            for r in expired:
                self._finalize_queued(r, RequestStatus.TIMED_OUT)
        for s in range(self.batch):
            m = self._req_meta.get(s)
            if (m is not None and m.get("deadline") is not None
                    and self.live[s] and t >= m["deadline"]):
                self.live[s] = False
                self.finish(s, status=RequestStatus.TIMED_OUT)

    def _token_budget(self, plen: int, gen_len: Optional[int]) -> int:
        """A request's cache-row budget — its final ``stop_pos``.

        The single source of truth for both page planning (try_admit /
        submit) and allocation+stopping (add_requests): clamped to the
        cache bound (an oversized gen_len must stop at max_len, not
        keep a slot live while decode writes clamp into the last row),
        with an empty prompt counting as its 1-token pad/BOS stand-in.
        """
        plen = max(1, int(plen))
        return min(plen + gen_len, self.max_len) if gen_len is not None \
            else self.max_len

    def _budget(self, req) -> int:
        return self._token_budget(len(req["prompt"]), req["gen_len"])

    def retire_finished(self) -> int:
        """finish() every slot whose generation ended (frees its lane —
        and, paged, its pages) so try_admit can reuse both."""
        with TraceAnnotation("engine.retire"), \
                self._journal_scope("retire"):
            n = 0
            for s in range(self.batch):
                if self.outputs[s] is not None and not self.live[s]:
                    self.finish(s)
                    n += 1
            return n

    def _rec_priority(self, rec: dict) -> PriorityClass:
        """SLO class of a queue record (fresh or preempted resume)."""
        if rec.get("resume"):
            return coerce_priority(rec["meta"].get("priority"))
        return coerce_priority(rec.get("priority"))

    def _queue_head(self) -> int:
        """Index of the next admission candidate: the FRONT of the most
        important non-empty class.  Within a class the queue stays
        FIFO; across classes a more important arrival overtakes
        everything below it — but a page-blocked head still blocks all
        lower classes (no skipping downward), so admission order stays
        deterministic and a big REALTIME request cannot be starved by
        a stream of small BATCH ones slipping past it."""
        best, best_i = None, 0
        for i, r in enumerate(self.waiting):
            p = self._rec_priority(r)
            if best is None or p < best:
                best, best_i = p, i
                if p == PriorityClass.REALTIME:
                    break
        return best_i

    def try_admit(self) -> int:
        """Admit queued requests into free lanes while pages last:
        class-ordered (REALTIME > STANDARD > BATCH), FIFO within a
        class, no head-of-line skipping — a page-blocked head waits
        for pages rather than being starved by smaller requests behind
        it, so admission order is deterministic, which the
        cross-backend conformance suite relies on.  All fresh
        admissions of one call share a single batched prefill;
        preempted records resume individually (page payload + lane
        restore, no prefill at all).

        With ``preempt=True``, a head that stays page-blocked for
        ``preempt_after`` consecutive admission sweeps escalates
        (tracked per class — see ``_head_blocked``): running victims
        (see :meth:`_victim_order`) at or below the head's class are
        spilled until the head fits — head-of-line blocking becomes
        time slicing.  A head whose class has a TTFT SLO target and is
        already past it escalates immediately."""
        with TraceAnnotation("engine.admit") as span, \
                self._journal_scope("admit"):
            if not TraceAnnotation.is_enabled():
                return self._try_admit()
            before = [self.counters[k] for k in ADMIT_SPAN_COUNTERS]
            n = self._try_admit()
            span.set_metadata(**{k: self.counters[k] - b for k, b in
                                 zip(ADMIT_SPAN_COUNTERS, before)})
            return n

    def _try_admit(self) -> int:
        free = [s for s in range(self.batch)
                if self.outputs[s] is None and not self.live[s]]
        admit, kw = {}, {"gen_len": {}, "temperature": {}, "top_k": {},
                         "priority": {}, "_t_submit": {}, "_ids": {},
                         "_deadlines": {}, "_prefix": {}}
        planned = 0
        planned_cls: Dict[PriorityClass, int] = {}
        resumed = 0
        placed: set = set()
        while self.waiting and free:
            i = self._queue_head()
            req = self.waiting[i]
            cls = self._rec_priority(req)
            pre = None
            if self.paged:
                if req.get("resume"):
                    need = req["n_pages"]
                else:
                    need = self.allocator.pages_for(self._budget(req))
                    if self.prefix_cache:
                        # a hit's shared pages are mapped, not allocated:
                        # admission costs only the suffix's fresh pages
                        pre = self._match_prefix(req["prompt"])
                        need -= len(pre["shared"])
                fits = self.allocator.can_alloc(planned + need)
                if fits and self.allocator.class_quotas:
                    # the head waits (no exception) when its class is
                    # over cap or the free pages belong to another
                    # class's reserved floor — exactly how a pool-short
                    # head waits for pages
                    want = dict(planned_cls)
                    want[cls] = want.get(cls, 0) + need
                    fits = self.allocator.quota_violation(want) is None
                if not fits:
                    if self.prefix_cache:
                        # drop cold cached prefixes before touching any
                        # running request.  Pages already promised this
                        # sweep are share()-held (refcount >= 2), so
                        # the eviction cannot take them; the CURRENT
                        # head's match is not held yet and is protected
                        # explicitly.  Class floor: the head may only
                        # evict chunks of its own class or less
                        # important ones.
                        mine = set(pre["shared"]) if pre else set()
                        if pre and pre["cow"] is not None:
                            mine.add(pre["cow"])
                        # the sweep must cover whichever constraint
                        # actually blocks the head: the pool shortfall,
                        # or — quota-blocked with a free pool — the
                        # class's own published pages holding its budget
                        want = max(
                            planned + need - self.allocator.free_pages,
                            self.allocator.quota_evict_want(
                                cls, need, planned=planned_cls))
                        if self.prefix_index.evict(
                                self.allocator, want, protect=mine,
                                floor=(cls if self.allocator.class_quotas
                                       else None)):
                            continue    # freed pages; recheck the head
                    if self._maybe_preempt(req, cls, planned + need, free,
                                           exclude=placed):
                        continue        # victims spilled; recheck head
                    break
            del self.waiting[i]
            hb = self._head_blocked.get(cls)
            if hb is not None and hb[0] == req["id"]:
                # reset the escalation counter only when the tracked
                # blocked head itself got through — popping any OTHER
                # record (a resume, a small admission) must not clobber
                # a still-blocked head's count, or interleaved progress
                # would keep it one sweep short of preempting forever
                del self._head_blocked[cls]
            s = free.pop(0)
            placed.add(s)
            if req.get("resume"):
                # resume allocates immediately (not via ``planned``)
                self._resume(s, req)
                resumed += 1
                continue
            if self.paged:
                planned += need
                planned_cls[cls] = planned_cls.get(cls, 0) + need
            if pre is not None:
                # hold the matched pages NOW: a later head's eviction
                # (or a direct add elsewhere) must not free them while
                # this admission is pending in ``admit``
                h = pre["shared"] + ([pre["cow"]]
                                     if pre["cow"] is not None else [])
                if h:
                    self.allocator.share(h)
                kw["_prefix"][s] = pre
            admit[s] = req["prompt"]
            kw["gen_len"][s] = req["gen_len"]
            kw["temperature"][s] = req["temperature"]
            kw["top_k"][s] = req["top_k"]
            kw["priority"][s] = cls
            kw["_t_submit"][s] = req["t_submit"]
            kw["_ids"][s] = req["id"]
            kw["_deadlines"][s] = req["deadline"]
        if admit:
            self.add_requests(admit, **kw)
        return len(admit) + resumed

    # -- preempt-and-spill ---------------------------------------------------
    def _victim_order(self, exclude=(), floor=None) -> List[int]:
        """Spill order under pressure: class before slack — every BATCH
        request yields before any STANDARD one, and REALTIME yields
        last of all.  Within a class, requests WITHOUT deadlines yield
        first (nobody's SLO pays for the spill), then most-slack
        deadlines; ties break latest-admitted first — LIFO time
        slicing, the oldest work keeps its pages.

        ``floor`` (the preempting head's class) drops victims MORE
        important than the head entirely: a BATCH admission may spill
        other BATCH work, never a REALTIME stream."""
        cands = [s for s in range(self.batch)
                 if self.live[s] and s in self._req_meta
                 and s not in exclude]
        if floor is not None:
            cands = [s for s in cands
                     if coerce_priority(self._req_meta[s].get("priority"))
                     >= floor]

        def rank(s):
            m = self._req_meta[s]
            dl = m.get("deadline")
            return (-int(coerce_priority(m.get("priority"))),
                    dl is not None, -(dl or 0.0), -m["t_admit"], -s)

        return sorted(cands, key=rank)

    def _preempt_until(self, target_free: int, exclude=(),
                       floor=None) -> None:
        """Spill victims until ``free_pages`` covers ``target_free``
        (or no victims remain — the caller re-checks and degrades)."""
        for v in self._victim_order(exclude, floor=floor):
            if self.allocator.free_pages >= target_free:
                break
            self._preempt(v)

    def _maybe_preempt(self, req, cls: PriorityClass, need: int,
                       free: List[int], exclude=()) -> bool:
        """Escalating head-of-line response inside try_admit: only
        after the SAME head has been page-blocked ``preempt_after``
        consecutive sweeps (counted per class) do victims spill (a
        transient shortfall one retire sweep would fix must not thrash
        the pool).  Exception: a head whose class carries a TTFT SLO
        target it has already missed escalates NOW — patience is
        exactly the budget the SLO says it doesn't have."""
        if not self.preempt:
            return False
        hb = self._head_blocked.get(cls)
        rounds = hb[1] + 1 if hb is not None and hb[0] == req["id"] else 1
        self._head_blocked[cls] = (req["id"], rounds)
        if rounds < self.preempt_after and not self._past_ttft_slo(req, cls):
            return False
        progressed = False
        # quota-aware fit: charge the whole plan to the head's class —
        # conservative when the sweep's earlier admissions were other
        # classes (may spill one victim more than strictly needed),
        # never permissive
        for v in self._victim_order(exclude, floor=cls):
            if self.allocator.can_alloc(need, cls=cls):
                break
            self._preempt(v)
            free.append(v)          # the victim's lane is admittable now
            progressed = True
        return progressed and self.allocator.can_alloc(need, cls=cls)

    def _past_ttft_slo(self, req: dict, cls: PriorityClass) -> bool:
        """Has this queued record already blown its class TTFT target?
        (Resume records don't re-count — their first token shipped.)"""
        tgt = self.slo_targets.get(cls, {}).get("ttft_s")
        if tgt is None or req.get("resume"):
            return False
        return self.clock() - req["t_submit"] >= tgt

    def _page_payload(self, pages: List[int]) -> Dict[str, np.ndarray]:
        """Host copy of the pool pages' payload, keyed by cache path.

        Every page-pool leaf carries the page axis at position 1 —
        (layers_or_groups, num_pages+1, …) — so one gather rule covers
        lm dense/moe KV, hybrid attention, and int8 scale leaves alike.
        Families without page leaves (ssm: dense recurrent state, pool
        meters admission only) yield an empty payload."""
        ids = jnp.asarray(pages, jnp.int32)
        out = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                self.cache)[0]:
            if any(getattr(k, "key", None) == "pages" for k in path):
                out[jax.tree_util.keystr(path)] = np.asarray(leaf[:, ids])
        return out

    def _write_pages(self, payload: Dict[str, np.ndarray],
                     pages: List[int]) -> None:
        """Scatter a spilled payload into (new) physical pages."""
        ids = jnp.asarray(pages, jnp.int32)

        def put(path, leaf):
            data = payload.get(jax.tree_util.keystr(path))
            if data is None:
                return leaf
            return leaf.at[:, ids].set(jnp.asarray(data))

        self.cache = jax.tree_util.tree_map_with_path(put, self.cache)

    def _lane_state(self, slot: int):
        """Host copy of ``slot``'s recurrent lane (None for pure-KV
        families) via the same batch-leading view speculative rollback
        uses — preemption reuses the spec_state machinery instead of
        growing a second per-family state protocol."""
        rec = spec_state_fn(self.cache, self.cfg)
        if rec is None:
            return None
        return jax.tree_util.tree_map(lambda t: np.asarray(t[slot]), rec)

    def _write_lane(self, slot: int, lane) -> None:
        if lane is None:
            return
        rec = spec_state_fn(self.cache, self.cfg)
        rec = jax.tree_util.tree_map(
            lambda c, s: c.at[slot].set(jnp.asarray(s)), rec, lane)
        self.cache = spec_restore_fn(self.cache, rec, self.cfg)

    def _preempt(self, slot: int) -> None:
        """Spill ``slot``'s request to host memory and re-queue it.

        O(pages) + one lane gather: page payloads device_get through
        the shared axis-1 page indexing, the recurrent lane (ssm /
        hybrid) rides the spec_state hooks, the allocator takes the
        pages back atomically, and the block-table row points at the
        trash page.  The record re-enters the queue at the BACK —
        time slicing, not a livelock where the resumed head instantly
        re-preempts its own victim."""
        meta = self._req_meta.pop(slot)
        # the table row is the authoritative mapping: shared prefix
        # pages first, then the slot's private pages.  ALL of them are
        # payload-copied and ALL the slot's references dropped; resume
        # restores into fresh private pages, which stays correct even
        # if the index evicts the shared originals while the record
        # waits in the queue.
        row = self.block_tables[slot]
        mapped = [int(p) for p in row[row != self._trash]]
        payload = self._page_payload(mapped) if mapped else {}
        lane = self._lane_state(slot)
        shared = (self._slot_shared.pop(slot, [])
                  if self.prefix_cache else [])
        if shared:
            self.allocator.free(shared)         # drop this slot's holds
        private = self._slot_pages.pop(slot, [])
        spilled = self.allocator.spill(slot)
        assert sorted(spilled) == sorted(private) \
            and set(mapped) == set(shared) | set(private), \
            "allocator/engine page maps diverged"
        self.block_tables[slot, :] = self._trash
        self._bt_dirty = True
        rec = {"resume": True, "id": meta["id"], "meta": meta,
               "deadline": meta.get("deadline"),
               "n_pages": len(mapped), "payload": payload, "lane": lane,
               "pub": (self._pub.pop(slot, (0, ROOT))
                       if self.prefix_cache else None),
               "outputs": self.outputs[slot],
               "pos": int(self.pos[slot]),
               "token": int(self.tokens[slot, 0]),
               "hist": self.hist[slot].copy(),
               "temperature": float(self.temperature[slot]),
               "top_k": int(self.top_k[slot]),
               "stop_pos": int(self.stop_pos[slot])}
        self.outputs[slot] = None
        self.live[slot] = False
        self.pos[slot] = 0
        self.tokens[slot, 0] = 0
        self.temperature[slot] = 0.0
        self.top_k[slot] = 0
        self.stop_pos[slot] = self.max_len
        self.cache = self._invalidate(self.cache, jnp.int32(slot))
        self._clean[slot] = True
        self.waiting.append(rec)
        self.counters["preemptions"] += 1
        self._class_count(meta.get("priority"), "preemptions")
        self.counters["spilled_pages"] += len(mapped)

    def _resume(self, slot: int, rec: dict) -> None:
        """Re-admit a preempted request: restore, never recompute.

        Fresh physical pages receive the spilled payload and the block
        table re-targets them (restore does not pin physical ids);
        ``pos``, the held token, partial outputs and drafting history
        pick up exactly where the spill happened — a resumed greedy
        stream is byte-identical to an unpreempted one."""
        pages = self.allocator.alloc(rec["n_pages"], owner=slot,
                                     cls=self._rec_priority(rec))
        self._slot_pages[slot] = pages
        if self.prefix_cache:
            # a resumed request owns ALL its pages privately (the spill
            # copied shared-prefix payloads too); publication resumes at
            # the preserved chain position, so already-indexed chunks
            # are recognized and skipped rather than re-published
            self._slot_shared[slot] = []
            self._pub[slot] = rec.get("pub") or (0, ROOT)
        self.block_tables[slot, :] = self._trash
        self.block_tables[slot, :len(pages)] = pages
        self._flush_block_tables()
        if not self._clean[slot]:
            # the idle lane decayed under decode blocks since its last
            # occupant — recurrent families need the zeroing
            self.cache = self._invalidate(self.cache, jnp.int32(slot))
        if rec["payload"]:
            self._write_pages(rec["payload"], pages)
        self._write_lane(slot, rec["lane"])
        self.pos[slot] = rec["pos"]
        self.tokens[slot, 0] = rec["token"]
        self.live[slot] = True
        self.outputs[slot] = rec["outputs"]
        self.hist[slot] = rec["hist"]
        self.temperature[slot] = rec["temperature"]
        self.top_k[slot] = rec["top_k"]
        self.stop_pos[slot] = rec["stop_pos"]
        self._clean[slot] = False
        self._req_meta[slot] = rec["meta"]
        self.counters["peak_live"] = max(self.counters["peak_live"],
                                         int(self.live.sum()))

    def _prefill_chunked(self, reqs, starts=None) -> Dict[int, int]:
        """Batched chunked prefill; ``starts`` (slot -> first token to
        ingest) makes it suffix-only for prefix-cache hits — the chunk
        grid then runs at ``start + c0`` per slot, reading the shared
        prefix pages through the already-flushed block table.

        Each chunk's program takes every slot's row of its last prompt
        token in that chunk and returns the greedy first tokens, a (B,)
        int32: that, not the chunk's logits, is what is fetched, once
        per chunk.  Returns slot -> first generated token."""
        chunk = self.prefill_chunk
        starts = starts or {}
        offs = {s: int(starts.get(s, 0)) for s in reqs}
        sufs = {s: p[offs[s]:] for s, p in reqs.items()}
        plen = max(t.shape[0] for t in sufs.values())
        padded = -(-plen // chunk) * chunk      # one compile per chunk width
        toks = np.zeros((self.batch, padded), np.int32)
        for s, t in sufs.items():
            toks[s, :t.shape[0]] = t
        fresh = np.fromiter(sorted(reqs), np.int64)
        # slots whose (shorter) suffix is exhausted park at the LAST
        # full chunk inside the table — their garbage writes land at
        # positions >= max_len (the margin region, never attendable and
        # below no slot's committed watermark) instead of clamping into
        # real rows.  Offsets exist only in paged+prefix mode, where
        # width * page_size >= max_len + margin >= max_len + chunk.
        park = (self.block_tables.shape[1] * self.allocator.page_size
                - chunk) if (self.paged and offs and max(offs.values()))\
            else None
        t_last = {s: t.shape[0] - 1 for s, t in sufs.items()}
        first: Dict[int, int] = {}
        for c0 in range(0, padded, chunk):
            if c0 >= plen:
                break
            ends = [s for s in sufs if c0 <= t_last[s] < c0 + chunk]
            # live slots keep their own position: their (ignored) writes
            # land at [pos, pos+chunk) inside the margin, never clamped.
            with TraceAnnotation("engine.prefill.dispatch"):
                cur = self.pos.copy()
                if park is None:
                    cur[fresh] = c0
                else:
                    for s in reqs:
                        cur[s] = min(offs[s] + c0, park)
                last = np.zeros((self.batch,), np.int32)
                for s in ends:
                    last[s] = t_last[s] - c0
                tok, self.cache = self.prefill(
                    self.params, {"tokens": _snap(toks[:, c0:c0 + chunk])},
                    self.cache, _snap(cur), _snap(last))
            self.counters["prefill_calls"] += 1
            self.counters["prefill_rows"] += self.batch * chunk
            for s, t in sufs.items():
                r = min(t.shape[0] - c0, chunk)
                if r > 0:
                    p0 = offs[s] + c0
                    self.counters["prefill_kv_tokens"] += p0 + r
                    self.counters["prefill_attn_pairs"] += (
                        r * p0 + r * (r + 1) // 2)
            with TraceAnnotation("engine.prefill.fetch"):
                tok = self._fetch(tok)
            with TraceAnnotation("engine.prefill.argmax"):
                for s in ends:
                    first[s] = int(tok[s])
        self.counters["prefill_tokens"] += sum(t.shape[0]
                                               for t in sufs.values())
        return first

    def _prefill_looped(self, reqs) -> Dict[int, int]:
        """Per-token fallback for families without chunkable prefill.

        The full-batch decode calls advance EVERY lane — on recurrent
        families the pad-token inputs would corrupt mid-generation
        neighbours' state (and earlier fresh slots would pollute later
        ones).  Each slot's loop therefore restores all OTHER lanes to
        their pre-loop state afterwards (``merge_slot``), making its
        prefill exactly equivalent to a solo prefill.
        """
        first: Dict[int, int] = {}
        for s, p in reqs.items():
            before = self.cache
            logits = None
            for t in range(p.shape[0]):
                tok = np.zeros((self.batch, 1), np.int32)
                tok[s, 0] = p[t]
                logits, self.cache = self.decode(
                    self.params, self.cache, _snap(tok), _snap(self.pos))
                self.pos[s] += 1
            first[s] = int(self._fetch(jnp.argmax(logits[s, -1])))
            self.cache = self._merge(self.cache, before, jnp.int32(s))
            # keep pos at prompt length: later slots' loops must not write
            # into this slot's freshly-filled rows (add_requests re-asserts
            # the same value afterwards)
        return first

    def _prefill_draft(self, reqs):
        """Ingest admitted prompts into the DRAFT model's cache.

        After this the drafter has consumed exactly each admitted
        slot's prompt — one token behind the engine's held first
        generated token, which is precisely the state the spec loop's
        draft scan expects (its first draft step consumes the held
        token).  Chunked for attention-cache drafters, per-slot looped
        with ``merge_slot`` isolation for recurrent ones, mirroring the
        target's two prefill regimes.
        """
        d_cfg, d_params, _ = self.draft
        if self.draft_chunked:
            chunk = self.prefill_chunk
            plen = max(p.shape[0] for p in reqs.values())
            padded = -(-plen // chunk) * chunk
            toks = np.zeros((self.batch, padded), np.int32)
            for s, p in reqs.items():
                toks[s, :p.shape[0]] = p
            fresh = np.fromiter(sorted(reqs), np.int64)
            for c0 in range(0, padded, chunk):
                if c0 >= plen:
                    break
                cur = self.pos.copy()
                cur[fresh] = c0
                _, self.draft_cache = self._draft_prefill(
                    d_params, {"tokens": _snap(toks[:, c0:c0 + chunk])},
                    self.draft_cache, _snap(cur))
        else:
            for s, p in reqs.items():
                before = self.draft_cache
                cur = self.pos.copy()
                cur[s] = 0
                for t in range(p.shape[0]):
                    tok = np.zeros((self.batch, 1), np.int32)
                    tok[s, 0] = p[t]
                    _, self.draft_cache = self._draft_decode(
                        d_params, self.draft_cache, _snap(tok), _snap(cur))
                    cur[s] += 1
                self.draft_cache = self._draft_merge(self.draft_cache,
                                                     before, jnp.int32(s))

    # -- decode / retire -----------------------------------------------------
    # NOTE: all engine state crosses the jit boundary via ``_snap`` (a
    # defensive numpy copy): pos/tokens/live are mutated in place right
    # after the async dispatch, and on the CPU backend even jnp.array's
    # host copy can complete after that mutation (see ``_snap``).
    def step_many(self, n: int):
        """Run ``n`` fused decode steps in ONE jit call, sync once.

        Returns ``(block, block_live)`` — (n, B) emitted tokens and
        their validity mask.  Token-for-token identical to ``n`` calls
        of ``step()`` (same model step order, same PRNG stream: step
        ``i`` of the block draws with the global step counter the i-th
        single step would use).

        With speculation enabled (``spec=True``) ``n`` counts
        *draft→verify rounds* instead of single tokens: the block is
        (n * (spec_k + 1), B) and each live slot commits between 1 and
        spec_k + 1 tokens per round.  Greedy streams remain
        byte-identical to the non-speculative engine's.

        Robustness path: every block boundary sweeps deadlines, applies
        the pressure-shedding policy, and — when recovery is on (a
        fault injector is attached, or ``recover=True``) — snapshots
        the engine first.  A faulted block (injected exception, device
        fault lane, corruption report) restores the snapshot and
        replays: the injector fires once per (round, kind), so the
        replay runs clean and commits the exact tokens the fault-free
        run would.  Without recovery, device-flagged slots finish
        FAILED with their valid prefix; host-side faults propagate.

        Durable mode (``durable_dir``): the block commitment is
        journaled WRITE-AHEAD — fsync'd before any device work — so a
        crash anywhere inside the block re-executes it on recovery;
        every ``snapshot_every`` blocks a full snapshot (with the
        journal cursor) bounds the replay tail.
        """
        with TraceAnnotation("engine.step"):
            if self._journal is not None and self._jmute == 0:
                self._blocks_since_snap += 1
                if (self.snapshot_every
                        and self._blocks_since_snap > self.snapshot_every):
                    # snapshot BEFORE this block's journal record: the
                    # cursor must not cover a block the snapshot state
                    # hasn't executed, or recovery would skip it
                    self._save_durable()
                    self._blocks_since_snap = 1
            with self._journal_scope("block", int(n), ahead=True):
                return self._step_many(n)

    def _step_many(self, n: int):
        self._round += 1
        self._sweep_deadlines()
        n_eff, spec_now = self._shed_policy(n)
        if self.prefix_cache:
            self._cow_guard()
        if self.paged and self._bt_dirty:
            self._flush_block_tables()
        snap = self.snapshot() if self._recover else None
        pos_before = self.pos.copy()
        injector = self.fault_injector
        attempt = 0
        fault_slots: tuple = ()
        while True:
            try:
                self._injected_slow = False
                if injector is not None:
                    injector.before_block(self._round, self)
                t0 = self.clock()
                if spec_now:
                    block, block_live, fault = self._block_spec(n_eff)
                else:
                    block, block_live, fault = self._block_decode(n_eff)
                if injector is not None:
                    injector.after_block(self._round, self)
                t1 = self.clock()
                if fault.any():
                    raise DeviceFault(np.where(fault)[0])
                break
            except (RuntimeError, FloatingPointError) as e:
                if snap is not None and attempt < self.max_replays:
                    attempt += 1
                    self.restore(snap)
                    self.counters["replays"] += 1
                    continue
                if isinstance(e, DeviceFault):
                    # no recovery path: keep the block's committed
                    # prefix and fail the flagged slots below
                    fault_slots = e.slots
                    break
                raise
        self._gen_step += n_eff
        self._clean[:] = False              # decode advanced every lane
        self.counters["decode_s"] += t1 - t0
        self.counters["gen_tokens"] += int(block_live.sum())
        if spec_now and self._spec_adapter is not None:
            # acceptance-adaptive spec_k: feed the block's measured
            # accept telemetry and re-rank k for the NEXT block.
            # Committed tokens cannot change — the verifier accepts the
            # longest argmax-matching prefix at any k — only the
            # draft-depth economics do.  A k change swaps to (or
            # traces) the (n, k) loop on the next block.
            rounds, acc = self._last_spec_obs
            self._spec_adapter.observe(rounds, acc)
            k_new = self._spec_adapter.propose()
            if k_new != self.spec_k:
                self.spec_k = int(k_new)
                self.counters["spec_k_rejits"] += 1
        # per-block straggler telemetry: wall time per fused step; the
        # injector's deterministic slow flag adds a synthetic penalty
        # so CI chaos runs flag stragglers without real sleeps
        dur = (t1 - t0) / max(1, n_eff)
        if self._injected_slow:
            dur += self._slow_penalty
            self._injected_slow = False
        if (self.straggler is not None
                and self.straggler.record(self._round, dur)):
            self.counters["straggler_blocks"] += 1
            # attribute the straggler block to every class that had a
            # request in it — the classes whose latency actually paid
            # for the slow step (meta spans slots that finished
            # mid-block too: they waited on the same sync)
            for cls in {coerce_priority(m.get("priority"))
                        for m in self._req_meta.values()}:
                self._class_count(cls, "straggler_blocks")
        # stamp generation end the moment a slot's live drops: finish()
        # may run much later (deferred retirement), and the idle gap
        # must not count against the request's decode throughput
        for s in range(self.batch):
            if not self.live[s] and s in self._req_meta:
                self._req_meta[s].setdefault("t_done", t1)
        for s in range(self.batch):
            if self.outputs[s] is not None:
                self.outputs[s].extend(
                    int(t) for t in block[block_live[:, s], s])
        if (self.spec or self.prefix_cache) and not spec_now:
            # a plain block still has to feed hist — the drafting
            # corpus under speculation, the publication token source
            # under prefix caching: commit its tokens at their absolute
            # positions (the device spec loop does this on-device)
            for s in range(self.batch):
                col = block[:, s][block_live[:, s]]
                if col.size:
                    p0 = int(pos_before[s])
                    end = min(p0 + col.size, self.hist.shape[1])
                    self.hist[s, p0:end] = col[:end - p0]
        if self.prefix_cache:
            # the invariant the spec-rewind clip guarantees (and the
            # publication condition depends on): a block only ever
            # advances the committed watermark
            assert (self.pos >= pos_before).all(), \
                "pos went backwards across a block"
            for s in range(self.batch):
                # publish live slots AND slots that finished mid-block
                # (their pages are still mapped until retirement) —
                # but never a fault-flagged slot: its pages may hold
                # the very corruption the fault lane caught
                if self.outputs[s] is not None and s not in fault_slots:
                    self._publish_committed(s)
        for s in fault_slots:
            if self.outputs[s] is not None:
                self.live[s] = False
                self.finish(s, status=RequestStatus.FAILED)
        # continuous batching: with requests waiting, retire finished
        # slots NOW and admit whatever the freed lanes/pages cover —
        # admission latency is one block, not one drained batch
        if self.waiting:
            self.retire_finished()
            self.try_admit()
        return block, block_live

    def _shed_policy(self, n: int):
        """Pressure shedding.  Returns (block size, run speculative?).

        With per-class ``slo_targets`` set, pressure is defined by SLO
        *risk* instead of the fixed pool-occupancy constant: a class is
        at risk when its oldest queued request has waited past the
        class TTFT target, or its recent completions ran below the
        class tok-per-s target.  Degradation is ordered by class —
        BATCH's budget goes first (risk anywhere sheds speculation,
        whose verify waste mostly buys batch throughput), the fused
        block is halved only when REALTIME itself is at risk (admission
        and retire checks must come sooner than anything else).

        Without targets, the legacy knob applies: past
        ``shed_threshold`` pool occupancy, halve the fused block and
        drop speculation for the block.  Both knobs are block-shape
        changes, not sampling changes — greedy streams are unaffected
        by construction."""
        if self.slo_targets:
            cls = self._slo_pressure()
            if cls is None:
                return n, self.spec
            if self.spec:
                self.counters["shed_spec_rounds"] += 1
            self._class_count(cls, "shed_rounds")
            if cls == PriorityClass.REALTIME:
                return max(1, n // 2), False
            return n, False
        if (self.shed_threshold is None or not self.paged
                or self.allocator.num_pages == 0):
            return n, self.spec
        occ = self.allocator.used_pages / self.allocator.num_pages
        if occ < self.shed_threshold:
            return n, self.spec
        if self.spec:
            self.counters["shed_spec_rounds"] += 1
        return max(1, n // 2), False

    def _slo_pressure(self) -> Optional[PriorityClass]:
        """Most important class currently behind its SLO target (None =
        every class inside budget).  Queued-wait risk reads the oldest
        FRESH queued request per class (resumes already shipped their
        first token); throughput risk reads the last few measurable
        completions of the class."""
        t = self.clock()
        worst = None
        for cls, tgt in self.slo_targets.items():
            at_risk = False
            ttft = tgt.get("ttft_s")
            if ttft is not None:
                at_risk = any(
                    not r.get("resume") and t - r["t_submit"] >= ttft
                    for r in self.waiting
                    if self._rec_priority(r) == cls)
            rate = tgt.get("tok_per_s")
            if rate is not None and not at_risk:
                recent = [r["tok_per_s"] for r in self.request_log[-8:]
                          if r.get("priority") == cls.name.lower()
                          and r["tok_per_s"] is not None]
                at_risk = bool(recent) and float(np.mean(recent)) < rate
            if at_risk and (worst is None or cls < worst):
                worst = cls
        return worst

    def _block_decode(self, n: int):
        """One fused plain-decode block (n single-token steps)."""
        loop = self._loops.get(n)
        if loop is None:
            # cache donated for the same reason as _invalidate: the
            # loop's output cache replaces self.cache unconditionally,
            # and a block must not materialize a second full KV copy
            loop = jax.jit(build_decode_loop(self.cfg, self.ctx, n),
                           donate_argnums=(1,))
            self._loops[n] = loop
        with TraceAnnotation("engine.decode.dispatch"):
            sample_params = {"temperature": _snap(self.temperature),
                             "top_k": _snap(self.top_k)}
            # all-greedy batches skip the top-k sorts / noise generation
            # (greedy consumes no PRNG state, so the stream is unaffected)
            key = self._key if (self.temperature > 0).any() else None
            self.cache, tokens, pos, live, block, block_live, fault = loop(
                self.params, self.cache, _snap(self.tokens),
                _snap(self.pos), _snap(self.live), _snap(self.stop_pos),
                sample_params, key, jnp.int32(self._gen_step),
                jnp.int32(self.eos_id))
        # ONE host sync for the whole block (np.asarray blocks until the
        # device values are ready; .copy() detaches the engine's mutable
        # state from the device buffers)
        with TraceAnnotation("engine.decode.fetch"):
            block = self._fetch(block)
            block_live = self._fetch(block_live)
            self.tokens = self._fetch(tokens).copy()
            self.pos = self._fetch(pos).copy()
            self.live = self._fetch(live).copy()
            fault = self._fetch(fault)
        return block, block_live, fault

    def _block_spec(self, n: int):
        """One fused speculative block (n draft→verify rounds).

        The whole pipeline — drafting, the single k+1-position target
        pass, acceptance, position rewind, recurrent-state rollback —
        runs inside ONE jit call; the host sees only the committed
        tokens, exactly like the plain decode block.
        """
        model_draft = self.draft is not None and self.drafter_fn is None
        # keyed by (block size, k): adaptive spec_k swaps k between
        # blocks, and each distinct pair is ONE trace — revisiting a
        # previous k is a cache hit, so re-jits are bounded by the
        # number of distinct k values the adapter ever proposes
        loop = self._spec_loops.get((n, self.spec_k))
        if loop is None:
            if self.drafter_fn is not None:
                drafter, kw = self.drafter_fn, {}
            elif model_draft:
                drafter = "model"
                kw = dict(draft_cfg=self.draft[0], draft_ctx=self.draft[2])
            else:
                drafter, kw = "ngram", {}
            loop = jax.jit(
                build_spec_decode_loop(self.cfg, self.ctx, n, self.spec_k,
                                       drafter=drafter,
                                       ngram=self.spec_ngram, **kw),
                donate_argnums=(1, 11) if model_draft else (1,))
            self._spec_loops[(n, self.spec_k)] = loop
        with TraceAnnotation("engine.decode.dispatch"):
            sample_params = {"temperature": _snap(self.temperature),
                             "top_k": _snap(self.top_k)}
            key = self._key if (self.temperature > 0).any() else None
            common = (self.params, self.cache, _snap(self.tokens),
                      _snap(self.pos), _snap(self.live),
                      _snap(self.stop_pos), sample_params, key,
                      jnp.int32(self._gen_step), jnp.int32(self.eos_id))
            if model_draft:
                out = loop(*common, self.draft[1], self.draft_cache)
            else:
                out = loop(*common, _snap(self.hist))
        (self.cache, tokens, pos, live, aux, block, block_live,
         accepted, fault) = out
        with TraceAnnotation("engine.decode.fetch"):
            block = self._fetch(block)
            block_live = self._fetch(block_live)
            accepted = self._fetch(accepted)
            self.tokens = self._fetch(tokens).copy()
            self.pos = self._fetch(pos).copy()
            self.live = self._fetch(live).copy()
            fault = self._fetch(fault)
            if model_draft:
                self.draft_cache = aux
            else:
                self.hist = self._fetch(aux).copy()
        # acceptance telemetry: rounds in which a slot was live, and
        # how many drafts each such round committed (0..spec_k)
        step_live = block_live.reshape(n, self.spec_k + 1,
                                       self.batch)[:, 0]
        rounds = int(step_live.sum())
        acc = int(accepted[step_live].sum())
        self.counters["verify_steps"] += rounds
        self.counters["draft_accepted"] += acc
        # this block's delta, for the spec_k adapter — consumed by
        # step_many only after the block survives the fault check, so
        # a restored-and-replayed block is observed exactly once
        self._last_spec_obs = (rounds, acc)
        return block, block_live, fault

    def _fetch(self, x) -> np.ndarray:
        """Copy a serving output to the host (waiting for the program
        that makes it), counting its bytes in ``host_fetch_bytes``."""
        a = np.asarray(x)
        self.counters["host_fetch_bytes"] += a.nbytes
        return a

    def step(self):
        """Per-token decode: the n=1 decode loop (baseline path)."""
        self.step_many(1)

    def finish(self, slot: int,
               status: RequestStatus = RequestStatus.COMPLETED):
        """Retire ``slot`` with a terminal ``status``.

        Whatever the slot committed lands in ``results[req_id]`` — a
        cancelled/timed-out/failed request returns its partial output
        with the status, never an exception (exceptions are for caller
        bugs and unrecoverable engine faults)."""
        with self._journal_scope("finish", int(slot), status.value):
            self._finish(slot, status)

    def _finish(self, slot: int, status: RequestStatus):
        meta = self._req_meta.pop(slot, None)
        if meta is not None:
            cls = coerce_priority(meta.get("priority"))
            done = meta.get("t_done", self.clock())
            self.request_log.append(request_row(
                ttft_s=meta["ttft_s"], queue_s=meta.get("queue_s", 0.0),
                req_id=meta["id"],
                gen_tokens=len(self.outputs[slot] or []),
                decode_s=done - meta["t_admit"], status=status,
                priority=cls))
            self.results[meta["id"]] = {
                "status": status, "tokens": list(self.outputs[slot] or [])}
            if status is RequestStatus.CANCELLED:
                self.counters["cancellations"] += 1
                self._class_count(cls, "cancellations")
            elif status is RequestStatus.TIMED_OUT:
                self.counters["timeouts"] += 1
                self._class_count(cls, "timeouts")
            elif status is RequestStatus.FAILED:
                self.counters["failures"] += 1
                self._class_count(cls, "failures")
            elif status is RequestStatus.COMPLETED:
                self._class_count(cls, "completed")
        self.done.append(self.outputs[slot])
        self.outputs[slot] = None
        self.live[slot] = False
        self.pos[slot] = 0
        self.temperature[slot] = 0.0
        self.top_k[slot] = 0
        self.stop_pos[slot] = self.max_len
        # invalidate the retired request's serving state (KV rows /
        # recurrent state) so a recycled slot can never observe a
        # previous occupant — family-aware (see models.api.invalidate_fn),
        # in-place via donation.  Paged KV needs no zeroing at all: the
        # block-table reset below makes the pages unreachable, so only
        # recurrent-state lanes (ssm/hybrid) are touched.
        self.cache = self._invalidate(self.cache, jnp.int32(slot))
        if self.paged:
            # O(pages) retirement: free-list append + host table edit;
            # the pages' contents are left as-is (never observable — a
            # new owner's visibility mask hides them until overwritten)
            # and the device table write is deferred to the next
            # consumer, so a whole retire sweep costs one upload
            if self.prefix_cache:
                # decrement-not-free: shared prefix pages lose this
                # slot's reference only — the index (and any other
                # sharer) keeps them resident and matchable
                self.allocator.free(self._slot_shared.pop(slot, []))
                self._pub.pop(slot, None)
            self.allocator.free(self._slot_pages.pop(slot, []))
            self.block_tables[slot, :] = self._trash
            self._bt_dirty = True
        self._clean[slot] = True

    # -- snapshot / restore --------------------------------------------------
    def snapshot(self) -> dict:
        """Copy-complete engine snapshot in host memory.

        Everything a block can mutate is captured — the device cache(s),
        slot arrays, allocator free-list ORDER, block tables, queue,
        outputs, results, counters, and the PRNG round (``_gen_step``)
        — so :meth:`restore` rewinds the engine to this exact block
        boundary and a replay consumes identical randomness.  The
        device cache crosses via ``device_get``: the fused loops donate
        their cache argument, so holding a device reference would alias
        freed buffers."""
        snap = {
            "cache": jax.device_get(self.cache),
            "pos": self.pos.copy(), "tokens": self.tokens.copy(),
            "live": self.live.copy(), "clean": self._clean.copy(),
            "temperature": self.temperature.copy(),
            "top_k": self.top_k.copy(),
            "stop_pos": self.stop_pos.copy(), "hist": self.hist.copy(),
            "gen_step": self._gen_step, "round": self._round,
            "next_id": self._next_id,
            "head_blocked": dict(self._head_blocked),
            "class_counters": {c: dict(row) for c, row
                               in self.class_counters.items()},
            "outputs": [None if o is None else list(o)
                        for o in self.outputs],
            "done": list(self.done),
            "waiting": [_copy_record(r) for r in self.waiting],
            "req_meta": {s: dict(m) for s, m in self._req_meta.items()},
            "results": {k: {"status": v["status"],
                            "tokens": list(v["tokens"])}
                        for k, v in self.results.items()},
            "counters": dict(self.counters),
            "request_log": [dict(r) for r in self.request_log],
        }
        if self.paged:
            snap["allocator"] = self.allocator.state()
            snap["block_tables"] = self.block_tables.copy()
            snap["bt_dirty"] = self._bt_dirty
            snap["slot_pages"] = {s: list(p)
                                  for s, p in self._slot_pages.items()}
        if self.prefix_cache:
            snap["prefix_index"] = self.prefix_index.state()
            snap["slot_shared"] = {s: list(p)
                                   for s, p in self._slot_shared.items()}
            snap["pub"] = dict(self._pub)
        if self.draft is not None:
            snap["draft_cache"] = jax.device_get(self.draft_cache)
        return snap

    def restore(self, snap: dict) -> None:
        """Rewind the engine to :meth:`snapshot` state; the snapshot
        stays pristine (everything mutable is re-copied), so one
        snapshot survives any number of replays.

        Forward-compat: snapshots written before the priority /
        warm-restart layer (PR 6-era dicts) miss the new fields —
        per-class head tracking (then a single tuple), class counters,
        prefix-index state, journal cursor.  Each defaults cleanly
        instead of KeyError'ing: old snapshots stay restorable, their
        requests simply land in STANDARD."""
        self.cache = jax.device_put(snap["cache"], self._cache_sh)
        self.pos = snap["pos"].copy()
        self.tokens = snap["tokens"].copy()
        self.live = snap["live"].copy()
        self._clean = snap["clean"].copy()
        self.temperature = snap["temperature"].copy()
        self.top_k = snap["top_k"].copy()
        self.stop_pos = snap["stop_pos"].copy()
        self.hist = snap["hist"].copy()
        self._gen_step = snap["gen_step"]
        self._round = snap["round"]
        self._next_id = snap["next_id"]
        hb = snap.get("head_blocked")
        if isinstance(hb, tuple):
            # legacy single-head tuple: a tracked head predating the
            # class split was necessarily scheduled as STANDARD-like
            # FIFO — park its count there, drop the no-head sentinel
            hb = ({PriorityClass.STANDARD: hb} if hb[0] is not None
                  else {})
        self._head_blocked = dict(hb or {})
        self.class_counters = {c: self._fresh_class_row()
                               for c in PriorityClass}
        for c, row in (snap.get("class_counters") or {}).items():
            self.class_counters[coerce_priority(c)].update(row)
        self.outputs = [None if o is None else list(o)
                        for o in snap["outputs"]]
        self.done = list(snap["done"])
        self.waiting = deque(_copy_record(r) for r in snap["waiting"])
        self._req_meta = {s: dict(m) for s, m in snap["req_meta"].items()}
        self.results = {k: {"status": v["status"],
                            "tokens": list(v["tokens"])}
                        for k, v in snap["results"].items()}
        self.counters = dict(self.counters, **snap["counters"])
        self.request_log = [dict(r) for r in snap["request_log"]]
        if self.paged:
            self.allocator.load_state(snap["allocator"])
            self.block_tables = snap["block_tables"].copy()
            self._bt_dirty = snap["bt_dirty"]
            self._slot_pages = {s: list(p)
                                for s, p in snap["slot_pages"].items()}
        if self.prefix_cache:
            idx = snap.get("prefix_index")
            if idx is not None:
                self.prefix_index.load_state(idx)
                self._slot_shared = {s: list(p) for s, p
                                     in snap["slot_shared"].items()}
                self._pub = dict(snap["pub"])
            else:
                # snapshot predates the prefix layer: start the index
                # cold — correctness never depended on it being warm
                self.prefix_index = PrefixIndex(self.allocator.page_size)
                self._slot_shared = {s: [] for s in self._slot_pages}
                self._pub = {s: (0, ROOT) for s in self._slot_pages}
        if self.draft is not None and "draft_cache" in snap:
            self.draft_cache = jax.device_put(snap["draft_cache"])

    def save_snapshot(self, directory: str, step: int = 0) -> str:
        """Persist :meth:`snapshot` to disk with the checkpoint store's
        atomics (write to ``.tmp``, ``os.replace``): a crash mid-save
        can never corrupt the newest complete snapshot."""
        from ..checkpoint.store import save_blob
        return save_blob(self.snapshot(), directory, step)

    def load_snapshot(self, directory: str,
                      step: Optional[int] = None) -> None:
        """Restore the newest (or given) on-disk snapshot."""
        from ..checkpoint.store import latest_step, load_blob
        if step is None:
            step = latest_step(directory)
            if step is None:
                raise FileNotFoundError(f"no engine snapshot under "
                                        f"{directory}")
        self.restore(load_blob(directory, step))

    # -- crash-safe warm restart ---------------------------------------------
    def _save_durable(self) -> str:
        """One durable snapshot: :meth:`snapshot` plus the journal
        cursor (records already REFLECTED in the state — recovery
        replays only the tail past it), through save_blob's tmp +
        os.replace atomics, so a crash mid-save leaves the previous
        snapshot authoritative."""
        from ..checkpoint.store import save_blob
        snap = self.snapshot()
        snap["journal_cursor"] = self._journal.count
        path = save_blob(snap, self._durable_dir, self._durable_step)
        self._durable_step += 1
        return path

    def recover(self, directory: str) -> dict:
        """Rebuild this (freshly constructed) engine from a killed
        run's durable directory and resume journaling into it.

        Construct the engine with the SAME arguments as the dead one
        but WITHOUT ``durable_dir`` (that would truncate the evidence),
        then call ``recover``: the newest durable snapshot restores
        (if one landed), the journal tail past its cursor re-executes
        — deterministic replay of the exact submit / admit / block /
        cancel / finish / retire sequence, muted so it is not
        re-journaled — and the journal reopens for append, torn tail
        truncated.  Every in-flight stream resumes byte-identically:
        greedy decode is deterministic and sampled decode replays the
        same PRNG round (``gen_step`` rides the snapshot).

        Returns ``{"snapshot_step", "replayed"}`` telemetry.
        """
        from ..checkpoint.store import BlobLog, latest_step, load_blob
        if self._journal is not None:
            raise RuntimeError(
                "recover() on an engine constructed with durable_dir: "
                "construction already truncated the journal — build "
                "the engine without durable_dir and recover into it")
        log = BlobLog(os.path.join(directory, "journal.log"))
        step = latest_step(directory)
        cursor = 0
        if step is not None:
            snap = load_blob(directory, step)
            cursor = int(snap.get("journal_cursor", 0))
            self.restore(snap)
            self._durable_step = step + 1
        records = log.read(cursor)
        self._jmute += 1
        try:
            for rec in records:
                self._replay_event(rec)
        finally:
            self._jmute -= 1
        self._durable_dir = str(directory)
        self._journal = log
        self._blocks_since_snap = 0
        self.counters["recoveries"] += 1
        return {"snapshot_step": step, "replayed": len(records)}

    def _replay_event(self, rec: tuple) -> None:
        """Re-execute one journaled transition (muted by recover)."""
        kind = rec[0]
        if kind == "submit":
            p = rec[1]
            rid = self.submit(p["prompt"], gen_len=p["gen_len"],
                              temperature=p["temperature"],
                              top_k=p["top_k"], deadline_s=p["deadline_s"],
                              priority=p["priority"])
            if rid != p["id"]:
                raise RuntimeError(
                    f"journal replay diverged: submit re-minted id "
                    f"{rid}, journal says {p['id']} (snapshot and "
                    f"journal are from different runs?)")
        elif kind == "add":
            p = rec[1]
            self.add_requests(p["requests"], gen_len=p["gen_len"],
                              temperature=p["temperature"],
                              top_k=p["top_k"],
                              deadline_s=p["deadline_s"],
                              priority=p["priority"])
        elif kind == "admit":
            self.try_admit()
        elif kind == "block":
            self.step_many(rec[1])
        elif kind == "retire":
            self.retire_finished()
        elif kind == "cancel":
            self.cancel(rec[1])
        elif kind == "finish":
            self.finish(rec[1], status=RequestStatus(rec[2]))
        else:
            raise RuntimeError(f"unknown journal record kind {kind!r}")

    def _poison_cache(self, value: float) -> None:
        """Chaos hook: overwrite every float leaf of the serving cache.

        Block tables and integer page payloads stay intact — injected
        corruption models bad page *contents*; a structurally broken
        table is an allocator bug, tested separately."""
        val = float(value)
        self.cache = jax.tree_util.tree_map(
            lambda leaf: (jnp.full_like(leaf, val)
                          if jnp.issubdtype(leaf.dtype, jnp.floating)
                          else leaf),
            self.cache)

    # -- telemetry -----------------------------------------------------------
    def stats(self) -> dict:
        """Aggregate serving telemetry.

        Combines the running counters with per-request rows from
        ``request_log``: time-to-first-token (submit→first token for
        queued requests), engine decode throughput (committed tokens
        per second of block walltime, syncs included), the mean queue
        wait of admitted requests, the prefill program's calls and the
        share of its rows that held prompt tokens, the bytes fetched to
        the host, and — under speculation — the mean number of drafted
        tokens accepted per verify round (committed tokens per round =
        that + 1).
        """
        c = dict(self.counters)
        out = {"requests": len(self.done), "admitted": c["admitted"],
               "peak_live": c["peak_live"], "gen_tokens": c["gen_tokens"],
               "decode_s": c["decode_s"],
               # None — not 0.0 — when no decode interval was measurable
               # (fake clocks, sub-resolution runs): the same rule
               # request_row applies per request, so aggregates skip the
               # value instead of reporting a fictitious stall
               "decode_tok_per_s": (c["gen_tokens"] / c["decode_s"]
                                    if c["decode_s"] > 0 else None),
               # admission: mean queue wait of the admitted requests, and
               # the share (0-1) of the prefill program's lane x token
               # rows that held prompt tokens (None before any prefill)
               "queue_wait_mean_s": (c["queue_wait_s"] / c["admitted"]
                                     if c["admitted"] else None),
               "prefill_calls": c["prefill_calls"],
               "prefill_useful_share": (
                   c["prefill_tokens"] / c["prefill_rows"]
                   if c["prefill_rows"] else None),
               # the paged prefill kernel's useful work: K/V tokens its
               # prompt rows read and their causal query-key pairs
               "prefill_kv_tokens": c["prefill_kv_tokens"],
               "prefill_attn_pairs": c["prefill_attn_pairs"],
               "host_fetch_bytes": c["host_fetch_bytes"]}
        if self.request_log:
            out["ttft_mean_s"] = float(np.mean(
                [r["ttft_s"] for r in self.request_log]))
            # rows with tok_per_s None had no measurable decode
            # interval (fake clocks, sub-resolution completions) —
            # skip them rather than average in a fictitious zero
            rates = [r["tok_per_s"] for r in self.request_log
                     if r["tok_per_s"] is not None]
            out["req_tok_per_s_mean"] = (float(np.mean(rates))
                                         if rates else 0.0)
        if self.spec:
            out["verify_steps"] = c["verify_steps"]
            out["accepted_per_step"] = (c["draft_accepted"]
                                        / max(c["verify_steps"], 1))
            # the adapted draft depth: current k, the construction cap,
            # and how many loop re-traces adaptation actually cost
            out["spec_k"] = self.spec_k
            out["spec_k_init"] = self._spec_k_init
            out["spec_k_rejits"] = c["spec_k_rejits"]
        # which model picked the knobs ("off" = legacy defaults), its
        # provenance, and the block size it resolved (None under "off":
        # the caller drives block size directly)
        out["autotune"] = self.autotune
        if self._autotune_est is not None:
            out["autotune_source"] = self._autotune_est.source
        if self.decode_block is not None:
            out["decode_block"] = self.decode_block
        if self.paged:
            # the resolved split-KV reuse factor this geometry runs
            # with (cost-model choice unless pinned by flag/ctx)
            out["kv_split"] = self.kv_split
            out["pages_per_step"] = self.pages_per_step
        if self.prefix_cache:
            out["prefix_hits"] = c["prefix_hits"]
            out["prefix_hit_pages"] = c["prefix_hit_pages"]
            out["prefix_tokens_saved"] = c["prefix_tokens_saved"]
            out["cow_copies"] = c["cow_copies"]
            out["shared_pages"] = self.allocator.shared_pages()
            out["prefix_index_pages"] = len(self.prefix_index)
        # lifecycle / robustness counters (see the PR 6 layer): how many
        # requests left through each non-happy path, and what the
        # degradation machinery did about pressure and faults
        out["queued"] = len(self.waiting)
        for k in ("preemptions", "cancellations", "timeouts", "failures",
                  "replays", "spilled_pages", "shed_spec_rounds",
                  "straggler_blocks"):
            out[k] = c[k]
        out["straggler_events"] = (len(self.straggler.events)
                                   if self.straggler is not None else 0)
        # fleet-facing health counters: how long this engine has been
        # up, how many times it was rebuilt from a journal (recover /
        # promotion), and how far a hot standby trails its journal
        # (None = no fleet heartbeat feeds it, like decode_tok_per_s
        # when unmeasurable)
        out["uptime_s"] = float(self.clock() - self._t_start)
        out["recoveries"] = c["recoveries"]
        out["journal_lag_records"] = self.journal_lag_records
        # per-class SLO telemetry: lifecycle counters plus latency
        # percentiles over the class's retired rows — only classes
        # with any activity appear, so single-class runs stay tidy
        classes = {}
        for cls in PriorityClass:
            row = dict(self.class_counters[cls])
            rows = [r for r in self.request_log
                    if r.get("priority", "standard") == cls.name.lower()]
            row["requests"] = len(rows)
            row["queued"] = sum(1 for r in self.waiting
                                if self._rec_priority(r) == cls)
            if rows:
                tt = [r["ttft_s"] for r in rows]
                row["ttft_p50_s"] = float(np.percentile(tt, 50))
                row["ttft_p99_s"] = float(np.percentile(tt, 99))
                rates = [r["tok_per_s"] for r in rows
                         if r["tok_per_s"] is not None]
                row["tok_per_s_mean"] = (float(np.mean(rates))
                                         if rates else None)
            if (row["requests"] or row["queued"]
                    or any(row[k] for k in self._fresh_class_row())):
                classes[cls.name.lower()] = row
        if classes:
            out["classes"] = classes
        if self.slo_targets:
            out["slo_targets"] = {c.name.lower(): dict(t)
                                  for c, t in self.slo_targets.items()}
        return out


def quantize_for_serving(params, ctx: QuantContext):
    """PTQ the parameter tree once, at engine construction.

    Weight matrices become QTensor (per-out-channel scales) per the
    context's precision policy; ``linear()`` then consumes them with
    zero per-forward weight-quantization work.  Each leaf is quantized
    by its own jitted program and each float weight is deleted once its
    quantized copy exists, so the device never holds both models: the
    tree passed in is consumed.  Leaves that stay float are returned
    as they are.  Each quantized leaf is waited for before the next is
    dispatched: the device allocates a program's outputs when it is
    enqueued but frees a deleted input only when its readers finish, so
    with cached compiles the dispatch ran ahead and held most of both
    models at once.
    """
    from ..core.qtypes import QTensor
    from ..core.quantize import ptq_leaf

    def one(path, leaf):
        q = jax.jit(lambda x: ptq_leaf(path, x, ctx.policy))(leaf)
        if isinstance(q, QTensor):
            jax.block_until_ready(q)
            leaf.delete()
        return q

    return jax.tree_util.tree_map_with_path(one, params)


def serving_params(cfg, ctx: QuantContext, mesh, *, seed: int):
    """Random parameters for ``cfg`` from ``seed``, made on the device.

    They are created by one jitted program straight into their mesh
    shardings in ``ctx.param_dtype`` — no float32 copy of the model is
    ever materialized unless that is the serving dtype — and, under
    ``mode="int8"``, quantized leaf by leaf with each float leaf freed
    (:func:`quantize_for_serving`).
    """
    fam = get_family(cfg)
    key = jax.random.PRNGKey(seed)

    def init(k):
        return fam.init(k, cfg, dtype=ctx.param_dtype)

    shapes = jax.eval_shape(init, key)
    params = jax.jit(init, out_shardings=named(
        param_specs(shapes, mesh), mesh))(key)
    if ctx.mode == "int8":
        # the fused pipeline's first leg: weights quantized ONCE here
        params = quantize_for_serving(params, ctx)
        params = jax.device_put(params, named(param_specs(params, mesh),
                                              mesh))
    return params


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--quant", default="none",
                    choices=["none", "fake", "int8"])
    ap.add_argument("--qbits", type=int, default=8)
    ap.add_argument("--lut", action="store_true")
    ap.add_argument("--f32", action="store_true")
    ap.add_argument("--reuse-factor", type=int, default=8,
                    help="layer-scan unroll = max(8 // reuse_factor, 1); "
                         "the default keeps the layer loop rolled: "
                         "unrolled by 8, XLA copies 8 layers of weights "
                         "per loop iteration, which took the yi-6b "
                         "decode loop to 16.25 GB, past one v5e's HBM")
    ap.add_argument("--kv-bits", type=int, default=None, choices=[8],
                    help="int8 KV cache (per-token scales)")
    ap.add_argument("--prefill-chunk", type=int, default=16,
                    help="tokens per batched prefill step")
    ap.add_argument("--decode-block", type=int, default=None,
                    help="decode steps fused per jit call (1 = per-"
                         "token); default: the autotuner's resolved "
                         "block (8 with --autotune off)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache: shared page pool + block tables; "
                         "admission metered by used tokens (dense mode "
                         "still wins at tiny batches — no indirection)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV rows per page (paged mode)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="page-pool size (default: batch*max_len/page_size, "
                         "the dense-equivalent HBM budget)")
    ap.add_argument("--kv-split", default="auto",
                    help="split-KV paged attention: number of parallel "
                         "flash-decoding partitions per slot (the kernel-"
                         "side reuse factor; 1 = today's serial page "
                         "chain, byte-identical). 'auto' picks from a "
                         "cached cost model (default)")
    ap.add_argument("--pages-per-step", default="auto",
                    help="KV pages DMA'd per grid step (multi-page tile, "
                         "double-buffered); 'auto' sizes the tile to a "
                         "~128-row MXU operand (default)")
    ap.add_argument("--autotune", default="analytic",
                    choices=("off", "analytic", "fitted"),
                    help="unified knob resolution: 'off' = legacy "
                         "defaults byte-for-byte; 'analytic' resolves "
                         "kv-split/pages-per-step/decode-block/spec-k "
                         "from the hand-set cost model and adapts "
                         "spec-k online from measured acceptance; "
                         "'fitted' does the same on least-squares "
                         "constants fitted from bench_calibrate "
                         "measurements (AUTOTUNE.json, falling back "
                         "to analytic without data). Explicit knob "
                         "flags always win")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="prefix caching over the page pool (paged "
                         "mode): committed prompt pages are indexed "
                         "and shared copy-on-write with later requests "
                         "that open with the same tokens — a hit "
                         "prefills only its suffix (inert for "
                         "recurrent families, whose state cannot skip "
                         "tokens)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="restrict sampling to the k best logits (0 = off)")
    ap.add_argument("--spec", action="store_true",
                    help="speculative decoding: draft k tokens per round "
                         "and verify them with ONE target pass (greedy "
                         "streams stay byte-identical; helps on "
                         "repetitive/code-like continuations, costs a "
                         "little on incompressible ones)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="drafted tokens per verify round (the serving-"
                         "side reuse factor: deeper = fewer target "
                         "passes when drafts hit, more waste when not)")
    ap.add_argument("--spec-draft", default=None,
                    help="arch name of a (smaller) draft model sharing "
                         "the target's vocab (implies --spec); default = "
                         "prompt-lookup self-speculation, no second model")
    ap.add_argument("--spec-ngram", type=int, default=2,
                    help="context length of the prompt-lookup match")
    ap.add_argument("--preempt", action="store_true",
                    help="preempt-and-spill (paged mode): under page "
                         "pressure spill a running victim's pages to "
                         "host memory and resume it later — graceful "
                         "degradation instead of head-of-line blocking")
    ap.add_argument("--shed-threshold", type=float, default=None,
                    help="page-pool occupancy (0..1) past which the "
                         "engine sheds pressure: halves the decode "
                         "block and skips speculation for the block")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request TTL from submission; past it the "
                         "request times out at the next block boundary "
                         "and returns its partial output")
    ap.add_argument("--priority-class", default="standard",
                    choices=[c.name.lower() for c in PriorityClass],
                    help="SLO class for the submitted requests: the "
                         "queue serves realtime > standard > batch "
                         "(FIFO within a class), victims spill batch "
                         "first, and per-class SLO targets drive the "
                         "shed knobs")
    ap.add_argument("--slo-ttft-s", type=float, default=None,
                    help="TTFT target (seconds) for the REALTIME "
                         "class; a realtime request queued past it "
                         "escalates preemption immediately and puts "
                         "the engine in SLO-shed mode (drops spec, "
                         "halves the block) until it is served")
    ap.add_argument("--slo-tok-per-s", type=float, default=None,
                    help="decode-throughput target (tok/s) for the "
                         "REALTIME class, driving the same shed knobs")
    ap.add_argument("--durable-dir", default=None,
                    help="crash-safe warm restart: journal every "
                         "request/block event (fsync'd write-ahead "
                         "log) and snapshot the engine every "
                         "--snapshot-every blocks under this "
                         "directory; rebuild a killed engine with "
                         "Engine.recover(dir)")
    ap.add_argument("--snapshot-every", type=int, default=8,
                    help="blocks between durable snapshots "
                         "(--durable-dir mode); smaller = shorter "
                         "replay tail, more snapshot IO")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve through a Fleet of N engine replicas "
                         "with class-aware least-pressure routing and "
                         "heartbeat failure detection (1 = single "
                         "engine, no fleet layer)")
    ap.add_argument("--standby-dir", default=None,
                    help="journal-shipped hot standby (implies a "
                         "fleet): the primary journals under this "
                         "directory, a warm standby tails it within "
                         "--replicas' bounded lag, and on primary "
                         "death the fleet promotes the standby and "
                         "resumes every in-flight stream "
                         "byte-identically")
    ap.add_argument("--class-quota", action="append", default=None,
                    metavar="CLASS:KIND=FRACTION",
                    help="partition the page pool per SLO class "
                         "(repeatable; needs --paged): e.g. "
                         "'realtime:floor=0.25' reserves a quarter of "
                         "the pages for realtime, 'batch:cap=0.5' "
                         "caps batch at half — a batch flood can "
                         "then never evict the realtime working set")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.spec_draft:
        args.spec = True                        # a drafter implies --spec
    return args


def setup(args, devices=None):
    """(cfg, ctx, mesh) for parsed serve arguments.

    The kernels are the serving backend on a TPU (``pallas``); elsewhere
    the portable ``ref`` lowerings run, and :func:`lowerings_line` says
    so.  ``devices`` restricts the mesh (default: every device).
    """
    from ..kernels.ops import on_tpu
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    ctx = build_ctx(args, backend="pallas" if on_tpu() else "ref")
    mesh = make_local_mesh(model=args.model_parallel, devices=devices)
    return cfg, ctx, mesh


def _first_pages(tree):
    """The first ``pages`` subtree of a serving cache (None: dense)."""
    if isinstance(tree, dict):
        if "pages" in tree:
            return tree["pages"]
        for sub in tree.values():
            found = _first_pages(sub)
            if found is not None:
                return found
    return None


def lowerings_line(eng) -> str:
    """One line naming the lowering every op of this engine resolved to
    (:func:`repro.nn.context.lowerings`)."""
    low = lowerings(eng.ctx, pages=_first_pages(eng.cache), spec=eng.spec)
    if eng.cfg.family == "ssm":         # no attention layer to lower
        low = {k: v for k, v in low.items() if "attention" not in k}
    return "lowerings: " + " ".join(f"{k}={v}" for k, v in low.items())


def build_engine(args, cfg, ctx, mesh, params):
    """``(engine, fleet)`` for parsed serve arguments; ``fleet`` is None
    unless ``--replicas`` > 1 or ``--standby-dir`` asks for one (the
    engine is then its first replica).  Call under ``use_mesh(mesh)``."""
    spec_draft = None
    if args.spec_draft:
        d_cfg = get_config(args.spec_draft)
        if args.smoke:
            d_cfg = d_cfg.smoke()
        d_params = get_family(d_cfg).init(
            jax.random.PRNGKey(args.seed + 1), d_cfg)
        spec_draft = (d_cfg, d_params, ctx)
    max_len = args.prompt_len + args.gen_len + 1

    def knob(v):
        return "auto" if v == "auto" else int(v)

    eng_kw = dict(batch=args.batch,
                  max_len=max_len, kv_bits=args.kv_bits,
                  prefill_chunk=args.prefill_chunk, seed=args.seed,
                  paged=args.paged, page_size=args.page_size,
                  num_pages=args.num_pages,
                  kv_split=knob(args.kv_split),
                  pages_per_step=knob(args.pages_per_step),
                  prefix_cache=args.prefix_cache,
                  autotune=args.autotune,
                  spec=args.spec,
                  spec_k=args.spec_k, spec_draft=spec_draft,
                  spec_ngram=args.spec_ngram, preempt=args.preempt,
                  shed_threshold=args.shed_threshold,
                  class_quotas=_parse_class_quotas(args.class_quota),
                  slo_targets=(
                      {"realtime": {"ttft_s": args.slo_ttft_s,
                                    "tok_per_s": args.slo_tok_per_s}}
                      if (args.slo_ttft_s is not None
                          or args.slo_tok_per_s is not None) else None),
                  durable_dir=args.durable_dir,
                  snapshot_every=args.snapshot_every)

    def make_engine(**over):
        return Engine(cfg, ctx, params, mesh, **dict(eng_kw, **over))

    if args.replicas > 1 or args.standby_dir is not None:
        from .fleet import Fleet
        # the fleet owns durability (primary journals under
        # --standby-dir); replicas sharing one --durable-dir would
        # clobber each other's journal
        eng_kw["durable_dir"] = None
        fleet = Fleet(make_engine, args.replicas,
                      standby_dir=args.standby_dir)
        return fleet.replicas[0], fleet
    return make_engine(), None


def make_prompts(cfg, args) -> List[np.ndarray]:
    """``--requests`` synthetic prompts of ``--prompt-len`` tokens."""
    src = SyntheticLM(cfg.vocab, seed=args.seed)
    return [src.tokens(i, 1, args.prompt_len)[0, :-1]
            for i in range(args.requests)]


def drive(eng, fleet, prompts, args) -> tuple:
    """Serve ``prompts`` to completion; returns ``(engine, generated
    tokens, decode block)`` — the engine, because a fleet promotion may
    have swapped it."""
    # explicit flag > autotuner-resolved block > the legacy default
    block = max(1, args.decode_block if args.decode_block is not None
                else (eng.decode_block or 8))
    gen_tokens = 0
    # continuous batching through the admission queue: every request
    # is submitted up front; step_many retires finished slots and
    # admits whatever the freed lanes (and, paged, freed pages)
    # cover, one block's latency after they free up
    if fleet is not None:
        for p in prompts:
            fleet.submit(p, gen_len=args.gen_len,
                         temperature=args.temperature,
                         top_k=args.top_k,
                         deadline_s=args.deadline_s,
                         priority=args.priority_class)
        fleet.try_admit()
        fleet.drain(block=block)
        eng = fleet.replicas[0]     # promotion may have swapped it
        gen_tokens = sum(
            s["gen_tokens"] for s in fleet.stats()["per_replica"]
            if s is not None)
    else:
        for p in prompts:
            eng.submit(p, gen_len=args.gen_len,
                       temperature=args.temperature, top_k=args.top_k,
                       deadline_s=args.deadline_s,
                       priority=args.priority_class)
        eng.try_admit()
        while eng.live.any() or eng.waiting:
            _, block_live = eng.step_many(block)
            gen_tokens += int(block_live.sum())
        eng.retire_finished()
    return eng, gen_tokens, block


def main(argv=None):
    args = parse_args(argv)
    configure_compile_cache()
    cfg, ctx, mesh = setup(args)
    with use_mesh(mesh):
        params = serving_params(cfg, ctx, mesh, seed=args.seed)
        eng, fleet = build_engine(args, cfg, ctx, mesh, params)
        print(lowerings_line(eng))
        prompts = make_prompts(cfg, args)
        t0 = time.perf_counter()
        eng, gen_tokens, block = drive(eng, fleet, prompts, args)
        dt = time.perf_counter() - t0
        paged_note = (f" paged(ps={eng.allocator.page_size},"
                      f"pages={eng.allocator.num_pages},"
                      f"kv_split={eng.kv_split},"
                      f"pages_per_step={eng.pages_per_step})"
                      if args.paged else " dense")
        spec_note = (f" spec(k={eng.spec_k},"
                     f"draft={args.spec_draft or 'ngram'})"
                     if args.spec else "")
        st = eng.stats()
        served = (len(fleet.results) if fleet is not None
                  else len(eng.done))
        print(f"served {served} requests, {gen_tokens} tokens in "
              f"{dt:.2f}s ({gen_tokens / dt:.1f} tok/s), "
              f"quant={args.quant} lut={args.lut} kv_bits={args.kv_bits} "
              f"decode_block={block}{paged_note}{spec_note} "
              f"peak_live={st['peak_live']}")
        if fleet is not None:
            fs = fleet.stats()
            print(f"-- fleet: {args.replicas} replicas "
                  f"(states {','.join(fs['states'])}), "
                  f"standby={'on' if fs['standby'] else 'off'}, "
                  f"deaths={fs['deaths']} promotions={fs['promotions']} "
                  f"redispatched={fs['redispatched']}")
        print_stats_table(st)
    return fleet.results if fleet is not None else eng.done


def _parse_class_quotas(specs) -> Optional[dict]:
    """``--class-quota CLASS:KIND=FRACTION`` strings -> the nested dict
    :func:`normalize_class_quotas` validates (None when no flag given)."""
    if not specs:
        return None
    quotas: Dict[str, Dict[str, float]] = {}
    for spec in specs:
        head, sep, val = spec.partition("=")
        cls, csep, kind = head.partition(":")
        if not sep or not csep or not cls or not kind:
            raise SystemExit(
                f"--class-quota {spec!r}: expected CLASS:KIND=FRACTION "
                f"(e.g. realtime:floor=0.25)")
        try:
            frac = float(val)
        except ValueError:
            raise SystemExit(
                f"--class-quota {spec!r}: fraction {val!r} is not a number")
        quotas.setdefault(cls, {})[kind] = frac
    return normalize_class_quotas(quotas)


def print_stats_table(st: dict) -> None:
    """Summary table of :meth:`Engine.stats` rows (serve CLI + examples)."""
    tps = st["decode_tok_per_s"]
    rows = [("requests served", f"{st['requests']}"),
            ("peak concurrent", f"{st['peak_live']}"),
            ("generated tokens", f"{st['gen_tokens']}"),
            # None = no measurable decode interval; "n/a" beats a
            # fictitious 0.0 that reads as a stalled engine
            ("decode tok/s", "n/a" if tps is None else f"{tps:.1f}")]
    if "ttft_mean_s" in st:
        rows.append(("mean TTFT", f"{st['ttft_mean_s'] * 1e3:.1f} ms"))
    if "uptime_s" in st:
        rows.append(("uptime", f"{st['uptime_s']:.2f} s"))
    if "accepted_per_step" in st:
        rows.append(("verify rounds", f"{st['verify_steps']}"))
        rows.append(("drafts accepted/round",
                     f"{st['accepted_per_step']:.2f}"))
    if st.get("autotune", "off") != "off":
        src = st.get("autotune_source", st["autotune"])
        rows.append(("autotune", f"{st['autotune']} ({src})"))
    if "spec_k" in st:
        rows.append(("spec k (now/cap/re-jits)",
                     f"{st['spec_k']}/{st['spec_k_init']}"
                     f"/{st['spec_k_rejits']}"))
    if "decode_block" in st:
        rows.append(("resolved decode block", f"{st['decode_block']}"))
    if "kv_split" in st:
        rows.append(("kv split / pages per step",
                     f"{st['kv_split']} / {st['pages_per_step']}"))
    for key, label in (("prefix_hits", "prefix-cache hits"),
                       ("prefix_tokens_saved", "prefill tokens skipped"),
                       ("cow_copies", "CoW page copies"),
                       ("shared_pages", "shared pages now"),
                       ("prefix_index_pages", "cached prefix pages"),
                       ("preemptions", "preemptions"),
                       ("spilled_pages", "pages spilled"),
                       ("cancellations", "cancellations"),
                       ("timeouts", "timeouts"),
                       ("failures", "failures"),
                       ("replays", "fault replays"),
                       ("recoveries", "recoveries"),
                       ("journal_lag_records", "journal lag (records)"),
                       ("shed_spec_rounds", "spec rounds shed"),
                       ("straggler_blocks", "straggler blocks")):
        if st.get(key):
            rows.append((label, f"{st[key]}"))
    # per-class lines only when more than one class saw traffic (or an
    # SLO target is set): single-class runs already read off the totals
    classes = st.get("classes", {})
    if len(classes) > 1 or "slo_targets" in st:
        for name, c in classes.items():
            p99 = c.get("ttft_p99_s")
            rows.append((
                f"class {name}",
                f"{c['requests']} done, {c['queued']} queued, "
                f"{c['preemptions']} preempted"
                + (f", p99 TTFT {p99 * 1e3:.1f} ms"
                   if p99 is not None else "")))
    width = max(len(k) for k, _ in rows)
    print("-- serving stats " + "-" * (width + 8))
    for k, v in rows:
        print(f"  {k:<{width}}  {v}")


if __name__ == "__main__":
    main()
