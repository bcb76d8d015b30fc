"""Serving conformance suite: paged engine ≡ dense engine, byte for byte.

The paged KV cache (shared page pool + block tables + free-list
allocator + admission queue) must be *observationally invisible*: for
the same submitted requests, the paged engine emits exactly the token
streams the dense engine does — for every serving family (lm KV pages,
hybrid pages-KV-only, ssm no-KV) under f32 and pre-quantized int8
weights, including requests admitted mid-stream onto freshly recycled
pages and prompts whose pages are physically non-contiguous.

Plus the allocator's own invariants (hypothesis-stub sweeps), the
``add_requests`` long-prompt rejection fix, the engine's admission
and transfer counters, its first-token prefill program against the
full-logits prefill step, and its lane-selected prefill calls against
full-width ones.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_config
from repro.core.precision import PrecisionPolicy
from repro.core.qtypes import FixedPointType
from repro.dist.constrain import use_mesh
from repro.launch.mesh import make_local_mesh
from repro.launch.paging import PageAllocator
from repro.launch.serve import Engine, quantize_for_serving
from repro.models.api import get_family
from repro.nn.attention import select_lanes
from repro.nn.context import QuantContext
from repro.train.step import build_prefill_step

ARCHS = {"lm": "gemma-2b", "ssm": "mamba2-370m", "hybrid": "zamba2-1.2b"}
_CACHE = {}


def _setup(family: str, quant: str):
    """(cfg, ctx, params, mesh) per (family, quant) — built once."""
    key = (family, quant)
    if key not in _CACHE:
        cfg = get_config(ARCHS[family]).smoke()
        if quant == "int8":
            ctx = QuantContext(mode="int8",
                               policy=PrecisionPolicy.uniform(
                                   FixedPointType(8, 4)),
                               compute_dtype=jnp.float32)
        else:
            ctx = QuantContext(compute_dtype=jnp.float32)
        fam = get_family(cfg)
        params = fam.init(jax.random.PRNGKey(0), cfg)
        if quant == "int8":
            params = quantize_for_serving(params, ctx)
        _CACHE[key] = (cfg, ctx, params, make_local_mesh())
    return _CACHE[key]


def _serve(setup, prompts, *, gen_len=6, block=4, batch=2, max_len=32,
           **kw):
    """Submit everything, run blocks to drain, return the done streams.

    ``step_many`` performs the continuous-batching admission: finished
    slots retire and queued requests take their lanes/pages one block
    after they free up."""
    cfg, ctx, params, mesh = setup
    with use_mesh(mesh):
        eng = Engine(cfg, ctx, params, mesh, batch=batch, max_len=max_len,
                     **kw)
        for p in prompts:
            eng.submit(p, gen_len=gen_len)
        eng.try_admit()
        while eng.live.any() or eng.waiting:
            eng.step_many(block)
        eng.retire_finished()
    return eng


def _prompts(cfg, lens, seed=0):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, cfg.vocab, (n,)) for n in lens]


# ===========================================================================
class TestPagedDenseConformance:
    """Byte-identical greedy streams, all families × weight precisions."""

    @pytest.mark.parametrize("family,quant", [
        ("lm", "f32"),
        ("ssm", "f32"),
        pytest.param("lm", "int8", marks=pytest.mark.slow),
        pytest.param("ssm", "int8", marks=pytest.mark.slow),
        pytest.param("hybrid", "f32", marks=pytest.mark.slow),
        pytest.param("hybrid", "int8", marks=pytest.mark.slow),
    ])
    def test_paged_matches_dense(self, family, quant):
        setup = _setup(family, quant)
        prompts = _prompts(setup[0], (9, 5, 12, 3))
        dense = _serve(setup, prompts)
        paged = _serve(setup, prompts, paged=True, page_size=8)
        assert paged.done == dense.done
        assert len(paged.done) == len(prompts)
        assert paged.allocator.used_pages == 0        # all pages returned

    @pytest.mark.slow
    def test_paged_matches_dense_int8_kv(self):
        """int8 KV *pages* (payload + per-token scale pages)."""
        setup = _setup("lm", "f32")
        prompts = _prompts(setup[0], (9, 5, 12))
        dense = _serve(setup, prompts, kv_bits=8)
        paged = _serve(setup, prompts, kv_bits=8, paged=True, page_size=8)
        assert paged.done == dense.done

    def test_midblock_finish_admit_recycles_pages(self):
        """A tight pool: the queued request is admitted the moment a
        finishing request's pages return — onto *recycled* pages whose
        stale contents must never leak into its stream."""
        setup = _setup("lm", "f32")
        prompts = _prompts(setup[0], (10, 10, 10), seed=1)
        # 16-token budgets (10 + 6) = 4 pages each; 8 pages = exactly two
        # concurrent requests, so request 3 runs entirely on recycled pages
        paged = _serve(setup, prompts, gen_len=6, max_len=24,
                       paged=True, page_size=4, num_pages=8)
        dense = _serve(setup, prompts, gen_len=6, max_len=24)
        assert paged.done == dense.done
        assert paged.counters["peak_live"] == 2

    def test_prompt_spans_noncontiguous_pages(self):
        """A request admitted after an early finish inherits freed page
        ids out of order — its logical prompt spans physically
        non-contiguous pages and must still decode identically."""
        setup = _setup("lm", "f32")
        cfg = setup[0]
        prompts = _prompts(cfg, (4, 10, 14), seed=2)
        cfg_kw = dict(gen_len=6, max_len=24, block=2)
        paged = _serve(setup, prompts, paged=True, page_size=4,
                       num_pages=11, **cfg_kw)
        # request 0 (4+6=10 tokens, 3 pages) finishes first; request 2
        # (14+6=20 tokens, 5 pages) reuses its LIFO-freed pages plus
        # fresh ones — physically out of order
        pages3 = paged._slot_pages  # noqa: SLF001 — drained, must be empty
        assert pages3 == {}
        dense = _serve(setup, prompts, **cfg_kw)
        assert paged.done == dense.done

    def test_admission_waits_for_pages_not_lanes(self):
        """With a free lane but an empty pool, a request waits; it is
        admitted as soon as freed pages cover its budget — and still
        produces exactly a fresh engine's stream."""
        setup = _setup("lm", "f32")
        cfg, ctx, params, mesh = setup
        prompts = _prompts(cfg, (10, 10, 10), seed=3)
        with use_mesh(mesh):
            eng = Engine(cfg, ctx, params, mesh, batch=3, max_len=24,
                         paged=True, page_size=4, num_pages=8)
            for p in prompts:
                eng.submit(p, gen_len=6)
            eng.try_admit()
            # three free lanes, but pages only cover two 4-page requests
            assert int(eng.live.sum()) == 2 and len(eng.waiting) == 1
            free_before = eng.allocator.free_pages
            assert free_before == 0
            while eng.live.any() or eng.waiting:
                eng.step_many(4)
            eng.retire_finished()

            solo = Engine(cfg, ctx, params, mesh, batch=3, max_len=24,
                          paged=True, page_size=4, num_pages=8)
            solo.submit(prompts[2], gen_len=6)
            solo.try_admit()
            while solo.live.any():
                solo.step_many(4)
            solo.retire_finished()
        assert eng.counters["admitted"] == 3
        assert eng.done[-1] == solo.done[0]


# ===========================================================================
class TestLongPromptRejection:
    """`add_requests` must reject prompts the cache cannot hold instead
    of silently clamp-writing their tail into the last rows."""

    @pytest.mark.parametrize("paged", [False, True])
    def test_add_requests_rejects_oversized_prompt(self, paged):
        setup = _setup("lm", "f32")
        cfg, ctx, params, mesh = setup
        prompt = _prompts(cfg, (33,))[0]         # max_len is 32
        with use_mesh(mesh):
            eng = Engine(cfg, ctx, params, mesh, batch=2, max_len=32,
                         paged=paged)
            with pytest.raises(ValueError, match="does not fit"):
                eng.add_requests({0: prompt}, gen_len=4)
            # nothing was admitted: the engine stays fully idle
            assert not eng.live.any() and eng.outputs == [None, None]
            if paged:
                assert eng.allocator.used_pages == 0
            # a fitting prompt still serves normally afterwards
            eng.add_requests({0: prompt[:8]}, gen_len=4)
            eng.step_many(4)
        assert len(eng.outputs[0]) == 4

    def test_submit_rejects_oversized_prompt(self):
        setup = _setup("lm", "f32")
        cfg, ctx, params, mesh = setup
        with use_mesh(mesh):
            eng = Engine(cfg, ctx, params, mesh, batch=2, max_len=16)
            with pytest.raises(ValueError, match="does not fit"):
                eng.submit(_prompts(cfg, (17,))[0])
            assert not eng.waiting

    def test_submit_rejects_request_larger_than_pool(self):
        """A request whose budget exceeds the whole pool would block the
        FIFO head forever — rejected at submit time."""
        setup = _setup("lm", "f32")
        cfg, ctx, params, mesh = setup
        with use_mesh(mesh):
            eng = Engine(cfg, ctx, params, mesh, batch=2, max_len=32,
                         paged=True, page_size=4, num_pages=4)
            with pytest.raises(ValueError, match="pool only has"):
                eng.submit(_prompts(cfg, (8,))[0], gen_len=12)  # 5 pages
            eng.submit(_prompts(cfg, (8,))[0], gen_len=8)       # 4: fits
            assert len(eng.waiting) == 1

    def test_direct_admission_oom_is_atomic(self):
        """A slot-addressed add_requests that cannot get pages raises
        BEFORE touching allocator or engine state."""
        setup = _setup("lm", "f32")
        cfg, ctx, params, mesh = setup
        prompts = _prompts(cfg, (8, 8))
        with use_mesh(mesh):
            eng = Engine(cfg, ctx, params, mesh, batch=2, max_len=32,
                         paged=True, page_size=4, num_pages=5)
            with pytest.raises(MemoryError, match="exhausted"):
                eng.add_requests({0: prompts[0], 1: prompts[1]}, gen_len=4)
            assert eng.allocator.used_pages == 0
            assert not eng.live.any()
            # the pool still serves a fitting admission afterwards
            eng.add_requests({0: prompts[0]}, gen_len=4)
            eng.step_many(4)
        assert len(eng.outputs[0]) == 4


# ===========================================================================
class TestPageAllocatorProperties:
    """Free-list invariants under hypothesis-stub interleaving sweeps."""

    @settings(max_examples=30, deadline=None)
    @given(st.integers(1, 64), st.integers(1, 16), st.integers(0, 2 ** 16))
    def test_interleaved_alloc_free_never_double_assigns(
            self, num_pages, page_size, seed):
        rs = np.random.RandomState(seed)
        alloc = PageAllocator(num_pages, page_size)
        held = {}
        outstanding = set()
        for step in range(60):
            if held and (rs.rand() < 0.4 or alloc.free_pages == 0):
                owner = rs.choice(sorted(held))
                pages = held.pop(owner)
                outstanding.difference_update(pages)
                alloc.free(pages)
            else:
                n = int(rs.randint(0, alloc.free_pages + 1))
                pages = alloc.alloc(n, owner=step)
                # a page may never be assigned twice concurrently
                assert not (outstanding & set(pages))
                assert len(set(pages)) == len(pages)
                outstanding.update(pages)
                if pages:
                    held[step] = pages
            assert alloc.used_pages == len(outstanding)
            assert alloc.free_pages == num_pages - len(outstanding)

    @settings(max_examples=30, deadline=None)
    @given(st.integers(2, 64), st.integers(0, 2 ** 16))
    def test_freed_pages_immediately_reusable(self, num_pages, seed):
        rs = np.random.RandomState(seed)
        alloc = PageAllocator(num_pages, 4)
        a = alloc.alloc(num_pages)               # drain the pool
        assert not alloc.can_alloc(1)
        give_back = [p for p in a if rs.rand() < 0.5]
        alloc.free(give_back)
        # everything just freed is claimable again in one shot, now
        b = alloc.alloc(len(give_back))
        assert sorted(b) == sorted(give_back)

    @settings(max_examples=30, deadline=None)
    @given(st.integers(1, 64), st.integers(1, 40), st.integers(0, 2 ** 16))
    def test_no_spurious_oom_while_free_covers_need(self, num_pages, steps,
                                                    seed):
        """The dense layout's failure mode — enough total memory but no
        whole slot free — must not exist: any request with ``need <=
        free_pages`` succeeds, regardless of alloc/free history."""
        rs = np.random.RandomState(seed)
        alloc = PageAllocator(num_pages, 8)
        held = []
        for _ in range(steps):
            if held and rs.rand() < 0.5:
                alloc.free(held.pop(rs.randint(len(held))))
            need = int(rs.randint(0, num_pages + 1))
            if need <= alloc.free_pages:
                held.append(alloc.alloc(need))   # must never raise
            else:
                with pytest.raises(MemoryError):
                    alloc.alloc(need)

    def test_tokens_to_pages_rounding(self):
        alloc = PageAllocator(8, 16)
        assert [alloc.pages_for(t) for t in (0, 1, 16, 17, 32)] \
            == [0, 1, 1, 2, 2]

    def test_double_free_rejected(self):
        alloc = PageAllocator(4, 8)
        pages = alloc.alloc(2)
        alloc.free(pages)
        with pytest.raises(ValueError):
            alloc.free(pages)

    def test_failed_free_is_atomic(self):
        """A free() mixing valid and already-free ids must raise WITHOUT
        half-freeing the valid ones — the idempotent-double-free guard
        that keeps preempt/restore cycles from listing a page twice."""
        alloc = PageAllocator(8, 4)
        held = alloc.alloc(4, owner="a")
        freed = held[:2]
        alloc.free(freed)
        before = alloc.state()
        with pytest.raises(ValueError, match="not allocated"):
            alloc.free([held[2], freed[0]])          # valid + double-free
        with pytest.raises(ValueError, match="duplicate"):
            alloc.free([held[2], held[2]])           # in-call duplicate
        assert alloc.state() == before               # untouched either way

    @settings(max_examples=30, deadline=None)
    @given(st.integers(2, 48), st.integers(0, 2 ** 16))
    def test_spill_adopt_interleavings_never_double_assign(
            self, num_pages, seed):
        """Preempt/resume as the allocator sees it: random alloc /
        free / spill(owner) / adopt(spilled ids) interleavings.  The
        invariants: a spill returns exactly the owner's pages, an adopt
        claims exactly the requested free ids, and no page is ever
        assigned to two owners at once across any cycle."""
        rs = np.random.RandomState(seed)
        alloc = PageAllocator(num_pages, 4)
        held: dict = {}                  # owner -> pages on the "device"
        spilled: dict = {}               # owner -> pages copied to host
        for step in range(80):
            ops = ["alloc"]
            if held:
                ops += ["free", "spill"]
            if spilled:
                ops += ["adopt"]
            op = ops[rs.randint(len(ops))]
            if op == "alloc":
                n = int(rs.randint(0, alloc.free_pages + 1))
                pages = alloc.alloc(n, owner=("r", step))
                if pages:
                    held[("r", step)] = pages
            elif op == "free":
                owner = sorted(held)[rs.randint(len(held))]
                alloc.free(held.pop(owner))
            elif op == "spill":
                owner = sorted(held)[rs.randint(len(held))]
                pages = alloc.spill(owner)
                assert sorted(pages) == sorted(held.pop(owner))
                spilled[owner] = pages
            else:                        # adopt: resume a spilled victim
                owner = sorted(spilled)[rs.randint(len(spilled))]
                pages = spilled.pop(owner)
                free_set = set(alloc.state()["free"])
                if set(pages) <= free_set:
                    alloc.adopt(pages, owner=owner)
                    held[owner] = pages
                else:                    # ids re-issued meanwhile: the
                    with pytest.raises(ValueError):  # claim must refuse
                        alloc.adopt(pages, owner=owner)
            # global invariant: held owners partition the used pages
            used = [p for pages in held.values() for p in pages]
            assert len(set(used)) == len(used)
            assert alloc.used_pages == len(used)
            for owner, pages in held.items():
                assert sorted(alloc.pages_of(owner)) == sorted(pages)

    @settings(max_examples=20, deadline=None)
    @given(st.integers(2, 32), st.integers(0, 2 ** 16))
    def test_state_round_trip_preserves_alloc_order(self, num_pages, seed):
        """load_state(state()) must reproduce the free-list ORDER: the
        next allocations after a restore hand out the same physical ids
        the original would — engine replay determinism rests on it."""
        rs = np.random.RandomState(seed)
        alloc = PageAllocator(num_pages, 4)
        for step in range(12):
            if rs.rand() < 0.5 and alloc.free_pages:
                alloc.alloc(int(rs.randint(1, alloc.free_pages + 1)),
                            owner=step)
            else:
                owners = {o for o in alloc.state()["owner"].values()}
                if owners:
                    alloc.spill(sorted(owners)[0])
        saved = alloc.state()
        twin = PageAllocator(num_pages, 4)
        twin.load_state(saved)
        n = min(3, alloc.free_pages)
        assert twin.alloc(n, owner="x") == alloc.alloc(n, owner="x")

    def test_load_state_rejects_non_partition(self):
        alloc = PageAllocator(4, 4)
        with pytest.raises(ValueError, match="partition"):
            alloc.load_state({"free": [0, 1], "owner": {1: "a", 3: "b"}})

    def test_adopt_rejects_assigned_or_unknown_ids(self):
        alloc = PageAllocator(4, 4)
        mine = alloc.alloc(2, owner="a")
        before = alloc.state()
        with pytest.raises(ValueError, match="already assigned"):
            alloc.adopt([mine[0]], owner="b")
        with pytest.raises(ValueError, match="not a valid free page"):
            alloc.adopt([99], owner="b")
        assert alloc.state() == before               # atomic: no change


# ===========================================================================
class _Clock:
    """A clock that moves only when told to."""

    def __init__(self, t=0.0):
        self.t = float(t)

    def __call__(self):
        return self.t


class TestServingCounters:
    """The engine's admission and transfer counters are exact at smoke
    size, and its step programs keep the names the benchmark's trace
    reduction matches."""

    def _engine(self, **kw):
        cfg, ctx, params, mesh = _setup("lm", "f32")
        return Engine(cfg, ctx, params, mesh, batch=2, max_len=32,
                      paged=True, page_size=8, prefill_chunk=8, **kw)

    def test_prefill_and_fetch_counters(self):
        """Two prompts of 12 and 16 tokens: two chunks of 8 over both
        lanes (width 2), then one decode block of 2 steps."""
        cfg, _, _, mesh = _setup("lm", "f32")
        prompts = _prompts(cfg, (12, 16), seed=4)
        with use_mesh(mesh):
            eng = self._engine()
            for p in prompts:
                eng.submit(p, gen_len=4)
            eng.try_admit()
            c = dict(eng.counters)
            eng.step_many(2)
        assert c["prefill_calls"] == 2
        assert c["prefill_rows"] == 2 * 2 * 8            # calls x W x chunk
        assert c["prefill_width_calls"] == {2: 2}
        assert c["prefill_tokens"] == 12 + 16
        first = 2 * (2 * 4)                # (W,) int32 first tokens per call
        assert c["host_fetch_bytes"] == first
        # block (2, B) int32 and its live mask; tokens (B, 1), pos (B,)
        # int32; live and fault (B,) bool
        block = 2 * 2 * 4 + 2 * 2 + 2 * 4 + 2 * 4 + 2 + 2
        assert eng.counters["host_fetch_bytes"] == first + block
        st = eng.stats()
        assert st["prefill_calls"] == 2
        assert st["prefill_width_calls"] == {2: 2}
        assert st["prefill_useful_share"] == pytest.approx(28 / 32)
        assert st["host_fetch_bytes"] == first + block

    def test_prefill_tokens_count_suffixes_under_prefix_hits(self):
        """A prompt whose first two pages are cached ingests only its
        suffix: 4 tokens in one chunk, over its own lane alone."""
        cfg, _, _, mesh = _setup("lm", "f32")
        first = _prompts(cfg, (16,), seed=5)[0]
        second = np.concatenate([first, _prompts(cfg, (4,), seed=6)[0]])
        with use_mesh(mesh):
            eng = self._engine(prefix_cache=True)
            eng.submit(first, gen_len=2)
            eng.try_admit()
            eng.step_many(2)
            eng.retire_finished()
            c = dict(eng.counters)
            eng.submit(second, gen_len=2)
            eng.try_admit()
        assert eng.counters["prefix_hits"] == c["prefix_hits"] + 1
        assert eng.counters["prefill_tokens"] - c["prefill_tokens"] == 4
        assert eng.counters["prefill_calls"] - c["prefill_calls"] == 1
        assert eng.counters["prefill_rows"] - c["prefill_rows"] == 1 * 8
        # the first prompt's two chunks and the suffix's one, each at
        # width 1
        assert eng.counters["prefill_width_calls"] == {1: 3}

    @pytest.mark.parametrize("case", ["fresh", "prefix_suffix"])
    def test_prefill_kernel_counters(self, case):
        """Prompts of 12 and 16 tokens in chunks of 8: per call and lane
        with prompt rows, ``prefill_kv_tokens`` adds the context at the
        chunk's end (8 + 8, then 12 + 16) and ``prefill_attn_pairs`` r*s +
        r(r+1)/2 for r rows from position s (36 + 36, then 8*4 + 10 and
        8*8 + 36), n(n+1)/2 of each whole prompt.  A prefix hit ingests
        only its suffix: after a 16-token prompt (8 + 16 tokens read), 4
        rows from position 16 read 20 tokens and make 16*4 + 10 pairs."""
        cfg, _, _, mesh = _setup("lm", "f32")
        with use_mesh(mesh):
            eng = self._engine(prefix_cache=case == "prefix_suffix")
            if case == "fresh":
                for p in _prompts(cfg, (12, 16), seed=4):
                    eng.submit(p, gen_len=4)
                want = (8 + 8 + 12 + 16, 36 + 36 + (8 * 4 + 10)
                        + (8 * 8 + 36))
                assert want[1] == 12 * 13 // 2 + 16 * 17 // 2
            else:
                first = _prompts(cfg, (16,), seed=5)[0]
                eng.submit(first, gen_len=2)
                eng.try_admit()
                eng.step_many(2)
                eng.retire_finished()
                eng.submit(np.concatenate(
                    [first, _prompts(cfg, (4,), seed=6)[0]]), gen_len=2)
                want = (8 + 16 + 20, 16 * 17 // 2 + 16 * 4 + 4 * 5 // 2)
            eng.try_admit()
        c = eng.counters
        assert (c["prefill_kv_tokens"], c["prefill_attn_pairs"]) == want
        st = eng.stats()
        assert (st["prefill_kv_tokens"], st["prefill_attn_pairs"]) == want

    def test_queue_wait_from_the_clock(self):
        """Requests submitted at t=1 and t=2 and admitted at t=5 waited
        4 s and 3 s; a direct add waited none."""
        cfg, _, _, mesh = _setup("lm", "f32")
        prompts = _prompts(cfg, (6, 6, 6), seed=7)
        clock = _Clock(1.0)
        with use_mesh(mesh):
            eng = self._engine(clock=clock)
            ids = [eng.submit(prompts[0], gen_len=2)]
            clock.t = 2.0
            ids.append(eng.submit(prompts[1], gen_len=2))
            clock.t = 5.0
            eng.try_admit()
            assert eng.counters["queue_wait_s"] == pytest.approx(7.0)
            eng.step_many(2)
            eng.retire_finished()
            eng.add_requests({0: prompts[2]}, gen_len=2)
            eng.step_many(2)
            eng.retire_finished()
        assert eng.counters["queue_wait_s"] == pytest.approx(7.0)
        rows = {r["id"]: r for r in eng.request_log}
        assert rows[ids[0]]["queue_s"] == pytest.approx(4.0)
        assert rows[ids[1]]["queue_s"] == pytest.approx(3.0)
        assert len(rows) == 3 and set(ids) <= set(eng.results)
        direct = [r for i, r in rows.items() if i not in ids]
        assert direct[0]["queue_s"] == 0.0
        assert eng.stats()["queue_wait_mean_s"] == pytest.approx(7.0 / 3)

    @pytest.mark.parametrize("program", ["prefill_step", "decode_loop"])
    def test_step_programs_keep_their_names(self, program):
        """The benchmark finds the step programs in a device trace by
        these names (``decode_step_ms.chat``, ``prefill_call_ms.chat``,
        ``paged_attn_roofline``): a rename fails here."""
        cfg, _, _, mesh = _setup("lm", "f32")
        with use_mesh(mesh):
            eng = self._engine()
            eng.submit(_prompts(cfg, (6,), seed=8)[0], gen_len=4)
            eng.try_admit()
            eng.step_many(2)
            b = eng.batch
            if program == "prefill_step":
                lowered = eng.prefill.lower(
                    eng.params, {"tokens": jnp.zeros((b, 8), jnp.int32)},
                    eng.cache, jnp.zeros((b,), jnp.int32),
                    jnp.zeros((b,), jnp.int32))
            else:
                lowered = eng._loops[2].lower(  # noqa: SLF001
                    eng.params, eng.cache, jnp.zeros((b, 1), jnp.int32),
                    jnp.zeros((b,), jnp.int32), jnp.zeros((b,), bool),
                    jnp.zeros((b,), jnp.int32),
                    {"temperature": jnp.zeros((b,), jnp.float32),
                     "top_k": jnp.zeros((b,), jnp.int32)},
                    None, jnp.int32(0), jnp.int32(eng.eos_id))
        head = lowered.compile().as_text().splitlines()[0]
        assert head.startswith("HloModule ") and program in head.split()[1]


# ===========================================================================
def _full_logits_spy(eng, seen):
    """Wrap the engine's prefill program so that each call also records,
    for each lane in it, its start position and the full-logits prefill
    step's (chunk, vocab) rows on the same inputs."""
    full = jax.jit(build_prefill_step(eng.cfg, eng.ctx))
    inner = eng.prefill

    def prefill(params, batch, cache, pos, last, lanes):
        logits, _ = full(params, batch, select_lanes(cache, lanes), pos)
        seen.append({s: (p, rows) for s, p, rows in zip(
            np.asarray(lanes).tolist(), np.asarray(pos).tolist(),
            np.asarray(logits)) if s < eng.batch})
        return inner(params, batch, cache, pos, last, lanes)

    eng.prefill = prefill


def _keep_block_tables(new, old):
    """``new`` with the block tables of ``old``."""
    return jax.tree_util.tree_map_with_path(
        lambda p, n, o: o if p[-1].key == "block_table" else n, new, old)


def _host_argmax_prefill(eng):
    """Serve the engine's prefill through the full-logits step, with the
    whole logits block fetched and argmaxed on the host."""
    full = jax.jit(build_prefill_step(eng.cfg, eng.ctx))

    def prefill(params, batch, cache, pos, last, lanes):
        logits, new = full(params, batch, select_lanes(cache, lanes), pos)
        logits, last = np.asarray(logits), np.asarray(last)
        rows = logits[np.arange(last.shape[0]), last]
        return (np.argmax(rows, axis=-1).astype(np.int32),
                _keep_block_tables(new, cache))

    eng.prefill = prefill


class TestFirstTokenPrefill:
    """The engine's prefill program returns each lane's first token, the
    argmax of the full-logits prefill step at the lane's last prompt
    row, wherever in the chunk grid that row falls."""

    CHUNK = 8
    LENS = {"one_token": (1,), "mid_chunk": (5,), "chunk_end": (8,),
            "multi_chunk": (20,), "lanes_end_apart": (3, 19)}

    def _engine(self, **kw):
        cfg, ctx, params, mesh = _setup("lm", "f32")
        return Engine(cfg, ctx, params, mesh, batch=2, max_len=32,
                      paged=True, page_size=8, prefill_chunk=self.CHUNK,
                      **kw)

    def _prefix_requests(self, eng, cfg, full_hit):
        """Publish a 16-token prompt, then admit it again (full hit,
        suffix from 15) or with 4 more tokens (suffix from 16), beside
        an unrelated 11-token prompt that starts from 0."""
        base = _prompts(cfg, (16,), seed=9)[0]
        eng.add_requests({0: base}, gen_len=2)
        eng.step_many(2)
        eng.retire_finished()
        tail = base if full_hit else np.concatenate(
            [base, _prompts(cfg, (4,), seed=10)[0]])
        reqs = {0: _prompts(cfg, (11,), seed=11)[0], 1: tail}
        return reqs, {1: 15 if full_hit else 16}

    @pytest.mark.parametrize("case", list(LENS) + [
        "prefix_suffix", "prefix_full_hit", "greedy_streams"])
    def test_first_tokens_are_argmax_of_full_logits(self, case):
        cfg, _, _, mesh = _setup("lm", "f32")
        with use_mesh(mesh):
            if case == "greedy_streams":
                streams = []
                for host_path in (False, True):
                    eng = self._engine()
                    if host_path:
                        _host_argmax_prefill(eng)
                    eng.add_requests(dict(enumerate(
                        _prompts(cfg, (5, 13), seed=12))), gen_len=6)
                    while eng.live.any():
                        eng.step_many(3)
                    streams.append([list(o) for o in eng.outputs])
                assert streams[0] == streams[1]
                assert all(len(o) == 6 for o in streams[0])
                return
            prefix = case.startswith("prefix")
            eng = self._engine(prefix_cache=prefix)
            if prefix:
                reqs, starts = self._prefix_requests(
                    eng, cfg, case == "prefix_full_hit")
            else:
                reqs = dict(enumerate(_prompts(cfg, self.LENS[case],
                                               seed=9)))
                starts = {}
            hits = eng.counters["prefix_hits"]
            seen = []
            _full_logits_spy(eng, seen)
            eng.add_requests(reqs, gen_len=2)
        assert eng.counters["prefix_hits"] - hits == int(prefix)
        assert len(seen) == -(-max(p.shape[0] - starts.get(s, 0)
                                   for s, p in reqs.items()) // self.CHUNK)
        for s, p in reqs.items():
            start = starts.get(s, 0)
            k, row = divmod(p.shape[0] - 1 - start, self.CHUNK)
            pos, logits = seen[k][s]
            assert pos == start + k * self.CHUNK
            assert eng.tokens[s, 0] == np.argmax(logits[row])


# ===========================================================================
class TestFirstTokenHead:
    """The first-token prefill program applies the final norm and the LM
    head to each lane's gathered row only.  At a glm4-shaped smoke size
    (qkv bias, half-width rotary, 16 query heads over one kv head, an
    untied head) it returns exactly the argmax of the full-logits
    program's rows, and holds no (B, chunk, vocab) array."""

    B, CHUNK, VOCAB = 4, 16, 1000

    def _setup(self):
        import dataclasses
        cfg = dataclasses.replace(
            get_config("glm4-9b").smoke(), n_heads=16, n_kv_heads=1,
            head_dim=16, vocab=self.VOCAB)
        assert cfg.qkv_bias and cfg.rope_fraction == 0.5
        assert not cfg.tie_embeddings
        ctx = QuantContext(compute_dtype=jnp.float32)
        params = get_family(cfg).init(jax.random.PRNGKey(3), cfg)
        cache = get_family(cfg).init_cache(cfg, self.B, 64, jnp.float32)
        rs = np.random.RandomState(13)
        tokens = jnp.asarray(rs.randint(0, cfg.vocab, (self.B, self.CHUNK)),
                             jnp.int32)
        pos = jnp.asarray([0, 16, 5, 32], jnp.int32)
        last = jnp.asarray([0, 7, 15, 11], jnp.int32)
        return cfg, ctx, params, ({"tokens": tokens}, cache, pos, last)

    def test_first_tokens_equal_argmax_of_gathered_full_logits(self):
        cfg, ctx, params, (batch, cache, pos, last) = self._setup()
        first = jax.jit(build_prefill_step(cfg, ctx, first_token=True))
        full = jax.jit(build_prefill_step(cfg, ctx))
        tok, c1 = first(params, batch, cache, pos, last)
        logits, c2 = full(params, batch, cache, pos)
        rows = np.asarray(logits)[np.arange(self.B), np.asarray(last)]
        assert tok.dtype == jnp.int32 and tok.shape == (self.B,)
        np.testing.assert_array_equal(np.asarray(tok),
                                      np.argmax(rows, axis=-1))
        for a, b in zip(jax.tree_util.tree_leaves(c1),
                        jax.tree_util.tree_leaves(c2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("form", ["first_token", "full_logits"])
    def test_no_chunk_by_vocab_array(self, form):
        """The full-logits form, the control, does hold one."""
        cfg, ctx, params, (batch, cache, pos, last) = self._setup()
        if form == "first_token":
            step = jax.jit(build_prefill_step(cfg, ctx, first_token=True))
            args = (params, batch, cache, pos, last)
        else:
            step = jax.jit(build_prefill_step(cfg, ctx))
            args = (params, batch, cache, pos)
        hlo = step.lower(*args).compile().as_text()
        shape = f"[{self.B},{self.CHUNK},{self.VOCAB}]"
        assert (shape in hlo) == (form == "full_logits")


# ===========================================================================
def _against_full_width(eng, log):
    """Wrap the engine's prefill program so that each call is also run at
    full width on the same cache: the call's rows in their lanes' rows,
    every other lane at its position with zero tokens, as the dense
    engine runs a call.  Logs (lanes, tokens, full-width tokens, cache
    in, cache out, full-width cache out) per call, on the host."""
    inner = eng.prefill

    def prefill(params, batch, cache, pos, last, lanes):
        sel = np.asarray(lanes)[np.asarray(lanes) < eng.batch]
        n = sel.shape[0]
        toks = np.zeros((eng.batch, batch["tokens"].shape[1]), np.int32)
        toks[sel] = np.asarray(batch["tokens"])[:n]
        at, rows = eng.pos.copy(), np.zeros((eng.batch,), np.int32)
        at[sel], rows[sel] = np.asarray(pos)[:n], np.asarray(last)[:n]
        full_tok, full = inner(params, {"tokens": jnp.asarray(toks)}, cache,
                               jnp.asarray(at), jnp.asarray(rows))
        tok, out = inner(params, batch, cache, pos, last, lanes)
        log.append((np.asarray(lanes), np.asarray(tok),
                    np.asarray(full_tok), *jax.device_get((cache, out, full))))
        return tok, out

    eng.prefill = prefill


class TestLaneSelectedPrefill:
    """On the paged cache each prefill call runs over only the lanes with
    prompt rows in its chunk, at the smallest power-of-two width that
    holds them.  Each call's tokens and its lanes' pages equal those of
    the full-width call on the same inputs; every other page, the
    decoding lanes' among them, is left bit-identical, and pad rows
    write the trash page alone.  On the Pallas kernel (interpret mode)
    and off it."""

    B, CHUNK = 4, 8
    #: per case: call widths of the admission under test
    WIDTHS = {"beside_decoding": {1: 3},
              "wave": {4: 2, 2: 1, 1: 1},
              "wave_int8_pool": {4: 2, 2: 1, 1: 1},
              "prefix_suffix": {2: 1, 1: 1}}

    def _engine(self, kernel, **kw):
        cfg, ctx, params, mesh = _setup("lm", "f32")
        ctx = dataclasses.replace(ctx, force_paged_kernel=kernel)
        return Engine(cfg, ctx, params, mesh, batch=self.B, max_len=48,
                      paged=True, page_size=8, prefill_chunk=self.CHUNK,
                      **kw)

    @pytest.mark.parametrize("kernel", [False, True],
                             ids=["einsum", "kernel"])
    @pytest.mark.parametrize("case", list(WIDTHS))
    def test_calls_equal_full_width(self, case, kernel):
        cfg, _, _, mesh = _setup("lm", "f32")
        with use_mesh(mesh):
            eng = self._engine(kernel, prefix_cache=case == "prefix_suffix",
                               kv_bits=8 if case.endswith("int8_pool")
                               else None)
            if case == "beside_decoding":
                # two lanes decode while a 20-token prompt is admitted
                eng.add_requests(dict(enumerate(_prompts(cfg, (12, 9)))),
                                 gen_len=8)
                eng.step_many(2)
                reqs = {2: _prompts(cfg, (20,), seed=1)[0]}
            elif case == "prefix_suffix":
                # lane 1 ingests a 4-token suffix after a cached 16-token
                # prefix and drops out of the second chunk, which lane
                # 0's fresh 11-token prompt still has rows in
                base = _prompts(cfg, (16,), seed=2)[0]
                eng.add_requests({1: base}, gen_len=2)
                eng.step_many(2)
                eng.retire_finished()
                reqs = {0: _prompts(cfg, (11,), seed=3)[0],
                        1: np.concatenate([base, _prompts(cfg, (4,),
                                                          seed=4)[0]])}
            else:
                # the shorter prompts end first: widths 4, 4 (one pad
                # row), 2, 1
                reqs = dict(enumerate(_prompts(cfg, (5, 13, 21, 30))))
            decoding = np.flatnonzero(eng.live)
            pos0 = eng.pos.copy()
            pages0 = jax.device_get(eng.cache)["dense"]["pages"]
            widths0 = dict(eng.counters["prefill_width_calls"])
            log = []
            _against_full_width(eng, log)
            eng.add_requests(reqs, gen_len=2)
        widths = {w: n - widths0.get(w, 0) for w, n in
                  eng.counters["prefill_width_calls"].items()
                  if n != widths0.get(w, 0)}
        assert widths == self.WIDTHS[case]
        trash = eng.allocator.num_pages
        for lanes, tok, full_tok, c_in, c_out, c_full in log:
            sel = lanes[lanes < self.B]
            assert sel.tolist() == sorted(sel.tolist())
            assert (lanes[sel.shape[0]:] == self.B).all()
            assert lanes.shape[0] == eng._prefill_width(sel.shape[0])
            np.testing.assert_array_equal(tok[:sel.shape[0]], full_tok[sel])
            bt = c_in["dense"]["block_table"]
            np.testing.assert_array_equal(c_out["dense"]["block_table"], bt)
            mine = sorted(set(bt[0, sel].ravel().tolist()) - {trash})
            rest = sorted(set(range(trash)) - set(mine))
            for name, leaf in c_out["dense"]["pages"].items():
                np.testing.assert_array_equal(
                    leaf[:, mine], c_full["dense"]["pages"][name][:, mine])
                np.testing.assert_array_equal(
                    leaf[:, rest], c_in["dense"]["pages"][name][:, rest])
        # the decoding lanes' pages and positions, across the admission
        pages = jax.device_get(eng.cache)["dense"]["pages"]
        for s in decoding:
            assert eng.pos[s] == pos0[s]
            own = sorted(set(eng.block_tables[s].tolist()) - {trash})
            for name, leaf in pages.items():
                np.testing.assert_array_equal(leaf[:, own],
                                              pages0[name][:, own])
        assert all(len(eng.outputs[s]) == 0 for s in reqs)
        assert all(eng.live[s] for s in reqs)

    def test_admissions_compile_no_prefill_program(self):
        """The engine compiles its prefill program once per width (1, 2
        and 4 lanes at a batch of 4) when it is built; admissions of 1
        through 4 lanes, beside decoding lanes or into an idle batch,
        compile none after that."""
        cfg, _, _, mesh = _setup("lm", "f32")
        with use_mesh(mesh):
            eng = self._engine(False)
            assert eng.prefill._cache_size() == 3
            rs = np.random.RandomState(5)
            for group in ((3,), (1, 2), (4,), (2, 2), (1, 1, 1, 1)):
                for n in group:
                    for _ in range(n):
                        eng.submit(rs.randint(0, cfg.vocab,
                                              (rs.randint(1, 30),)),
                                   gen_len=int(rs.randint(1, 10)))
                    eng.try_admit()
                    eng.step_many(3)
                    eng.retire_finished()
            while eng.live.any() or eng.waiting:
                eng.step_many(3)
                eng.retire_finished()
        assert set(eng.counters["prefill_width_calls"]) == {1, 2, 4}
        assert eng.prefill._cache_size() == 3
