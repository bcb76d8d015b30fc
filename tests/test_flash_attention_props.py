"""Property tests for the flash (blocked online-softmax) attention kernel.

``flash_attention_pallas`` runs in interpret mode on CPU and must match
the ``flash_attention_ref`` oracle across the cases its blocking logic
actually has to handle:

* ``Sq``/``Skv`` that are NOT multiples of the ``bq``/``bk`` block shape
  (the padded-tail mask path);
* queries sitting at the tail of a longer KV context (decode-style
  ``Skv > Sq`` with the diagonal shifted by ``q_off``);
* GQA group sizes > 1 (the BlockSpec ``h // group`` index fold);
* block shapes smaller than, equal to, and larger than the sequence.

Sweeps run through the deterministic hypothesis stub.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ref import flash_attention_ref

TOL = dict(rtol=2e-5, atol=2e-5)


def _qkv(b, hq, hkv, sq, skv, d, seed=0):
    rs = np.random.RandomState(seed)
    q = jnp.asarray(rs.randn(b, hq, sq, d), jnp.float32)
    k = jnp.asarray(rs.randn(b, hkv, skv, d), jnp.float32)
    v = jnp.asarray(rs.randn(b, hkv, skv, d), jnp.float32)
    return q, k, v


def _check(q, k, v, *, causal, bq, bk):
    got = flash_attention_pallas(q, k, v, causal=causal, bq=bq, bk=bk,
                                 interpret=True)
    want = flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


# ===========================================================================
class TestPaddedTails:
    """Sq/Skv not multiples of the block shape → masked padding rows."""

    @pytest.mark.parametrize("sq,skv,bq,bk", [
        (5, 5, 4, 4),       # one ragged tail block on both axes
        (9, 9, 4, 4),       # tail of 1 — the off-by-one magnet
        (7, 13, 4, 4),      # ragged AND sq != skv (diagonal shifted)
        (3, 17, 8, 8),      # sq smaller than one block
        (13, 13, 16, 16),   # whole sequence inside one padded block
        (6, 11, 4, 8),      # asymmetric block shapes
    ])
    @pytest.mark.parametrize("causal", [True, False])
    def test_non_multiple_shapes(self, sq, skv, bq, bk, causal):
        q, k, v = _qkv(2, 2, 2, sq, skv, 8)
        _check(q, k, v, causal=causal, bq=bq, bk=bk)

    @settings(max_examples=10, deadline=None)
    @given(st.integers(1, 20), st.integers(0, 12),
           st.sampled_from([2, 4, 8]), st.sampled_from([2, 4, 8]),
           st.integers(0, 2 ** 16))
    def test_sweep_ragged_shapes(self, sq, extra_kv, bq, bk, seed):
        """Random (Sq, Skv >= Sq) against random block shapes: the
        causal diagonal must sit at q_off = Skv - Sq regardless of how
        the blocks tile."""
        skv = sq + extra_kv
        q, k, v = _qkv(1, 2, 1, sq, skv, 8, seed=seed)
        _check(q, k, v, causal=True, bq=bq, bk=bk)


# ===========================================================================
class TestDiagonalBlocks:
    def test_diagonal_mask_within_block(self):
        """bq == bk == Sq: the whole causal mask is elementwise inside
        one diagonal block (no block skipping at all)."""
        q, k, v = _qkv(1, 1, 1, 8, 8, 8, seed=1)
        _check(q, k, v, causal=True, bq=8, bk=8)

    def test_blocks_above_diagonal_are_skipped_correctly(self):
        """Strictly-above-diagonal blocks contribute nothing: a huge
        value planted in a future kv position must not leak."""
        q, k, v = _qkv(1, 1, 1, 8, 8, 4, seed=2)
        v = v.at[0, 0, 6].set(1e4)       # only visible to queries >= 6
        got = flash_attention_pallas(q, k, v, causal=True, bq=2, bk=2,
                                     interpret=True)
        want = flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)
        assert np.abs(np.asarray(got)[0, 0, :6]).max() < 100

    def test_decode_style_tail_queries(self):
        """Skv > Sq: queries are the LAST sq positions (serving chunk)."""
        q, k, v = _qkv(2, 2, 2, 3, 29, 8, seed=3)
        _check(q, k, v, causal=True, bq=2, bk=8)


# ===========================================================================
class TestGQAGroups:
    @pytest.mark.parametrize("hq,hkv", [(2, 1), (4, 2), (8, 2), (6, 3)])
    def test_group_folding(self, hq, hkv):
        """K/V heads are indexed h // group — never broadcast: every
        query head must read its own group's KV."""
        q, k, v = _qkv(2, hq, hkv, 9, 9, 8, seed=4)
        _check(q, k, v, causal=True, bq=4, bk=4)

    def test_groups_see_distinct_kv(self):
        """Give each KV head a distinct constant V: outputs per query
        head must equal their group's constant (softmax mixes only
        within one head's rows)."""
        b, hq, hkv, s, d = 1, 4, 2, 6, 8
        q, k, _ = _qkv(b, hq, hkv, s, s, d, seed=5)
        v = jnp.stack([jnp.full((s, d), float(h + 1))
                       for h in range(hkv)])[None]
        out = np.asarray(flash_attention_pallas(q, k, v, causal=True,
                                                bq=4, bk=4, interpret=True))
        group = hq // hkv
        for h in range(hq):
            np.testing.assert_allclose(out[0, h], h // group + 1.0,
                                       rtol=1e-6)

    @settings(max_examples=8, deadline=None)
    @given(st.sampled_from([(2, 1), (4, 1), (4, 2), (6, 2)]),
           st.integers(2, 12), st.integers(0, 2 ** 16))
    def test_sweep_gqa_vs_ref(self, heads, sq, seed):
        hq, hkv = heads
        q, k, v = _qkv(2, hq, hkv, sq, sq + 4, 8, seed=seed)
        _check(q, k, v, causal=True, bq=4, bk=4)
