"""Sampling determinism suite for the on-device token draw.

The ``sample_tokens`` op is the one stateful-looking step of the fused
decode loop, so its contract is determinism: given (logits, params, key)
the draw is identical standalone, under ``jax.jit``, and inside
``lax.scan`` — and the fused lowering matches the independent sort-based
oracle in ``repro.kernels.ref`` exactly.  Shape/seed sweeps run through
the deterministic hypothesis stub (tests/_hypothesis_stub.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels.ops import sample_tokens
from repro.kernels.ref import sample_tokens_ref
from repro.kernels.sampling import sample_tokens_fused

# the fixed logits fixture the oracle comparison runs on
FIXTURE = np.random.RandomState(1234).randn(6, 96).astype(np.float32)
FIX_TEMP = np.asarray([0.0, 0.5, 0.9, 1.4, 2.0, 0.7], np.float32)
FIX_TOPK = np.asarray([0, 1, 4, 0, 8, 96], np.int32)


def _fix():
    return (jnp.asarray(FIXTURE), jnp.asarray(FIX_TEMP),
            jnp.asarray(FIX_TOPK))


# ===========================================================================
class TestOracleAgreement:
    def test_fused_matches_ref_on_fixture(self):
        logits, temp, topk = _fix()
        for seed in range(16):
            key = jax.random.PRNGKey(seed)
            got = sample_tokens_fused(logits, temp, topk, key)
            want = sample_tokens_ref(logits, temp, topk, key)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_registry_dispatch(self):
        logits, temp, topk = _fix()
        key = jax.random.PRNGKey(0)
        ref = sample_tokens(logits, temp, topk, key, backend="ref")
        fused = sample_tokens(logits, temp, topk, key, backend="pallas")
        default = sample_tokens(logits, temp, topk, key)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(fused))
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(default))

    def test_greedy_slots_are_argmax(self):
        logits, temp, topk = _fix()
        out = np.asarray(sample_tokens_fused(logits, temp, topk,
                                             jax.random.PRNGKey(3)))
        am = np.argmax(FIXTURE, axis=-1)
        assert out[0] == am[0]                  # temperature 0.0 slot
        assert out[1] == am[1]                  # top_k 1 slot
        # no key at all: every slot greedy
        np.testing.assert_array_equal(
            np.asarray(sample_tokens_fused(logits, temp, topk, None)), am)


# ===========================================================================
class TestJitBoundaryDeterminism:
    def test_eager_jit_scan_identical(self):
        """The same per-step keys produce the same draws whether the op
        runs eagerly, jitted, or as a lax.scan body — the property the
        fused decode block relies on to match per-token stepping."""
        logits, temp, topk = _fix()
        base = jax.random.PRNGKey(9)
        steps = 5

        eager = jnp.stack([
            sample_tokens_fused(logits, temp, topk,
                                jax.random.fold_in(base, i))
            for i in range(steps)])

        jitted_one = jax.jit(sample_tokens_fused)
        jit_out = jnp.stack([
            jitted_one(logits, temp, topk, jax.random.fold_in(base, i))
            for i in range(steps)])

        @jax.jit
        def scanned():
            def body(_, i):
                key = jax.random.fold_in(base, i)
                return None, sample_tokens_fused(logits, temp, topk, key)
            _, out = jax.lax.scan(body, None, jnp.arange(steps))
            return out

        np.testing.assert_array_equal(np.asarray(eager), np.asarray(jit_out))
        np.testing.assert_array_equal(np.asarray(eager),
                                      np.asarray(scanned()))

    def test_same_seed_reproduces_across_processes_shape(self):
        """Fixed (key, logits) → fixed draw: rerunning the sampler is
        bit-stable (no hidden global state)."""
        logits, temp, topk = _fix()
        key = jax.random.PRNGKey(123)
        a = np.asarray(sample_tokens_fused(logits, temp, topk, key))
        b = np.asarray(sample_tokens_fused(logits, temp, topk, key))
        c = np.asarray(jax.jit(sample_tokens_fused)(logits, temp, topk, key))
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)


# ===========================================================================
class TestSamplingSemantics:
    @settings(max_examples=15, deadline=None)
    @given(st.integers(1, 5), st.integers(2, 128), st.integers(0, 2 ** 16),
           st.floats(0.05, 3.0), st.integers(0, 12))
    def test_sweep_fused_matches_ref_and_in_range(self, b, v, seed, temp, k):
        rs = np.random.RandomState(seed)
        logits = jnp.asarray(rs.randn(b, v), jnp.float32)
        temps = jnp.full((b,), temp, jnp.float32)
        topks = jnp.full((b,), k, jnp.int32)
        key = jax.random.PRNGKey(seed)
        got = np.asarray(sample_tokens_fused(logits, temps, topks, key))
        want = np.asarray(sample_tokens_ref(logits, temps, topks, key))
        np.testing.assert_array_equal(got, want)
        assert ((0 <= got) & (got < v)).all()

    @settings(max_examples=10, deadline=None)
    @given(st.integers(1, 8), st.integers(0, 2 ** 16))
    def test_samples_stay_inside_top_k(self, k, seed):
        rs = np.random.RandomState(seed)
        logits = rs.randn(3, 64).astype(np.float32)
        topset = np.argsort(-logits, axis=-1)[:, :k]
        temps = jnp.full((3,), 1.5, jnp.float32)
        topks = jnp.full((3,), k, jnp.int32)
        out = np.asarray(sample_tokens_fused(
            jnp.asarray(logits), temps, topks, jax.random.PRNGKey(seed)))
        for s in range(3):
            assert out[s] in topset[s]

    def test_tied_logits_keep_exactly_k_candidates(self):
        """Ties at the k-th place — routine under int8-dequantized
        heads — must resolve to exactly k candidates identically in
        both lowerings (rank-based candidacy, not a value threshold)."""
        logits = np.full((1, 16), 1.0, np.float32)
        logits[0, :3] = 5.0                     # 13-way tie below the top-3
        temps = jnp.asarray([2.0], jnp.float32)
        topks = jnp.asarray([4], jnp.int32)     # k-th candidate is tied
        allowed = {0, 1, 2, 3}                  # stable argsort: index 3
        for seed in range(24):
            key = jax.random.PRNGKey(seed)
            got = int(sample_tokens_fused(jnp.asarray(logits), temps,
                                          topks, key)[0])
            want = int(sample_tokens_ref(jnp.asarray(logits), temps,
                                         topks, key)[0])
            assert got == want
            assert got in allowed

    def test_top_k_beyond_vocab_and_flat_rows_are_defined(self):
        """k > V behaves as unrestricted; an all-equal row still draws
        a valid id — identically in both lowerings."""
        logits = jnp.asarray(np.zeros((2, 8), np.float32))
        temps = jnp.asarray([1.0, 1.0], jnp.float32)
        topks = jnp.asarray([100, 8], jnp.int32)
        for seed in range(8):
            key = jax.random.PRNGKey(seed)
            got = np.asarray(sample_tokens_fused(logits, temps, topks, key))
            want = np.asarray(sample_tokens_ref(logits, temps, topks, key))
            np.testing.assert_array_equal(got, want)
            assert ((0 <= got) & (got < 8)).all()

    def test_temperature_spreads_and_key_matters(self):
        """Different keys move the sampled slots but never the greedy
        ones (per-slot params mix inside one batch)."""
        logits, temp, topk = _fix()
        draws = np.stack([
            np.asarray(sample_tokens_fused(logits, temp, topk,
                                           jax.random.PRNGKey(s)))
            for s in range(32)])
        assert (draws[:, 0] == draws[0, 0]).all()       # greedy slot fixed
        assert len(set(draws[:, 3].tolist())) > 1       # temp-2.0 slot moves
