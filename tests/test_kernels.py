"""Pallas kernel validation: shape/dtype sweeps, assert_allclose vs the
pure-jnp oracles in kernels/ref.py (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.kv_gather import kv_gather

KEY = jax.random.PRNGKey(0)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


class TestFlashAttention:
    @pytest.mark.parametrize("B,H,KV,S,dh,bq,bk", [
        (1, 4, 4, 128, 64, 64, 64),     # MHA
        (2, 4, 2, 128, 32, 32, 64),     # GQA, rectangular blocks
        (1, 8, 1, 256, 64, 128, 128),   # MQA
        (2, 6, 2, 64, 16, 16, 16),      # odd-ish head count
    ])
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_ref(self, B, H, KV, S, dh, bq, bk, causal):
        kq, kk, kv_ = jax.random.split(KEY, 3)
        q = jax.random.normal(kq, (B, H, S, dh), jnp.float32)
        k = jax.random.normal(kk, (B, KV, S, dh), jnp.float32)
        v = jax.random.normal(kv_, (B, KV, S, dh), jnp.float32)
        out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                              interpret=True)
        want = ref.ref_flash_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(out, want, **_tol(jnp.float32))

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        q = jax.random.normal(KEY, (1, 2, 64, 32), dtype)
        k = jax.random.normal(KEY, (1, 2, 64, 32), dtype)
        v = jax.random.normal(KEY, (1, 2, 64, 32), dtype)
        out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                              interpret=True)
        want = ref.ref_flash_attention(q.astype(jnp.float32),
                                       k.astype(jnp.float32),
                                       v.astype(jnp.float32), causal=True)
        assert out.dtype == dtype
        np.testing.assert_allclose(out.astype(jnp.float32), want, **_tol(dtype))

    @given(st.sampled_from([32, 64, 128]), st.sampled_from([1, 2, 4]),
           st.integers(0, 2**31 - 1))
    @settings(max_examples=8, deadline=None)
    def test_property_block_size_invariance(self, bk, group, seed):
        """The tiling must never change the math."""
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
        H, S, dh = 2 * group, 128, 32
        q = jax.random.normal(k1, (1, H, S, dh), jnp.float32)
        k = jax.random.normal(k2, (1, 2, S, dh), jnp.float32)
        v = jax.random.normal(k3, (1, 2, S, dh), jnp.float32)
        out = flash_attention(q, k, v, causal=True, block_q=32, block_k=bk,
                              interpret=True)
        want = ref.ref_flash_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out, want, rtol=3e-5, atol=3e-5)


class TestDecodeAttention:
    @pytest.mark.parametrize("B,H,KV,S,dh,bs", [
        (2, 4, 4, 256, 64, 64),
        (2, 8, 2, 256, 32, 128),
        (1, 4, 1, 512, 64, 256),
    ])
    def test_matches_ref(self, B, H, KV, S, dh, bs):
        kq, kk, kv_, kl = jax.random.split(KEY, 4)
        q = jax.random.normal(kq, (B, H, dh), jnp.float32)
        kc = jax.random.normal(kk, (B, S, KV, dh), jnp.float32)
        vc = jax.random.normal(kv_, (B, S, KV, dh), jnp.float32)
        lengths = jax.random.randint(kl, (B,), 1, S + 1)
        out = decode_attention(q, kc, vc, lengths, block_s=bs, interpret=True)
        want = ref.ref_decode_attention(q, kc, vc, lengths)
        np.testing.assert_allclose(out, want, rtol=3e-5, atol=3e-5)

    def test_short_lengths_ignore_tail(self):
        """Bytes past `lengths` must not affect the result."""
        kq, kk, kv_ = jax.random.split(KEY, 3)
        B, H, KV, S, dh = 1, 2, 2, 128, 16
        q = jax.random.normal(kq, (B, H, dh), jnp.float32)
        kc = jax.random.normal(kk, (B, S, KV, dh), jnp.float32)
        vc = jax.random.normal(kv_, (B, S, KV, dh), jnp.float32)
        lengths = jnp.array([40])
        out1 = decode_attention(q, kc, vc, lengths, block_s=32, interpret=True)
        kc2 = kc.at[:, 40:].set(999.0)
        vc2 = vc.at[:, 40:].set(-999.0)
        out2 = decode_attention(q, kc2, vc2, lengths, block_s=32, interpret=True)
        np.testing.assert_allclose(out1, out2, rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        q = jax.random.normal(KEY, (2, 4, 32), dtype)
        kc = jax.random.normal(KEY, (2, 128, 2, 32), dtype)
        vc = jax.random.normal(KEY, (2, 128, 2, 32), dtype)
        lengths = jnp.array([100, 128])
        out = decode_attention(q, kc, vc, lengths, block_s=64, interpret=True)
        want = ref.ref_decode_attention(q.astype(jnp.float32),
                                        kc.astype(jnp.float32),
                                        vc.astype(jnp.float32), lengths)
        np.testing.assert_allclose(out.astype(jnp.float32), want, **_tol(dtype))


class TestKVGather:
    @pytest.mark.parametrize("P,G,W,N", [(16, 8, 32, 5), (64, 16, 128, 64),
                                         (8, 4, 8, 1)])
    def test_matches_ref(self, P, G, W, N):
        pool = jax.random.normal(KEY, (P, G, W), jnp.float32)
        idx = jax.random.randint(KEY, (N,), 0, P)
        out = kv_gather(pool, idx, interpret=True)
        np.testing.assert_allclose(out, ref.ref_kv_gather(pool, idx))

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8])
    def test_dtypes(self, dtype):
        pool = jnp.arange(16 * 8 * 16).reshape(16, 8, 16).astype(dtype)
        idx = jnp.array([3, 3, 0, 15], jnp.int32)
        out = kv_gather(pool, idx, interpret=True)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(ref.ref_kv_gather(pool, idx)))

    @given(st.integers(0, 2**31 - 1), st.integers(1, 12))
    @settings(max_examples=10, deadline=None)
    def test_property_any_index_pattern(self, seed, n):
        key = jax.random.PRNGKey(seed)
        pool = jax.random.normal(key, (10, 4, 8), jnp.float32)
        idx = jax.random.randint(key, (n,), 0, 10)
        out = kv_gather(pool, idx, interpret=True)
        np.testing.assert_allclose(out, ref.ref_kv_gather(pool, idx))
