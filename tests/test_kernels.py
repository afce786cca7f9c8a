"""Pallas kernel sweeps: shapes × dtypes × masking modes against the
pure-jnp oracle (interpret=True on CPU)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops


def rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def make_qkv(b, sq, sk, hq, hkv, d, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = rand(ks[0], (b, sq, hq, d), dtype)
    k = rand(ks[1], (b, sk, hkv, d), dtype)
    v = rand(ks[2], (b, sk, hkv, d), dtype)
    return q, k, v


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


class TestFlashAttentionSweep:
    """Pallas flash attention (interpret mode) vs naive oracle."""

    @pytest.mark.parametrize("b,s,hq,hkv,d,dtype", [
        (1, 128, 4, 4, 64, jnp.float32),    # MHA
        (1, 128, 4, 4, 64, jnp.bfloat16),   # MHA, storage dtype
        (2, 256, 8, 2, 64, jnp.float32),    # GQA 4:1
        (2, 256, 8, 2, 64, jnp.bfloat16),
        (1, 128, 4, 1, 128, jnp.float32),   # MQA, wide head
        (2, 384, 4, 4, 64, jnp.float32),    # seq not a block multiple
    ])
    def test_causal_shapes_dtypes(self, b, s, hq, hkv, d, dtype):
        q, k, v = make_qkv(b, s, s, hq, hkv, d, dtype)
        got = ops.attention(q, k, v, causal=True, impl="pallas")
        want = ops.attention(q, k, v, causal=True, impl="naive")
        assert got.dtype == want.dtype
        np.testing.assert_allclose(np.float32(got), np.float32(want),
                                   **tol(dtype))

    @pytest.mark.parametrize("window", [32, 100, 256])
    def test_sliding_window(self, window):
        q, k, v = make_qkv(1, 256, 256, 4, 4, 64, jnp.float32)
        got = ops.attention(q, k, v, causal=True, sliding_window=window,
                            impl="pallas")
        want = ops.attention(q, k, v, causal=True, sliding_window=window,
                             impl="naive")
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def test_noncausal(self):
        q, k, v = make_qkv(2, 128, 128, 4, 4, 64, jnp.float32)
        got = ops.attention(q, k, v, causal=False, impl="pallas")
        want = ops.attention(q, k, v, causal=False, impl="naive")
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def test_block_shape_independence(self):
        q, k, v = make_qkv(1, 512, 512, 4, 4, 64, jnp.float32)
        outs = [ops.attention(q, k, v, causal=True, impl="pallas",
                              block_q=bq, block_k=bk)
                for bq, bk in [(128, 128), (128, 256), (256, 512)]]
        for o in outs[1:]:
            np.testing.assert_allclose(outs[0], o, rtol=1e-5, atol=1e-5)


class TestPallasRefusals:
    """impl='pallas' never quietly runs the reference: a call the kernel
    cannot serve raises."""

    def test_attention_with_kv_mask(self):
        q, k, v = make_qkv(1, 32, 32, 2, 2, 16, jnp.float32)
        with pytest.raises(NotImplementedError, match="blocked"):
            ops.attention(q, k, v, kv_mask=jnp.ones((1, 32), bool),
                          impl="pallas")

    def test_attention_with_q_offset(self):
        q, k, v = make_qkv(1, 1, 32, 2, 2, 16, jnp.float32)
        with pytest.raises(NotImplementedError, match="blocked"):
            ops.attention(q, k, v, q_offset=31, impl="pallas")

    @pytest.mark.parametrize("s,chunk,carry", [(96, 64, False),
                                               (64, 16, True)])
    def test_ssd_ragged_chunk_or_initial_state(self, s, chunk, carry):
        x, dt, A, B, C, D = TestSSDSweep().make(1, s, 2, 8, 8)
        init = jnp.zeros((1, 2, 8, 8)) if carry else None
        with pytest.raises(NotImplementedError, match="blocked"):
            ops.ssd(x, dt, A, B, C, D, chunk=chunk, initial_state=init,
                    impl="pallas")


class TestBlockedAttention:
    """The jnp online-softmax path (dry-run / CPU production path)."""

    @pytest.mark.parametrize("sq,sk", [(64, 64), (64, 192), (1, 333)])
    def test_rectangular_and_offset(self, sq, sk):
        q, k, v = make_qkv(2, sq, sk, 4, 2, 32, jnp.float32)
        off = sk - sq
        got = ops.attention(q, k, v, causal=True, q_offset=off,
                            impl="blocked", block_k=128)
        want = ops.attention(q, k, v, causal=True, q_offset=off, impl="naive")
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def test_kv_mask(self):
        q, k, v = make_qkv(2, 32, 64, 4, 4, 32, jnp.float32)
        kv_mask = (jnp.arange(64)[None, :] < jnp.array([40, 64])[:, None])
        got = ops.attention(q, k, v, causal=False, kv_mask=kv_mask,
                            impl="blocked", block_k=32)
        want = ops.attention(q, k, v, causal=False, kv_mask=kv_mask,
                             impl="naive")
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def test_decode_attention_matches_naive(self):
        q, k, v = make_qkv(3, 1, 96, 8, 2, 32, jnp.float32)
        pos = jnp.array([10, 50, 95])
        got = ops.decode_attention(q, k, v, q_offset=pos)
        want = jnp.concatenate([
            ops.attention(q[i:i + 1], k[i:i + 1], v[i:i + 1], causal=True,
                          q_offset=int(pos[i]), impl="naive")
            for i in range(3)])
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


class TestSSDSweep:
    """Mamba2 SSD: Pallas chunked kernel + jnp chunked form vs the
    sequential-recurrence oracle."""

    def make(self, b, s, h, p, n, dtype=jnp.float32, seed=0):
        ks = jax.random.split(jax.random.PRNGKey(seed), 6)
        x = rand(ks[0], (b, s, h, p), dtype)
        dt = jax.nn.softplus(rand(ks[1], (b, s, h), jnp.float32))
        A = -jnp.exp(jax.random.normal(ks[2], (h,)))
        B = rand(ks[3], (b, s, n), dtype)
        C = rand(ks[4], (b, s, n), dtype)
        D = jax.random.normal(ks[5], (h,))
        return x, dt, A, B, C, D

    @pytest.mark.parametrize("s,chunk", [(64, 16), (128, 32), (96, 32)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_chunked_vs_naive(self, s, chunk, dtype):
        x, dt, A, B, C, D = self.make(2, s, 4, 16, 16, dtype)
        y_c, st_c = ops.ssd(x, dt, A, B, C, D, chunk=chunk, impl="blocked")
        y_n, st_n = ops.ssd(x, dt, A, B, C, D, impl="naive")
        t = tol(dtype)
        np.testing.assert_allclose(np.float32(y_c), np.float32(y_n), **t)
        np.testing.assert_allclose(st_c, st_n, rtol=1e-3, atol=1e-3)

    @pytest.mark.parametrize("s,chunk", [(64, 16), (128, 64)])
    def test_pallas_vs_naive(self, s, chunk):
        x, dt, A, B, C, D = self.make(1, s, 2, 16, 8)
        y_p, _ = ops.ssd(x, dt, A, B, C, D, chunk=chunk, impl="pallas")
        y_n, _ = ops.ssd(x, dt, A, B, C, D, impl="naive")
        np.testing.assert_allclose(y_p, y_n, rtol=2e-4, atol=2e-4)

    def test_initial_state_threading(self):
        """Splitting a sequence in two with state carry == one long scan."""
        x, dt, A, B, C, D = self.make(2, 64, 4, 8, 8)
        y_full, st_full = ops.ssd(x, dt, A, B, C, D, chunk=16, impl="blocked")
        y1, st1 = ops.ssd(x[:, :32], dt[:, :32], A, B[:, :32], C[:, :32], D,
                          chunk=16, impl="blocked")
        y2, st2 = ops.ssd(x[:, 32:], dt[:, 32:], A, B[:, 32:], C[:, 32:], D,
                          chunk=16, initial_state=st1, impl="blocked")
        np.testing.assert_allclose(
            jnp.concatenate([y1, y2], 1), y_full, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(st2, st_full, rtol=1e-4, atol=1e-4)

    def test_decode_step_matches_scan_tail(self):
        """One ssd_decode_step == last position of the full scan."""
        x, dt, A, B, C, D = self.make(2, 33, 4, 8, 8)
        y_full, st_full = ops.ssd(x, dt, A, B, C, D, impl="naive")
        _, st_prefix = ops.ssd(x[:, :32], dt[:, :32], A, B[:, :32],
                               C[:, :32], D, impl="naive")
        y_tok, st_tok = ops.ssd_decode_step(
            x[:, 32], dt[:, 32], A, B[:, 32], C[:, 32], D, st_prefix)
        np.testing.assert_allclose(y_tok, y_full[:, 32], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(st_tok, st_full, rtol=1e-5, atol=1e-5)

    def test_chunk_size_independence(self):
        x, dt, A, B, C, D = self.make(1, 128, 2, 8, 8)
        outs = [ops.ssd(x, dt, A, B, C, D, chunk=c, impl="blocked")[0]
                for c in (16, 32, 64, 128)]
        for o in outs[1:]:
            np.testing.assert_allclose(outs[0], o, rtol=1e-4, atol=1e-4)
