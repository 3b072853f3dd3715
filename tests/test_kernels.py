"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_dispatch import moe_gather
from repro.kernels.ssd_scan import ssd_scan


def tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(
        atol=3e-5, rtol=3e-5
    )


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "B,Hq,Hkv,Sq,Skv,hd",
        [
            (1, 4, 4, 256, 256, 64),
            (2, 4, 2, 256, 512, 64),
            (1, 4, 1, 128, 384, 128),
            (1, 8, 8, 512, 512, 64),
        ],
    )
    def test_causal(self, B, Hq, Hkv, Sq, Skv, hd, dtype):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, Hq, Sq, hd), dtype)
        k = jax.random.normal(ks[1], (B, Hkv, Skv, hd), dtype)
        v = jax.random.normal(ks[2], (B, Hkv, Skv, hd), dtype)
        out = flash_attention(q, k, v, causal=True, interpret=True)
        want = ref.flash_attention_ref(
            q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
            causal=True,
        )
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(want), **tol(dtype)
        )

    @pytest.mark.parametrize("window", [64, 128])
    def test_sliding_window(self, window):
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (1, 2, 256, 64), jnp.float32)
        k = jax.random.normal(ks[1], (1, 2, 256, 64), jnp.float32)
        v = jax.random.normal(ks[2], (1, 2, 256, 64), jnp.float32)
        out = flash_attention(q, k, v, causal=True, window=window, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=3e-5)

    def test_softcap(self):
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(ks[0], (1, 2, 128, 64), jnp.float32)
        k = jax.random.normal(ks[1], (1, 2, 128, 64), jnp.float32)
        v = jax.random.normal(ks[2], (1, 2, 128, 64), jnp.float32)
        out = flash_attention(q, k, v, causal=True, softcap=30.0, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=True, softcap=30.0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=3e-5)

    def test_matches_model_attention(self):
        """Kernel == the model's chunked attention (different blocking)."""
        from repro.models.attention import attend_chunked

        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(ks[0], (2, 4, 256, 64), jnp.float32)
        k = jax.random.normal(ks[1], (2, 2, 256, 64), jnp.float32)
        v = jax.random.normal(ks[2], (2, 2, 256, 64), jnp.float32)
        out = flash_attention(q, k, v, causal=True, interpret=True)
        # model layout is [B, S, H, hd]
        out2 = attend_chunked(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), mode="causal", block_q=128, block_k=128,
        ).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(out), np.asarray(out2), atol=3e-5)


class TestDecodeAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "B,Hq,Hkv,S,hd,valid", [(2, 4, 4, 512, 64, 300), (1, 8, 2, 1024, 128, 1024),
                                (2, 4, 1, 512, 64, 17)],
    )
    def test_vs_ref(self, B, Hq, Hkv, S, hd, valid, dtype):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, Hq, hd), dtype)
        ck = jax.random.normal(ks[1], (B, Hkv, S, hd), dtype)
        cv = jax.random.normal(ks[2], (B, Hkv, S, hd), dtype)
        out = decode_attention(q, ck, cv, jnp.int32(valid), interpret=True)
        want = ref.decode_attention_ref(
            q.astype(jnp.float32), ck.astype(jnp.float32), cv.astype(jnp.float32),
            valid,
        )
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(want), **tol(dtype)
        )

    def test_window(self):
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (1, 2, 64), jnp.float32)
        ck = jax.random.normal(ks[1], (1, 2, 512, 64), jnp.float32)
        cv = jax.random.normal(ks[2], (1, 2, 512, 64), jnp.float32)
        out = decode_attention(q, ck, cv, jnp.int32(400), window=128, interpret=True)
        want = ref.decode_attention_ref(q, ck, cv, 400, window=128)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=3e-5)


class TestSSDScan:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "B,H,S,P,N,chunk", [(2, 2, 256, 64, 32, 64), (1, 4, 128, 32, 128, 128),
                            (1, 2, 512, 64, 64, 128)],
    )
    def test_vs_sequential(self, B, H, S, P, N, chunk, dtype):
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        x = (jax.random.normal(ks[0], (B, H, S, P)) * 0.5).astype(dtype)
        dt = jax.nn.softplus(jax.random.normal(ks[1], (B, H, S)))
        A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
        Bm = (jax.random.normal(ks[3], (B, H, S, N)) * 0.3).astype(dtype)
        Cm = (jax.random.normal(ks[4], (B, H, S, N)) * 0.3).astype(dtype)
        y, h = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
        y_ref, h_ref = ref.ssd_scan_ref(x, dt, A, Bm, Cm)
        t = dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 else dict(
            atol=2e-4, rtol=2e-4
        )
        np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(y_ref, np.float32), **t)
        np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), **t)

    def test_matches_model_ssd(self):
        """Kernel == the model's pure-jnp chunked SSD (mamba2.ssd_chunked)."""
        from repro.models.mamba2 import ssd_chunked

        ks = jax.random.split(jax.random.PRNGKey(7), 5)
        B, H, S, P, N = 2, 4, 256, 32, 64
        x = jax.random.normal(ks[0], (B, H, S, P)) * 0.5
        dt = jax.nn.softplus(jax.random.normal(ks[1], (B, H, S)))
        A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
        Bm = jax.random.normal(ks[3], (B, H, S, N)) * 0.3
        Cm = jax.random.normal(ks[4], (B, H, S, N)) * 0.3
        y, h = ssd_scan(x, dt, A, Bm, Cm, chunk=64, interpret=True)
        # model layout: [B, S, H, P] / [B, S, G, N]
        y2, h2 = ssd_chunked(
            x.transpose(0, 2, 1, 3), dt.transpose(0, 2, 1), A,
            Bm.transpose(0, 2, 1, 3), Cm.transpose(0, 2, 1, 3), chunk=64,
        )
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(y2.transpose(0, 2, 1, 3)), atol=2e-4, rtol=2e-4
        )
        np.testing.assert_allclose(np.asarray(h), np.asarray(h2), atol=2e-4, rtol=2e-4)


class TestMoEGather:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("T,d,R", [(64, 128, 96), (128, 256, 128)])
    def test_vs_ref(self, T, d, R, dtype):
        ks = jax.random.split(jax.random.PRNGKey(0), 2)
        x = jax.random.normal(ks[0], (T, d), dtype)
        # include dummy rows (== T)
        row_token = jax.random.randint(ks[1], (R,), 0, T + 1).astype(jnp.int32)
        out = moe_gather(x, row_token, interpret=True)
        want = ref.moe_gather_ref(x, row_token)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))

    def test_combine_roundtrip(self):
        """gather -> identity expert -> combine == weighted one-hot matmul."""
        T, d, R = 32, 64, 48
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        x = jax.random.normal(ks[0], (T, d), jnp.float32)
        row_token = jax.random.randint(ks[1], (R,), 0, T + 1).astype(jnp.int32)
        w = jax.random.uniform(ks[2], (R,))
        buf = moe_gather(x, row_token, interpret=True)
        y = ref.moe_combine_ref(buf, row_token, w, T)
        onehot = (row_token[:, None] == jnp.arange(T)[None, :]).astype(jnp.float32)
        want = jnp.einsum("rt,r,rd->td", onehot, w, buf)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5)
