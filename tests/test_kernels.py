"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention, flash_decode
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.rwkv6_scan import wkv6


def rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


# --------------------------------------------------------------------------- #
# flash attention                                                              #
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("B,Tq,Tk,H,Hkv,hd", [
    (1, 128, 128, 4, 4, 64),
    (2, 256, 256, 8, 2, 64),      # GQA
    (1, 64, 512, 4, 1, 128),      # MQA, cross-length
    (2, 384, 384, 6, 3, 32),      # non-pow2 blocks
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_causal(B, Tq, Tk, H, Hkv, hd, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = rand(ks[0], (B, Tq, H, hd), dtype)
    k = rand(ks[1], (B, Tk, Hkv, hd), dtype)
    v = rand(ks[2], (B, Tk, Hkv, hd), dtype)
    off = Tk - Tq
    got = flash_attention(q, k, v, causal=True, q_offset=off,
                          block_q=128, block_k=128, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True, q_offset=off)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("window", [32, 128])
def test_flash_attention_sliding_window(window):
    B, T, H, hd = 1, 256, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = rand(ks[0], (B, T, H, hd), jnp.float32)
    k = rand(ks[1], (B, T, H, hd), jnp.float32)
    v = rand(ks[2], (B, T, H, hd), jnp.float32)
    got = flash_attention(q, k, v, causal=True, window=window,
                          block_q=64, block_k=64, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_noncausal():
    B, Tq, Tk, H, hd = 1, 128, 192, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = rand(ks[0], (B, Tq, H, hd), jnp.float32)
    k = rand(ks[1], (B, Tk, H, hd), jnp.float32)
    v = rand(ks[2], (B, Tk, H, hd), jnp.float32)
    got = flash_attention(q, k, v, causal=False, block_q=64, block_k=64,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("B,S,H,Hkv,hd", [
    (2, 256, 4, 4, 64),
    (4, 512, 8, 2, 64),
    (1, 128, 4, 1, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode(B, S, H, Hkv, hd, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = rand(ks[0], (B, H, hd), dtype)
    k = rand(ks[1], (B, S, Hkv, hd), dtype)
    v = rand(ks[2], (B, S, Hkv, hd), dtype)
    cur = jnp.int32(S // 2)
    got = flash_decode(q, k, v, cur, block_k=128, interpret=True)
    want = ref.flash_decode_ref(q, k, v, cur)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_flash_decode_per_request_lengths():
    """Continuous batching: each request has its own context length."""
    B, S, H, hd = 3, 256, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = rand(ks[0], (B, H, hd), jnp.float32)
    k = rand(ks[1], (B, S, H, hd), jnp.float32)
    v = rand(ks[2], (B, S, H, hd), jnp.float32)
    lens = jnp.array([10, 100, 255], jnp.int32)
    got = flash_decode(q, k, v, lens, block_k=64, interpret=True)
    want = ref.flash_decode_ref(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# --------------------------------------------------------------------------- #
# RWKV6 WKV                                                                    #
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("B,T,H,dk,dv", [
    (1, 64, 2, 32, 32),
    (2, 128, 4, 64, 64),
    (1, 96, 2, 64, 64),      # non-pow2 T
])
def test_wkv6(B, T, H, dk, dv):
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    r = rand(ks[0], (B, T, H, dk), jnp.float32) * 0.5
    k = rand(ks[1], (B, T, H, dk), jnp.float32) * 0.5
    v = rand(ks[2], (B, T, H, dv), jnp.float32) * 0.5
    w = jax.nn.sigmoid(rand(ks[3], (B, T, H, dk), jnp.float32)) * 0.5 + 0.45
    u = rand(ks[4], (H, dk), jnp.float32) * 0.5
    s0 = rand(ks[5], (B, H, dk, dv), jnp.float32) * 0.1
    y_got, s_got = wkv6(r, k, v, w, u, s0, block_t=32, interpret=True)
    y_want, s_want = ref.wkv6_ref(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(y_got), np.asarray(y_want),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s_got), np.asarray(s_want),
                               atol=1e-4, rtol=1e-4)


def test_wkv6_state_chaining():
    """Running two half-sequences with carried state == one full run."""
    B, T, H, dk = 1, 64, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(6), 5)
    r = rand(ks[0], (B, T, H, dk), jnp.float32) * 0.5
    k = rand(ks[1], (B, T, H, dk), jnp.float32) * 0.5
    v = rand(ks[2], (B, T, H, dk), jnp.float32) * 0.5
    w = jax.nn.sigmoid(rand(ks[3], (B, T, H, dk), jnp.float32)) * 0.5 + 0.45
    u = rand(ks[4], (H, dk), jnp.float32) * 0.5
    s0 = jnp.zeros((B, H, dk, dk), jnp.float32)
    y_full, s_full = wkv6(r, k, v, w, u, s0, interpret=True)
    h = T // 2
    y1, s1 = wkv6(r[:, :h], k[:, :h], v[:, :h], w[:, :h], u, s0, interpret=True)
    y2, s2 = wkv6(r[:, h:], k[:, h:], v[:, h:], w[:, h:], u, s1, interpret=True)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                               atol=1e-4, rtol=1e-4)


# --------------------------------------------------------------------------- #
# RG-LRU linear recurrence                                                     #
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("B,T,W", [
    (1, 128, 256),
    (2, 256, 512),
    (1, 192, 160),           # non-pow2 both
])
def test_rglru(B, T, W):
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    a = jax.nn.sigmoid(rand(ks[0], (B, T, W), jnp.float32)) * 0.9
    b = rand(ks[1], (B, T, W), jnp.float32)
    h0 = rand(ks[2], (B, W), jnp.float32)
    h_got, hT_got = rglru_scan(a, b, h0, block_t=64, block_w=128, interpret=True)
    h_want, hT_want = ref.linear_recurrence_ref(a, b, h0)
    np.testing.assert_allclose(np.asarray(h_got), np.asarray(h_want),
                               atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(hT_got), np.asarray(hT_want),
                               atol=1e-5, rtol=1e-4)


def test_rglru_state_chaining():
    B, T, W = 1, 128, 64
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    a = jax.nn.sigmoid(rand(ks[0], (B, T, W), jnp.float32)) * 0.9
    b = rand(ks[1], (B, T, W), jnp.float32)
    h0 = rand(ks[2], (B, W), jnp.float32)
    h_full, hT_full = rglru_scan(a, b, h0, interpret=True)
    h1, s1 = rglru_scan(a[:, :64], b[:, :64], h0, interpret=True)
    h2, s2 = rglru_scan(a[:, 64:], b[:, 64:], s1, interpret=True)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([h1, h2], 1)),
                               np.asarray(h_full), atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(hT_full),
                               atol=1e-5, rtol=1e-4)


# --------------------------------------------------------------------------- #
# ops dispatch: pallas backend end-to-end inside a model block                 #
# --------------------------------------------------------------------------- #
def test_ops_backend_switch():
    from repro.kernels import ops
    B, T, H, hd = 1, 64, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = rand(ks[0], (B, T, H, hd), jnp.float32)
    k = rand(ks[1], (B, T, H, hd), jnp.float32)
    v = rand(ks[2], (B, T, H, hd), jnp.float32)
    ref_out = ops.flash_attention(q, k, v, causal=True)
    try:
        ops.set_backend("interpret")
        pal_out = ops.flash_attention(q, k, v, causal=True)
        # gradient flows through the custom_vjp oracle backward
        g = jax.grad(lambda q: ops.flash_attention(q, k, v).sum())(q)
        assert g.shape == q.shape and not np.isnan(np.asarray(g)).any()
    finally:
        ops.set_backend("ref")
    np.testing.assert_allclose(np.asarray(pal_out), np.asarray(ref_out),
                               atol=2e-5, rtol=2e-5)
