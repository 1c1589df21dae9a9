"""Per-kernel correctness: Pallas (interpret=True) vs pure-jnp oracle,
swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention, flash_attention_ref
from repro.kernels.rwkv6_scan import rwkv6_scan, rwkv6_scan_ref
from repro.kernels.vfl_matmul import vfl_matmul, vfl_matmul_ref


def allclose(a, b, dtype):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    scale = max(1.0, float(np.abs(b).max()))
    np.testing.assert_allclose(a, b, atol=tol * scale, rtol=tol)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("M,Kl,Kf,off", [
    (32, 128, 512, 0), (64, 128, 512, 128), (128, 256, 1024, 512),
    (16, 128, 128, 0),
])
def test_vfl_matmul(M, Kl, Kf, off, dtype):
    key = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    x = jax.random.normal(k1, (M, Kl), dtype)
    w = jax.random.normal(k2, (Kf, 256), dtype)
    out = vfl_matmul(x, w, off)
    ref = vfl_matmul_ref(x, w, off)
    allclose(out, ref, dtype)


@pytest.mark.parametrize("M,Kl,Kf,off", [
    (16, 128, 512, 128),         # lane-aligned slice
    (8, 56, 140, 28),            # mnist-style whole-image-row slice
    (32, 128, 128, 0),           # whole-width client
    (6, 3, 9, 3),                # titanic-sized tiny slice
    (64, 17, 51, 17),            # bank's unaligned 17-column thirds
    (200, 98, 784, 392),         # a partial last row block (bm=128)
])
def test_vfl_matmul_grads_match_ref(M, Kl, Kf, off):
    """custom_vjp vs autodiff through the zeropad oracle: dx is the
    sliced g @ W.T, dW scatter-adds into the client's row block (exact
    zeros elsewhere).  interpret=True so the CPU suite exercises the
    kernel's backward without a TPU."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(ks[0], (M, Kl), jnp.float32)
    w = jax.random.normal(ks[1], (Kf, 32), jnp.float32)
    t = jax.random.normal(ks[2], (M, 32), jnp.float32)  # cotangent seed

    def loss_kernel(x, w):
        return (vfl_matmul(x, w, off, interpret=True) * t).sum()

    def loss_ref(x, w):
        return (vfl_matmul_ref(x, w, off) * t).sum()

    gx, gw = jax.grad(loss_kernel, argnums=(0, 1))(x, w)
    gx_r, gw_r = jax.grad(loss_ref, argnums=(0, 1))(x, w)
    allclose(gx, gx_r, jnp.float32)
    allclose(gw, gw_r, jnp.float32)
    # rows of W outside the client's slice get an exact zero gradient
    gw_np = np.asarray(gw)
    assert np.all(gw_np[:off] == 0) and np.all(gw_np[off + Kl:] == 0)


def test_vfl_matmul_value_and_grad_under_jit_scan():
    """The vjp composes with jit+scan the way the protocol engine uses
    it (value_and_grad inside a scanned training step)."""
    ks = jax.random.split(jax.random.PRNGKey(4), 2)
    xs = jax.random.normal(ks[0], (4, 8, 56), jnp.float32)
    w = jax.random.normal(ks[1], (140, 16), jnp.float32)

    @jax.jit
    def train(w):
        def body(w, x):
            def loss(w):
                return (vfl_matmul(x, w, 28) ** 2).sum()
            l, g = jax.value_and_grad(loss)(w)
            return w - 0.01 * g, l
        return jax.lax.scan(body, w, xs)

    w2, losses = train(w)
    def loss_ref(w):
        return (vfl_matmul_ref(xs[0], w, 28) ** 2).sum()
    assert np.all(np.isfinite(np.asarray(losses)))
    # one reference step reproduces the first scanned step
    w_ref = w - 0.01 * jax.grad(loss_ref)(w)
    @jax.jit
    def one(w):
        def loss(w):
            return (vfl_matmul(xs[0], w, 28) ** 2).sum()
        return w - 0.01 * jax.grad(loss)(w)
    allclose(one(w), w_ref, jnp.float32)


def test_vfl_matmul_skips_zero_blocks():
    """The kernel must produce the same result regardless of what lives
    outside the client's slice of W-rows' input (it never reads x
    outside the slice -- x IS the slice)."""
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (32, 128), jnp.float32)
    w = jax.random.normal(key, (512, 128), jnp.float32)
    out1 = vfl_matmul(x, w, 128)
    # zeroing W rows outside the slice must not change the result
    w2 = w.at[:128].set(0).at[256:].set(0)
    out2 = vfl_matmul(x, w2, 128)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2))


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KV,S,hd,causal,window,cap", [
    (2, 4, 2, 256, 64, True, None, 0.0),
    (1, 4, 4, 256, 64, True, 128, 0.0),
    (1, 8, 2, 128, 64, True, None, 50.0),
    (2, 2, 2, 256, 64, False, None, 0.0),
    (1, 2, 1, 512, 128, True, 256, 30.0),
])
def test_flash_attention(B, H, KV, S, hd, causal, window, cap, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, S, hd), dtype)
    k = jax.random.normal(ks[1], (B, KV, S, hd), dtype)
    v = jax.random.normal(ks[2], (B, KV, S, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          softcap=cap, bq=64, bk=64)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window,
                              softcap=cap)
    allclose(out, ref, dtype)


def test_flash_attention_block_sizes():
    """Result must be block-size independent."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, 2, 256, 64))
    k = jax.random.normal(ks[1], (1, 2, 256, 64))
    v = jax.random.normal(ks[2], (1, 2, 256, 64))
    outs = [flash_attention(q, k, v, bq=bq, bk=bk)
            for (bq, bk) in [(64, 64), (128, 128), (256, 64), (64, 256)]]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,T,H,hd,chunk", [
    (2, 128, 2, 64, 32), (1, 256, 4, 64, 64), (2, 64, 2, 128, 64),
])
def test_rwkv6_scan(B, T, H, hd, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    r = jax.random.normal(ks[0], (B, T, H, hd), dtype)
    k = (jax.random.normal(ks[1], (B, T, H, hd)) * 0.3).astype(dtype)
    v = jax.random.normal(ks[2], (B, T, H, hd), dtype)
    w = (jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, hd)))
         * 0.5 + 0.45).astype(dtype)
    u = (jax.random.normal(ks[4], (H, hd)) * 0.2).astype(jnp.float32)
    out = rwkv6_scan(r, k, v, w, u, chunk=chunk)
    ref = rwkv6_scan_ref(r, k, v, w, u)
    allclose(out, ref, dtype)


def test_rwkv6_chunk_invariance():
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    B, T, H, hd = 1, 128, 2, 64
    r = jax.random.normal(ks[0], (B, T, H, hd))
    k = jax.random.normal(ks[1], (B, T, H, hd)) * 0.3
    v = jax.random.normal(ks[2], (B, T, H, hd))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, hd))) * 0.5 + 0.4
    u = jax.random.normal(ks[4], (H, hd)) * 0.2
    outs = [rwkv6_scan(r, k, v, w, u, chunk=c) for c in (16, 32, 64, 128)]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
from repro.kernels.mamba_scan import mamba_scan, mamba_scan_ref
from repro.kernels.moe_router import moe_router, moe_router_ref


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,T,D,N,bd,chunk", [
    (1, 64, 128, 16, 64, 32), (2, 128, 256, 8, 128, 64),
    (1, 96, 128, 16, 128, 32),
])
def test_mamba_scan(B, T, D, N, bd, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    a = (jax.nn.sigmoid(jax.random.normal(ks[0], (B, T, D, N)))
         * 0.5 + 0.45).astype(dtype)
    bx = (jax.random.normal(ks[1], (B, T, D, N)) * 0.2).astype(dtype)
    c = jax.random.normal(ks[2], (B, T, N), dtype)
    out = mamba_scan(a, bx, c, bd=bd, chunk=chunk)
    ref = mamba_scan_ref(a, bx, c)
    allclose(out, ref, dtype)


def test_mamba_scan_chunk_invariance():
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (1, 128, 128, 8))) * 0.5 + 0.4
    bx = jax.random.normal(ks[1], (1, 128, 128, 8)) * 0.2
    c = jax.random.normal(ks[2], (1, 128, 8))
    outs = [mamba_scan(a, bx, c, chunk=ch) for ch in (16, 32, 64, 128)]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("T,E,k", [(256, 64, 6), (128, 8, 2), (384, 16, 4)])
def test_moe_router(T, E, k):
    logits = jax.random.normal(jax.random.PRNGKey(1), (T, E)) * 2
    w, i, s = moe_router(logits, k)
    wr, ir, sr = moe_router_ref(logits, k)
    np.testing.assert_allclose(np.asarray(w), np.asarray(wr), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), atol=1e-4)
    # weights renormalized
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-5)
