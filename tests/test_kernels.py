"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles
(interpret=True executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention as fa
from repro.kernels import ref
from repro.kernels import rmsnorm as rn
from repro.kernels import short_conv as sc
from repro.kernels import toeplitz_conv as tc


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=2e-4, atol=2e-4
    )


# ------------------------------------------------------------- rmsnorm

@pytest.mark.parametrize("shape", [(4, 8, 32), (2, 128), (1, 3, 5, 64), (300, 96)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm(shape, dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), shape, dtype)
    g = jax.random.normal(jax.random.PRNGKey(1), (shape[-1],), jnp.float32) * 0.1
    got = rn.rmsnorm(x, g, interpret=True, block_rows=64)
    want = ref.rmsnorm(x, g)
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32), **tol(dtype)
    )


# ---------------------------------------------------------- short conv

@pytest.mark.parametrize("B,L,D,K", [(2, 16, 8, 3), (1, 100, 33, 4), (3, 512, 128, 3)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("gated", [False, True])
def test_short_conv(B, L, D, K, dtype, gated):
    u = jax.random.normal(jax.random.PRNGKey(0), (B, L, D), dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (D, K), jnp.float32)
    g = jax.random.normal(jax.random.PRNGKey(2), (B, L, D), dtype) if gated else None
    got = sc.short_conv_gate(u, w, g, block_l=64, block_d=32, interpret=True)
    want = ref.short_conv_gate(u, w, g)
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32), **tol(dtype)
    )


def test_short_conv_causal_blocks():
    """Halo handling: output identical whether L fits one block or many."""
    B, L, D = 1, 256, 16
    u = jax.random.normal(jax.random.PRNGKey(0), (B, L, D))
    w = jax.random.normal(jax.random.PRNGKey(1), (D, 3), jnp.float32)
    one = sc.short_conv_gate(u, w, block_l=256, block_d=16, interpret=True)
    many = sc.short_conv_gate(u, w, block_l=32, block_d=8, interpret=True)
    np.testing.assert_allclose(one, many, rtol=1e-5, atol=1e-5)


# --------------------------------------------------------- toeplitz conv

@pytest.mark.parametrize(
    "B,L,D,C", [(2, 64, 8, 16), (1, 128, 16, 32), (2, 96, 8, 32)]
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("gated", [False, True])
def test_toeplitz_conv_full(B, L, D, C, dtype, gated):
    u = jax.random.normal(jax.random.PRNGKey(0), (B, L, D), dtype)
    h = jax.random.normal(jax.random.PRNGKey(1), (D, L), jnp.float32) / L
    skip = jax.random.normal(jax.random.PRNGKey(2), (D,), jnp.float32)
    g = jax.random.normal(jax.random.PRNGKey(3), (B, L, D), dtype) if gated else None
    got = tc.toeplitz_conv(u, h, skip, g, chunk=C, block_d=8, interpret=True)
    want = ref.toeplitz_conv(u, h, skip, g)
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32), **tol(dtype)
    )


def test_toeplitz_conv_banded():
    """Banded support matches a filter truncated to K chunk diagonals."""
    B, L, D, C, K = 1, 128, 4, 16, 3
    u = jax.random.normal(jax.random.PRNGKey(0), (B, L, D))
    h = jax.random.normal(jax.random.PRNGKey(1), (D, L), jnp.float32) / L
    got = tc.toeplitz_conv(u, h, chunk=C, block_d=4, n_chunk_diags=K, interpret=True)
    want = ref.toeplitz_conv(u, h, n_chunk_diags=K, chunk=C)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_toeplitz_matches_fftconv():
    """Kernel == core fft path (full support)."""
    from repro.core.fftconv import fft_causal_conv
    B, L, D = 2, 64, 8
    u = jax.random.normal(jax.random.PRNGKey(0), (B, L, D))
    h = jax.random.normal(jax.random.PRNGKey(1), (D, L), jnp.float32) / L
    got = tc.toeplitz_conv(u, h, chunk=16, block_d=8, interpret=True)
    np.testing.assert_allclose(got, fft_causal_conv(u, h), rtol=1e-4, atol=1e-4)


# ------------------------------------------------------- flash attention

@pytest.mark.parametrize(
    "B,H,Hkv,L,Dh", [(1, 4, 4, 64, 16), (2, 8, 2, 128, 32), (1, 6, 1, 96, 16)]
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_causal(B, H, Hkv, L, Dh, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, L, Dh), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, L, Dh), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, L, Dh), dtype)
    got = fa.flash_attention(q, k, v, blk_q=32, blk_k=32, interpret=True)
    want = ref.flash_attention(q, k, v)
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32), **tol(dtype)
    )


def test_flash_window():
    B, H, L, Dh = 1, 2, 128, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(ks[i], (B, H, L, Dh)) for i in range(3))
    got = fa.flash_attention(q, k, v, window=32, blk_q=16, blk_k=16, interpret=True)
    want = ref.flash_attention(q, k, v, window=32)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_flash_decode_shape():
    """Lq=1 decode against a Lk-long KV cache."""
    B, H, Hkv, Lk, Dh = 2, 8, 2, 128, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, 1, Dh))
    k = jax.random.normal(ks[1], (B, Hkv, Lk, Dh))
    v = jax.random.normal(ks[2], (B, Hkv, Lk, Dh))
    got = fa.flash_attention(q, k, v, blk_q=1, blk_k=32, interpret=True)
    want = ref.flash_attention(q, k, v)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_flash_unpadded_vs_padded():
    """L not a multiple of the block size (kv padding masked)."""
    B, H, L, Dh = 1, 2, 100, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(ks[i], (B, H, L, Dh)) for i in range(3))
    got = fa.flash_attention(q, k, v, blk_q=32, blk_k=32, interpret=True)
    want = ref.flash_attention(q, k, v)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


# ----------------------------------------------------- platform dispatch

def test_tpu_never_interprets_or_falls_back(monkeypatch):
    """On a TPU the kernels lower natively and the ops wrappers never hand
    the work to the jnp oracle; off-TPU both defaults pick the stand-ins."""
    from repro.kernels import platform

    assert platform.resolve_interpret(None) is True  # this CPU host
    assert platform.resolve_use_kernel(None) is False
    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    assert platform.resolve_interpret(None) is False
    assert platform.resolve_use_kernel(None) is True
    with pytest.raises(ValueError, match="interpret=True on a TPU"):
        platform.resolve_interpret(True)
    with pytest.raises(ValueError, match="use_kernel=False on a TPU"):
        platform.resolve_use_kernel(False)
