"""Block-FFT causal conv: the four-step (Bailey) DFT with the small DFTs
evaluated as dense real matmuls on the MXU instead of XLA's FFT op.  This
is the TPU analogue of FlashConv's "block FFT" for tensor cores (H3
paper).  ``fftconv.fft_causal_conv`` runs it when the program is traced
on a TPU; the ``blockfft`` backend runs it on every platform, which is
how the CPU tests reach it.

Four-step decomposition, N = R·S, x row-major A[r, s] = x[r·S + s]:

    X[k1 + R·k2] = Σ_s W_S^{s·k2} · W_N^{s·k1} · Σ_r W_R^{r·k1} A[r, s]

  1. R-point DFT over r — one (2·K1 × R1) real matmul for every signal
  2. S-point DFT over s — for each k1, one (2S × 2S) real matmul whose
     columns carry the twiddles W_N^{s·k1}, so no elementwise pass (and
     no HBM round trip) sits between the stages

Complex values are explicit (re, im) fp32 planes stacked along the
contracted axis, so a complex matmul is one real matmul of twice the
depth; the spectrum's layout is ``(K1, B, D, 2S)``.  Real-signal
structure cuts the work:

  * the zero-padded input holds data in its first R1 = ⌈L/S⌉ rows only,
    so stage 1 contracts over those rows alone;
  * a real signal's spectrum is conjugate-symmetric, X[N−k] = conj X[k],
    so only k1 ∈ [0, R/2] (K1 = ⌊R/2⌋ + 1 rows) is computed and carried;
  * the inverse (the same two stages transposed, twiddles conjugated)
    rebuilds the real signal from that half, since the k1 and R−k1 terms
    sum to twice a real part, and only for its first L samples.

The split is a fixed rule from N (``_split``): R nearest 2·√N, the
least matmul work, (128, 32) at N = 4096.

Precision: every matmul is pinned to ``lax.Precision.HIGHEST`` under any
policy or ambient ``default_matmul_precision``.  The XLA FFT this replaces
computes in fp32; a one- or three-pass bf16 matmul would give a different
(less exact) conv, not a faster one.  The DFT and twiddle matrices are
built in float64 numpy and rounded once to fp32.

Gradients come from a ``jax.custom_vjp`` whose backward is the same
transforms.  With c = conv(u, h) + skip·u in fp32 and y = cast(c)·gate:
dgate = dy·cast(c), dc = dy·gate, du = corr(dc, h) + skip·dc,
dh = Σ_b corr(u, dc)[:L] and dskip = Σ dc·u.  A correlation is the
conjugate-spectrum product on the same N points, so the backward takes
one forward transform (of dc, shared by du and dh), one inverse for du
and a one-row inverse for dh (Σ_b is taken on the spectra); the spectra
of u and h are residuals of the forward, which under remat are
recomputed with it.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

SCOPE = "four_step_fft"  # named scope of the whole conv; holds "fft"


def factor_candidates(N: int, limit: int = 6) -> Tuple[Tuple[int, int], ...]:
    """Valid (R, S) splits of N for the four-step transform, nearest-√N
    first — the autotune search space for this backend (core.autotune).

    Every divisor pair computes the same DFT; they differ only in how the
    work lands on the two small dense matmuls (R×R and S×S), so searching
    over them is semantics-preserving by construction."""
    divs = [r for r in range(1, math.isqrt(N) + 1) if N % r == 0]
    pairs = []
    for r in reversed(divs):  # closest to √N first
        pairs.append((r, N // r))
        if (N // r, r) != (r, N // r):
            pairs.append((N // r, r))
    return tuple(pairs[:limit])


def _factor(N: int) -> Tuple[int, int]:
    """N = R·S with R preferring the MXU-friendly power-of-two near √N.

    The four-step identity holds for ANY factorization, so when N's
    power-of-two part is small (odd / prime conv lengths pad to N = 2L
    with L odd) we fall back to the largest divisor ≤ √N instead of
    doubling R past N forever (the pre-fix behavior hung on prime L —
    caught by tests/test_conv_backends_prop.py).
    """
    R = 1 << max(math.ceil(math.log2(math.sqrt(N))), 0)
    while R <= N and N % R:
        R *= 2
    if R <= N and N % R == 0:
        return R, N // R
    R = max(r for r in range(1, math.isqrt(N) + 1) if N % r == 0)
    return R, N // R


def _split(N: int) -> Tuple[int, int]:
    """The (R, S) split of N that this module's transforms use: the
    divisor R nearest 2·√N, which minimises the real matmul work per
    sample (R/2 in the R-point stage, on the data rows; 2S in the
    S-point stage, on stacked re/im planes).  At N = 4096 that is
    (128, 32), the fastest of ``factor_candidates(4096)`` on a v5e."""
    divs = {d for r in range(1, math.isqrt(N) + 1) if N % r == 0
            for d in (r, N // r)}
    R = min(sorted(divs), key=lambda r: r / 2 + 2 * N / r)
    return R, N // R


class _Plan(NamedTuple):
    """The real-plane DFT matrices of one (L, split): float64-built, fp32,
    and numpy, not jnp: the cache outlives every trace that reads it."""

    L: int
    R1: int  # input rows that hold data: ⌈L/S⌉
    K1: int  # k1 rows carried: ⌊R/2⌋ + 1
    S: int
    fwd_r: np.ndarray  # (2·K1, R1): R-point DFT, rows (k1, re|im)
    fwd_s: np.ndarray  # (K1, 2S, 2, S): twiddled S-point DFT per k1
    inv_s: np.ndarray  # (K1, 2S, 2S): inverse S-point DFT, then twiddle
    inv_r: np.ndarray  # (R1, K1, 2): real part of the inverse R-point / N


def _planes(c: np.ndarray) -> np.ndarray:
    """Complex (..., I, J) -> the real (..., 2I, 2J) that maps (re, im)
    planes stacked along J to those of the product, stacked along I."""
    return np.concatenate([
        np.concatenate([c.real, -c.imag], axis=-1),
        np.concatenate([c.imag, c.real], axis=-1),
    ], axis=-2)


@functools.lru_cache(maxsize=32)
def _plan(L: int, factors: Optional[Tuple[int, int]] = None) -> _Plan:
    from repro.core.fftconv import next_fast_len

    # any N >= 2L-1 keeps the first L outputs wrap-free; a 5-smooth N also
    # keeps the factor split balanced for odd / prime-ish L
    N = next_fast_len(2 * L - 1)
    if factors is None or factors[0] * factors[1] != N:
        factors = _split(N)  # also drops a stale plan for another N
    R, S = factors
    R1, K1 = -(-L // S), R // 2 + 1
    k1, s = np.arange(K1), np.arange(S)
    a1 = 2 * np.pi * np.outer(k1, np.arange(R1)) / R
    fwd_r = np.stack([np.cos(a1), -np.sin(a1)], axis=1)
    tw = np.exp(-2j * np.pi * np.outer(k1, s) / N)  # W_N^{k1·s}
    fs = np.exp(-2j * np.pi * np.outer(s, s) / S)  # W_S^{k2·s}
    fwd_s = _planes(fs[None] * tw[:, None, :])  # columns scaled by tw
    inv_s = _planes(np.conj(fs)[None] * np.conj(tw)[:, :, None])  # rows
    # x[r] = (1/N) Σ_{k1 < K1} w·Re(W_R^{-r·k1} Q[k1]): the terms k1 and
    # R−k1 of a conjugate-symmetric Q are conjugates (w = 2); k1 = 0 and,
    # for even R, k1 = R/2 are their own partners (w = 1)
    w = np.where((k1 == 0) | (2 * k1 == R), 1.0, 2.0) / N
    a4 = 2 * np.pi * np.outer(np.arange(R1), k1) / R
    inv_r = np.stack([w * np.cos(a4), -w * np.sin(a4)], axis=2)
    f32 = lambda x: np.asarray(x, np.float32)  # noqa: E731
    return _Plan(
        L, R1, K1, S, f32(fwd_r.reshape(2 * K1, R1)),
        f32(fwd_s.reshape(K1, 2 * S, 2, S)), f32(inv_s), f32(inv_r),
    )


def _dot(x: jax.Array, m: np.ndarray, contract, batch=((), ())):
    """``lax.dot_general`` with a DFT matrix, fp32-exact on any platform."""
    return lax.dot_general(
        x, jnp.asarray(m), (contract, batch),
        precision=lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )


def _spectrum(x: jax.Array, p: _Plan) -> jax.Array:
    """Real (B, L, D) fp32 -> half spectrum (K1, B, D, 2S):
    ``out[k1, b, d, c·S + k2]`` is (re, im)[c] of X[k1 + R·k2]."""
    B, L, D = x.shape
    a = jnp.pad(x, ((0, 0), (0, p.R1 * p.S - L), (0, 0)))
    a = a.reshape(B, p.R1, p.S, D)
    c1 = _dot(a, p.fwd_r, ((1,), (1,)))  # (B, S, D, 2·K1)
    c1 = c1.reshape(B, p.S, D, p.K1, 2)
    # per k1: contract (s, re|im) -> (re|im, k2)
    return _dot(c1, p.fwd_s, ((1, 4), (3, 2)), ((3,), (0,)))


def _inverse(y: jax.Array, p: _Plan) -> jax.Array:
    """Conjugate-symmetric half spectrum (K1, B, D, 2S) -> the first L
    samples (B, L, D) of its real inverse DFT."""
    K1, B, D, _ = y.shape
    q = _dot(y, p.inv_s, ((3,), (2,)), ((0,), (0,)))  # (K1, B, D, 2S)
    q = q.reshape(K1, B, D, 2, p.S)
    x = _dot(q, p.inv_r, ((0, 3), (1, 2)))  # (B, D, S, R1)
    x = x.transpose(0, 3, 2, 1).reshape(B, p.R1 * p.S, D)
    return x[:, : p.L]


def _cmul(x: jax.Array, y: jax.Array, conj_y: bool = False) -> jax.Array:
    """x·y (or x·conj(y)) of two spectra in the (K1, B, D, 2S) layout."""
    S = x.shape[-1] // 2
    xr, xi = x[..., :S], x[..., S:]
    yr, yi = y[..., :S], y[..., S:]
    if conj_y:
        yi = -yi
    return jnp.concatenate([xr * yr - xi * yi, xr * yi + xi * yr], axis=-1)


def _forward(u, h, skip, gate, factors):
    from repro.core.fftconv import _fused_epilogue

    with jax.named_scope(SCOPE):
        p = _plan(u.shape[1], factors)
        u32 = u.astype(jnp.float32)
        U = _spectrum(u32, p)
        H = _spectrum(h.astype(jnp.float32).T[None], p)
        # skip-add in fp32 and downcast (the gate multiplies after)
        c = _fused_epilogue(_inverse(_cmul(U, H), p), u32, skip, None,
                            u.dtype)
        y = c if gate is None else c * gate.astype(u.dtype)
    return y, (U, H, c)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _conv(u, h, skip, gate, factors):
    return _forward(u, h, skip, gate, factors)[0]


def _conv_fwd(u, h, skip, gate, factors):
    y, spectra = _forward(u, h, skip, gate, factors)
    return y, (u, h, skip, gate, spectra)


def _conv_bwd(factors, res, dy):
    u, h, skip, gate, (U, H, c) = res
    with jax.named_scope(SCOPE):
        p = _plan(u.shape[1], factors)
        dgate = None
        if gate is None:
            dc = dy.astype(jnp.float32)
        else:
            dgate = (dy * c).astype(gate.dtype)
            dc = (dy * gate.astype(u.dtype)).astype(jnp.float32)
        DC = _spectrum(dc, p)
        du = _inverse(_cmul(DC, H, conj_y=True), p)
        dskip = None
        if skip is not None:
            du = du + dc * skip.astype(jnp.float32)
            dskip = jnp.sum(dc * u.astype(jnp.float32), axis=(0, 1))
            dskip = dskip.astype(skip.dtype)
        # Σ_b over the spectra first: one inverse for every batch row
        G = jnp.sum(_cmul(DC, U, conj_y=True), axis=1, keepdims=True)
        dh = _inverse(G, p)[0].T.astype(h.dtype)
    return du.astype(u.dtype), dh, dskip, dgate


_conv.defvjp(_conv_fwd, _conv_bwd)


@functools.partial(jax.jit, static_argnames=("factors",))
def blockfft_causal_conv(
    u: jax.Array,  # (B, L, D)
    h: jax.Array,  # (D, L)
    skip: Optional[jax.Array] = None,  # (D,)
    gate: Optional[jax.Array] = None,  # (B, L, D)
    *,
    factors: Optional[Tuple[int, int]] = None,  # (R, S); None: _split
) -> jax.Array:
    """Depthwise causal conv (the ConvBackend contract) by the four-step
    DFT on ``next_fast_len(2L - 1)`` points, fp32 inside, gate and skip
    fused as in ``fftconv._fused_epilogue``.  A ``factors`` split that
    does not multiply to that length is ignored (a stale plan)."""
    B, L, D = u.shape
    assert h.shape == (D, L), (h.shape, (D, L))
    return _conv(u, h, skip, gate,
                 None if factors is None else tuple(factors))
