"""Overlapped two-level (inner R / outer S) FFT causal-conv Pallas kernel.

The four-step block FFT (``repro.core.blockfft``) already puts every DFT
FLOP on the MXU; what it leaves on the table is *overlap*: each stage
(inner R-point DFTs, twiddle, outer S-point DFTs, pointwise filter
multiply, inverse) runs as a separate XLA op, so the activation makes a
full HBM round-trip between stages.  This kernel runs the whole two-level
schedule inside ONE ``pallas_call``:

  * every length-N signal (one per (channel, batch row)) is held as its
    transposed four-step matrix ``At[s, r] = x[r·S + s]``, signals stacked
    on the leading axis: the inner DFT is then one lane-contracting matmul
    ``At @ FR`` and the outer DFT a per-signal left multiply ``FS @ Ut``,
    with no in-kernel transpose;
  * the grid iterates ``(channel_block, s_chunk)`` — Pallas's software
    pipeline double-buffers the next s-chunk's HBM→VMEM input stream
    against the current chunk's inner-DFT matmuls, so HBM transfers
    overlap MXU compute;
  * ``overlap`` is the pipeline depth: the input is streamed in
    ``overlap`` chunks over the S rows (smaller in-flight transfers,
    deeper overlap), each landing in its rows of a VMEM spectrum scratch;
  * on the last chunk the twiddle, outer S-point DFT, pointwise filter
    multiply, inverse transform, and the gated-fusion finalize (skip-add in
    fp32 → downcast → gate multiply in the output dtype, the DESIGN.md §7
    bit-identity policy) all happen in VMEM.  The wrapper's transposes to
    and from the signal layout are plain XLA ops around the call.

Complex arithmetic is carried as explicit (re, im) fp32 planes (Pallas TPU
has no complex lanes); the filter spectrum is precomputed outside the
kernel with the same factor split, so the kernel's pointwise stage matches
``blockfft_causal_conv``'s spectrum layout term for term.

Like every kernel here, off-TPU the body runs in the Pallas interpreter
(tests pin it against the direct conv on small shapes); it never falls
back to another schedule.  The ``(R, S)`` split, channel tile, and overlap
depth are autotunable as the ``"twolevel"`` plan kind (``core.autotune``;
consulted by the ``blockfft_overlap`` registration in ``core.conv_api``).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.blockfft import _factor
from repro.kernels.platform import resolve_interpret


@functools.lru_cache(maxsize=32)
def _dft_mats(N: int, factors: Optional[Tuple[int, int]] = None):
    """Complex64 (R×R, S×S DFTs, R×S twiddle) of the split."""
    # numpy on purpose: this cache is shared across jit traces, and jnp
    # constant construction inside a trace would poison it with tracers
    # from a long-dead trace (UnexpectedTracerError on the next jit).
    R, S = _factor(N) if factors is None else factors
    if R * S != N:
        raise ValueError(f"factors {factors} do not multiply to N={N}")
    r = np.arange(R)
    s = np.arange(S)
    FR = np.exp(-2j * np.pi * np.outer(r, r) / R).astype(np.complex64)
    FS = np.exp(-2j * np.pi * np.outer(s, s) / S).astype(np.complex64)
    TW = np.exp(
        -2j * np.pi * np.outer(r, s) / N
    ).astype(np.complex64)  # W_N^{k1·s}
    return R, S, FR, FS, TW


def _four_step_fft(x: jax.Array, N: int, factors) -> jax.Array:
    """x: (B, N, D) real -> spectrum C (B, R, S, D) with
    X[k1 + k2·R] = C[:, k1, k2, :] (the kernel's filter spectrum)."""
    R, S, FR, FS, TW = _dft_mats(N, factors)
    B, _, D = x.shape
    A = x.reshape(B, R, S, D).astype(jnp.complex64)
    Bm = jnp.einsum("kr,brsd->bksd", FR, A)
    Bm = Bm * TW[None, :, :, None]
    return jnp.einsum("bksd,sj->bkjd", Bm, FS)


def _largest_divisor_leq(n: int, k: int) -> int:
    k = max(1, min(k, n))
    while n % k:
        k -= 1
    return k


def twolevel_candidates(shape, limit: int = 3):
    """Autotune search space for the ``"twolevel"`` plan kind: valid
    ``(R, S)`` splits of the padded length × overlap depth × channel tile.
    Every point computes the identical convolution (factor splits
    reassociate the DFT sums; overlap/tile only re-chunk the schedule), so
    the search is semantics-preserving by construction — the
    ``core.autotune`` contract."""
    from repro.core.blockfft import factor_candidates
    from repro.core.fftconv import next_fast_len

    B, L, D = shape
    N = next_fast_len(2 * L - 1)
    cands = []
    for R, S in factor_candidates(N, limit=limit):
        for ov in (2, 4):
            if S % ov:
                continue
            for bd in (8, 16):
                cands.append(
                    {"factors": [R, S], "overlap": ov, "block_d": bd}
                )
    # degenerate split vocabulary (tiny N): keep at least the default point
    if not cands:
        R, S = _factor(N)
        cands.append({"factors": [R, S], "overlap": 1, "block_d": 8})
    return cands


def _twolevel_kernel(
    x_ref,       # (Q, Sc, R) fp32 — s-chunk of the transposed padded input
    frre_ref,    # (R, R) inner DFT (re); symmetric, so FR == FR^T
    frim_ref,    # (R, R) (im)
    fsre_ref,    # (S, S) outer DFT (re); symmetric
    fsim_ref,    # (S, S) (im)
    twre_ref,    # (S, R) transposed twiddle W_N^{k1 s} (re)
    twim_ref,    # (S, R) (im)
    hre_ref,     # (bd, S, R) transposed filter spectrum block (re)
    him_ref,     # (bd, S, R) (im)
    xi_ref,      # (Q, S, R) fp32 whole input block (skip term, finalize)
    skip_ref,    # (bd, 1, 1) fp32
    g_ref,       # (Q, S, R) gate (output dtype; dummy block when ungated)
    o_ref,       # (Q, S, R) output
    accre_ref, accim_ref,  # VMEM (Q, S, R) fp32 spectrum accumulators
    *, N: int, overlap: int, gated: bool,
):
    c = pl.program_id(1)
    Q, Sc, R = x_ref.shape
    S = accre_ref.shape[1]
    bd = hre_ref.shape[0]
    f32 = jnp.float32

    def mm(a, b):  # (M, K) @ (K, N) on the MXU
        return jnp.dot(a, b, preferred_element_type=f32)

    def bmm(m, x):  # per-signal left multiply: (Q, S, S) @ (Q, S, R)
        return jax.lax.dot_general(
            m, x, (((2,), (1,)), ((0,), (0,))), preferred_element_type=f32,
        )

    # ---- stage 1 (every pipeline step): inner R-point DFTs of this
    # s-chunk.  The next chunk streams HBM→VMEM while these matmuls
    # occupy the MXU — the overlap this kernel exists for.  Real input,
    # so a chunk costs two real matmuls: Bt = At @ FR.
    a = x_ref[...].reshape(Q * Sc, R)
    off = pl.multiple_of(c * Sc, Sc)
    accre_ref[:, pl.ds(off, Sc), :] = mm(a, frre_ref[...]).reshape(Q, Sc, R)
    accim_ref[:, pl.ds(off, Sc), :] = mm(a, frim_ref[...]).reshape(Q, Sc, R)

    # ---- stages 2–5 (last step only): twiddle → outer DFT → pointwise
    # filter → inverse transform → gated finalize, all in VMEM.
    @pl.when(c == overlap - 1)
    def _finalize():
        twre = twre_ref[...][None]
        twim = twim_ref[...][None]
        bre, bim = accre_ref[...], accim_ref[...]
        ure = bre * twre - bim * twim
        uim = bre * twim + bim * twre
        # outer S-point DFT of every signal: Ct = FS @ Ut
        fsre = jnp.broadcast_to(fsre_ref[...][None], (Q, S, S))
        fsim = jnp.broadcast_to(fsim_ref[...][None], (Q, S, S))
        cre = bmm(fsre, ure) - bmm(fsim, uim)
        cim = bmm(fsre, uim) + bmm(fsim, ure)
        # pointwise filter multiply: one spectrum per channel, shared by
        # the batch rows of that channel (signal q = d·B + b)
        hre = hre_ref[...][:, None]
        him = him_ref[...][:, None]
        cre = cre.reshape(bd, Q // bd, S, R)
        cim = cim.reshape(bd, Q // bd, S, R)
        yre = (cre * hre - cim * him).reshape(Q, S, R)
        yim = (cre * him + cim * hre).reshape(Q, S, R)
        # inverse outer DFT: Dt = conj(FS) @ Yt
        dre = bmm(fsre, yre) + bmm(fsim, yim)
        dim = bmm(fsre, yim) - bmm(fsim, yre)
        # conjugate twiddle (elementwise)
        ere = dre * twre + dim * twim
        eim = dim * twre - dre * twim
        # inverse inner DFT — the conv output is real by construction, so
        # only the real plane: Re At = Et_re @ FRre + Et_im @ FRim
        are = (
            mm(ere.reshape(Q * S, R), frre_ref[...])
            + mm(eim.reshape(Q * S, R), frim_ref[...])
        ).reshape(Q, S, R)
        # gated-fusion finalize (fftconv._fused_epilogue policy): skip-add
        # in fp32, downcast, THEN gate in the output dtype — bit-identical
        # to the two-pass gate-after schedule
        skip = jnp.broadcast_to(
            skip_ref[...][:, None], (bd, Q // bd, 1, 1)
        ).reshape(Q, 1, 1)
        y = are * (1.0 / N) + xi_ref[...] * skip
        y = y.astype(o_ref.dtype)
        if gated:
            y = y * g_ref[...].astype(o_ref.dtype)
        o_ref[...] = y


def _to_signals(x, R: int, S: int):
    """(B, N, Dp) -> (Dp·B, S, R): signal q = d·B + b, with
    ``out[q, s, r] = x[b, r·S + s, d]`` (the four-step matrix, transposed
    so both DFT stages contract over a lane or a leading axis)."""
    B, N, Dp = x.shape
    return x.reshape(B, R, S, Dp).transpose(3, 0, 2, 1).reshape(Dp * B, S, R)


def _from_signals(y, B: int, R: int, S: int):
    """Inverse of :func:`_to_signals`."""
    Dp = y.shape[0] // B
    return y.reshape(Dp, B, S, R).transpose(1, 3, 2, 0).reshape(B, R * S, Dp)


# scoped-VMEM budget for one grid step: the finalize keeps ~a dozen
# (Q, S, R) fp32 planes live, past the 16 MiB default at hyena widths
_VMEM_LIMIT = 96 * 1024 * 1024
# default channel tile: the largest divisor of D whose (Q, S, R) fp32
# plane (lanes padded to 128) fits in _PLANE_BYTES, with at most
# _MAX_SIGNALS signals — Mosaic unrolls the batched outer-DFT matmuls
# over Q, so compile time grows with it
_PLANE_BYTES = 1 << 20
_MAX_SIGNALS = 64


def _pipeline_depth(S: int, overlap: int) -> int:
    """Largest depth <= ``overlap`` that splits S into whole 8-row sublane
    tiles (what a TPU block must be); 1 streams all S rows at once."""
    for ov in range(min(overlap, S), 1, -1):
        if S % ov == 0 and (S // ov) % 8 == 0:
            return ov
    return 1


@functools.partial(
    jax.jit,
    static_argnames=("factors", "block_d", "overlap", "interpret"),
)
def twolevel_fft_conv(
    u: jax.Array,  # (B, L, D)
    h: jax.Array,  # (D, L)
    skip: Optional[jax.Array] = None,  # (D,)
    gate: Optional[jax.Array] = None,  # (B, L, D) elementwise output gate
    *,
    factors: Optional[Tuple[int, int]] = None,  # autotuned (R, S) split
    block_d: Optional[int] = None,  # None: sized to _PLANE_BYTES
    overlap: int = 2,  # input pipeline depth (see _pipeline_depth)
    interpret: bool | None = None,  # None => interpret off-TPU only
) -> jax.Array:
    """Two-level overlapped FFT causal conv (ConvBackend contract): one
    ``pallas_call`` over a ``(channel_block, s_chunk)`` grid."""
    from repro.core.fftconv import next_fast_len

    interpret = resolve_interpret(interpret)
    B, L, D = u.shape
    N = next_fast_len(2 * L - 1)
    if factors is not None and factors[0] * factors[1] != N:
        factors = None  # stale plan for a different padded length
    R, S, FR, FS, TW = _dft_mats(N, factors)
    ov = _pipeline_depth(S, overlap)
    Sc = S // ov
    if block_d is None:
        plane = B * S * (-(-R // 128) * 128) * 4  # one channel's signals
        block_d = _largest_divisor_leq(
            D, min(_PLANE_BYTES // plane, _MAX_SIGNALS // B)
        )
    bd = max(1, min(block_d, D))
    pad_d = (-D) % bd
    Dp = D + pad_d
    Q = bd * B
    out_dtype = u.dtype
    pad = ((0, 0), (0, N - L), (0, pad_d))
    xt = _to_signals(jnp.pad(u.astype(jnp.float32), pad), R, S)
    h32 = jnp.pad(h.astype(jnp.float32), ((0, pad_d), (0, 0)))
    skip32 = (
        jnp.zeros((Dp,), jnp.float32) if skip is None
        else jnp.pad(skip.astype(jnp.float32), (0, pad_d))
    )
    # filter spectrum, precomputed with the SAME split (one small transform
    # per call, shared across the batch and the grid), transposed to the
    # signal layout: Ht[d, k2, k1] = X[k1 + k2·R]
    hp = jnp.pad(h32.T, ((0, N - L), (0, 0)))[None]  # (1, N, Dp)
    Ht = _four_step_fft(hp, N, (R, S))[0].transpose(2, 1, 0)  # (Dp, S, R)
    gated = gate is not None
    if gated:
        g_arg = _to_signals(jnp.pad(gate.astype(out_dtype), pad), R, S)
        g_spec = pl.BlockSpec((Q, S, R), lambda d, c: (d, 0, 0))
    else:
        g_arg = jnp.zeros((Dp * B, 8, R), out_dtype)
        g_spec = pl.BlockSpec((Q, 8, R), lambda d, c: (d, 0, 0))
    full = lambda d, c: (0, 0)  # noqa: E731 — block-pinned constants
    sig = pl.BlockSpec((Q, S, R), lambda d, c: (d, 0, 0))

    out = pl.pallas_call(
        functools.partial(_twolevel_kernel, N=N, overlap=ov, gated=gated),
        grid=(Dp // bd, ov),
        in_specs=[
            # s-chunk of the input (streams in per pipeline step)
            pl.BlockSpec((Q, Sc, R), lambda d, c: (d, c, 0)),
            pl.BlockSpec((R, R), full),
            pl.BlockSpec((R, R), full),
            pl.BlockSpec((S, S), full),
            pl.BlockSpec((S, S), full),
            pl.BlockSpec((S, R), full),
            pl.BlockSpec((S, R), full),
            # filter spectrum block for this channel tile
            pl.BlockSpec((bd, S, R), lambda d, c: (d, 0, 0)),
            pl.BlockSpec((bd, S, R), lambda d, c: (d, 0, 0)),
            # whole input block (skip term) + skip + gate, read at finalize
            sig,
            pl.BlockSpec((bd, 1, 1), lambda d, c: (d, 0, 0)),
            g_spec,
        ],
        out_specs=sig,
        out_shape=jax.ShapeDtypeStruct((Dp * B, S, R), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((Q, S, R), jnp.float32),
            pltpu.VMEM((Q, S, R), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(
        xt,
        jnp.asarray(FR.real), jnp.asarray(FR.imag),
        jnp.asarray(FS.real), jnp.asarray(FS.imag),
        jnp.asarray(TW.real.T), jnp.asarray(TW.imag.T),
        Ht.real, Ht.imag,
        xt, skip32.reshape(Dp, 1, 1), g_arg,
    )
    return _from_signals(out, B, R, S)[:, :L, :D]
