"""Chunked block-Toeplitz causal long-conv Pallas TPU kernel.

This is the MXU-native adaptation of the paper's fused CUDA FFTConv
(DESIGN.md §2).  The causal depthwise conv ``y_t = Σ_lag h_lag · u_{t-lag}``
is chunked into C-sized blocks; the contribution of lag-chunk ``k = i - j``
to output chunk ``i`` is a per-channel C×C Toeplitz matmul

    y_i[d] += T_k[d] @ u_j[d],     T_k[d][a, b] = h[d][kC + a - b]

evaluated as a channel-batched ``dot_general`` on the MXU.  Hyena filters are
exponential-decay windowed, so truncating to ``n_chunk_diags`` chunk
diagonals (banded support) turns the O(L²/C) schedule into O(L·K) while
keeping every FLOP on the systolic array instead of the VPU-bound FFT.

Causality inside the diagonal block is obtained *structurally*: the filter is
front-padded with C zeros, so negative lags index into the zero pad — no
masks in the inner loop.

The Hyena recurrence's data-controlled gate ``xⁿ ⊙ conv(v)`` fuses into the
kernel: the gate chunk rides in through one extra BlockSpec and multiplies
the downcast accumulator at finalize, in VMEM, so the gated conv output
hits HBM exactly once — the unfused path wrote the conv output and re-read
it for a separate full-tensor gate multiply.  The multiply happens in the
*output* dtype, bit-identical to the two-pass schedule it replaces
(core.fftconv._fused_epilogue documents the policy).

Grid: (d_block, i_chunk, j_rel) with j_rel (the chunk diagonal) innermost;
fp32 VMEM scratch accumulator, finalized on the last diagonal.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret


def _toeplitz_kernel(
    u_ref, ha_ref, hb_ref, ui_ref, skip_ref, g_ref, o_ref, acc_ref,
    *, C: int, K: int, gated: bool,
):
    r = pl.program_id(2)  # chunk diagonal (j_rel); j = i - r
    i = pl.program_id(1)

    @pl.when(r == 0)
    def _init():
        ui = ui_ref[...].astype(jnp.float32)  # (blk_d, B, C), u chunk i
        acc_ref[...] = ui * skip_ref[...][:, :, None]

    @pl.when(r <= i)
    def _accumulate():
        taps = jnp.concatenate(
            [ha_ref[...], hb_ref[...]], axis=1
        ).astype(jnp.float32)  # (blk_d, 2C); padded coords kC .. kC+2C-1
        bd = taps.shape[0]
        # W[d, s, t] = taps[d, C + t - s]: row s is the tap window shifted
        # by C + s — one strided lane rotation (the MXU-native skew), then
        # the first C lanes
        win = jnp.broadcast_to(taps[:, None, :], (bd, C, 2 * C))
        W = pltpu.roll(win, C, 2, stride=1, stride_axis=1)[:, :, :C]
        u = u_ref[...].astype(jnp.float32)  # (blk_d, B, C), u chunk j
        acc_ref[...] += jax.lax.dot_general(
            u, W, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )

    @pl.when(r == K - 1)
    def _finalize():
        y = acc_ref[...].astype(o_ref.dtype)
        if gated:
            # gate applied to the *downcast* accumulator, in VMEM: saves
            # the HBM round-trip of the two-pass schedule while staying
            # bit-identical to it (gate * conv in the output dtype —
            # fftconv._fused_epilogue documents why)
            y = y * g_ref[...].astype(o_ref.dtype)
        o_ref[...] = y


@functools.partial(
    jax.jit,
    static_argnames=("chunk", "block_d", "n_chunk_diags", "interpret"),
)
def toeplitz_conv(
    u: jax.Array,  # (B, L, D)
    h: jax.Array,  # (D, L)
    skip: jax.Array | None = None,  # (D,)
    gate: jax.Array | None = None,  # (B, L, D) elementwise output gate
    *,
    chunk: int = 128,
    block_d: int = 32,  # the (block_d, C, 2C) fp32 tap window: 4 MiB
    n_chunk_diags: int | None = None,
    interpret: bool | None = None,  # None => interpret off-TPU only
) -> jax.Array:
    interpret = resolve_interpret(interpret)
    B, L, D = u.shape
    # C stays whole even past L: the filter blocks are (block_d, C) lane
    # tiles, so a short sequence pads up to one chunk (causality keeps the
    # padded tail out of every real output)
    C = chunk
    pad_l = (-L) % C
    block_d = min(block_d, D)
    pad_d = (-D) % block_d
    if pad_l or pad_d:
        u = jnp.pad(u, ((0, 0), (0, pad_l), (0, pad_d)))
        h = jnp.pad(h, ((0, pad_d), (0, pad_l)))
        if gate is not None:
            gate = jnp.pad(gate, ((0, 0), (0, pad_l), (0, pad_d)))
    if skip is None:
        skip = jnp.zeros((h.shape[0],), jnp.float32)
    elif pad_d:
        skip = jnp.pad(skip, (0, pad_d))
    Lp, Dp = u.shape[1], u.shape[2]
    n_chunks = Lp // C
    K = n_chunks if n_chunk_diags is None else min(n_chunk_diags, n_chunks)
    # channel-major (Dp, B, Lp): each channel's Toeplitz matmul is a
    # (B, C) @ (C, C) MXU tile with the sequence chunk on the lanes
    ut = u.transpose(2, 0, 1)
    # front-pad C zeros => negative lags hit zeros (structural causality);
    # the last diagonal's high block needs one extra C of zeros at the end.
    hpad = jnp.pad(h, ((0, 0), (C, C)))  # (Dp, Lp + 2C)
    gated = gate is not None
    g_in = gate.transpose(2, 0, 1) if gated else jnp.zeros((Dp, B, 1), u.dtype)
    grid = (Dp // block_d, n_chunks, K)
    out = pl.pallas_call(
        functools.partial(_toeplitz_kernel, C=C, K=K, gated=gated),
        grid=grid,
        in_specs=[
            # u chunk j = i - r (clamped; masked when r > i)
            pl.BlockSpec(
                (block_d, B, C),
                lambda d, i, r: (d, 0, jnp.maximum(i - r, 0)),
            ),
            # filter window low/high blocks for lag-chunk k = r
            pl.BlockSpec((block_d, C), lambda d, i, r: (d, r)),
            pl.BlockSpec((block_d, C), lambda d, i, r: (d, r + 1)),
            # u chunk i (skip term, read at r == 0)
            pl.BlockSpec((block_d, B, C), lambda d, i, r: (d, 0, i)),
            pl.BlockSpec((block_d, 1), lambda d, i, r: (d, 0)),
            # gate chunk i (read at finalize; dummy column when ungated)
            pl.BlockSpec(
                (block_d, B, C if gated else 1),
                (lambda d, i, r: (d, 0, i)) if gated
                else (lambda d, i, r: (d, 0, 0)),
            ),
        ],
        out_specs=pl.BlockSpec((block_d, B, C), lambda d, i, r: (d, 0, i)),
        out_shape=jax.ShapeDtypeStruct(ut.shape, u.dtype),
        scratch_shapes=[pltpu.VMEM((block_d, B, C), jnp.float32)],
        interpret=interpret,
    )(ut, hpad, hpad, ut, skip.astype(jnp.float32).reshape(-1, 1), g_in)
    out = out.transpose(1, 2, 0)
    if pad_l or pad_d:
        out = out[:, :L, :D]
    return out
