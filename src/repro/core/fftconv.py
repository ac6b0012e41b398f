"""Causal long convolution via FFT (paper §2.1 "Fast Methods for
Convolutions", Prop. 3.1 causality note).

The aperiodic causal convolution ``y_t = Σ_{n≤t} h_{t-n} u_n`` is evaluated
by zero-padding input and filter to ``2L`` and multiplying in the frequency
domain — ``iFFT(D_H FFT(pad(u)))`` — in ``O(L log L)``.  Causality holds
because the filter is evaluated at ``t = 0..L-1`` only and the padding
prevents circular wrap-around (paper: "all we need is to evaluate the filter
at t=0,…,L−1 and zero-pad ... to 2L−1 before taking FFT").

The transform always runs in fp32 (bf16 loses too much precision over
long reductions); inputs/outputs keep their dtype.  Which transform runs
follows the platform, read when the conv is traced: XLA's real FFT on the
CPU and GPU; on a TPU the four-step DFT as fp32-exact (``HIGHEST``) MXU
matmuls, with its own VJP (``core/blockfft.py``), because XLA's TPU FFT
moves the transform axis minor and back through HBM many times.

The optional ``gate`` argument fuses the Hyena recurrence's data-controlled
gate ``xⁿ ⊙ conv(v)`` into the single post-iFFT elementwise expression —
skip-add and gate-multiply happen in fp32 before the downcast, in one pass
over the tensor instead of a separate full-tensor multiply (DESIGN.md §7).

Layouts: activations are channel-last ``(B, L, D)``; filters ``(D, L)``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.platform import on_tpu


def next_fast_len(n: int) -> int:
    """Smallest 5-smooth (2^a·3^b·5^c) integer >= n.

    ``jnp.fft`` plans degrade badly on lengths with large prime factors;
    padding the conv's ``fft_size`` up to the next fast composite keeps odd
    and prime-ish L off the worst-case DFT path at the cost of a few extra
    (already-zero-padded) points."""
    if n <= 1:
        return 1
    best = 1 << (n - 1).bit_length()  # next power of two is always valid
    f5 = 1
    while f5 < best:
        f35 = f5
        while f35 < best:
            f = f35
            while f < n:
                f *= 2
            if f < best:
                best = f
            f35 *= 3
        f5 *= 5
    return best


def _fused_epilogue(y, u32, skip, gate, dtype):
    """One elementwise pass: y (+ skip·u) in fp32, downcast, then (· gate)
    in the output dtype.

    The gate multiplies the *downcast* conv output on purpose: fusion must
    be a pure memory-traffic optimization, bit-identical to the two-pass
    schedule ``gate * conv(u)`` it replaces — keeping fp32 through the gate
    would be more precise but would make enabling fusion change bf16 model
    outputs (DESIGN.md §7)."""
    if skip is not None:
        y = y + u32 * skip[None, None, :].astype(jnp.float32)
    y = y.astype(dtype)
    if gate is not None:
        y = y * gate.astype(dtype)
    return y


def fft_causal_conv(
    u: jax.Array,  # (B, L, D)
    h: jax.Array,  # (D, L)
    skip: Optional[jax.Array] = None,  # (D,) residual gain: y += skip * u
    gate: Optional[jax.Array] = None,  # (B, L, D) elementwise output gate
) -> jax.Array:
    """Depthwise causal convolution of every channel with its own length-L
    filter, via a DFT on ``next_fast_len(2L - 1)`` points.

    The transform follows the platform the program runs on, read when the
    conv is traced: on a TPU the four-step DFT on the MXU
    (``blockfft.blockfft_causal_conv``, fp32-exact matmuls), because
    XLA's TPU FFT moves the transform axis minor and back through HBM many
    times; elsewhere XLA's real FFT.  Both compute the same conv in fp32."""
    if on_tpu():
        from repro.core.blockfft import blockfft_causal_conv

        return blockfft_causal_conv(u, h, skip, gate)
    B, L, D = u.shape
    assert h.shape == (D, L), (h.shape, (D, L))
    # linear conv of two length-L signals has support 2L-1; any fft_size
    # >= 2L-1 keeps the first L outputs free of circular wrap-around, so
    # the truncation back to L is exact (the padding only adds zeros).
    fft_size = next_fast_len(2 * L - 1)
    assert fft_size >= 2 * L - 1, (fft_size, L)
    dtype = u.dtype
    u32 = u.astype(jnp.float32)
    h32 = h.astype(jnp.float32)
    U = jnp.fft.rfft(u32, n=fft_size, axis=1)  # (B, F, D)
    H = jnp.fft.rfft(h32, n=fft_size, axis=1).T  # (F, D)
    y = jnp.fft.irfft(U * H[None], n=fft_size, axis=1)[:, :L, :]
    return _fused_epilogue(y, u32, skip, gate, dtype)


def fft_causal_conv_sharded(
    u: jax.Array,  # (B, L, D)
    h: jax.Array,  # (D, L)
    skip: Optional[jax.Array] = None,
    gate: Optional[jax.Array] = None,  # (B, L, D), same layout as u
) -> jax.Array:
    """FFT conv under shard_map: the XLA SPMD partitioner cannot partition
    the FFT custom-call — sharding constraints around it only relocate a
    full all-gather of the activation (measured 260 GB/chip/layer in the
    dry-run baseline).  Hyena's long conv is depthwise, so forcing
    per-shard execution with shard_map (batch on data axes, channels on
    model) removes that traffic entirely: zero collectives inside the conv
    (EXPERIMENTS.md §Perf pair A).
    """
    from repro.distributed.ctx import current_mesh, shard_map
    from jax.sharding import PartitionSpec as P

    mesh = current_mesh()
    B, L, D = u.shape
    if mesh is None:
        return fft_causal_conv(u, h, skip, gate)
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    data_sz = 1
    for a in data_axes:
        data_sz *= mesh.shape[a]
    model = "model" if "model" in mesh.shape else None
    model_sz = mesh.shape.get("model", 1)
    if (data_axes and B % data_sz) or (model and D % model_sz):
        return fft_causal_conv(u, h, skip, gate)
    bspec = data_axes if len(data_axes) > 1 else (data_axes[0] if data_axes else None)
    skip_in = skip if skip is not None else jnp.zeros((D,), jnp.float32)
    act_spec = P(bspec, None, model)
    if gate is None:
        fn = shard_map(
            lambda ub, hb, sb: fft_causal_conv(ub, hb, sb),
            mesh=mesh,
            in_specs=(act_spec, P(model, None), P(model)),
            out_specs=act_spec,
            check=False,  # FFT transpose rule trips the vma checker under AD
        )
        return fn(u, h, skip_in)
    # the gate shares u's activation layout, so fusing it keeps the conv
    # collective-free: the gate multiply happens on the shard, per chip
    fn = shard_map(
        lambda ub, hb, sb, gb: fft_causal_conv(ub, hb, sb, gb),
        mesh=mesh,
        in_specs=(act_spec, P(model, None), P(model), act_spec),
        out_specs=act_spec,
        check=False,
    )
    return fn(u, h, skip_in, gate)


def direct_causal_conv(
    u: jax.Array,  # (B, L, D)
    h: jax.Array,  # (D, L)
    skip: Optional[jax.Array] = None,
    gate: Optional[jax.Array] = None,  # (B, L, D)
) -> jax.Array:
    """O(L²) reference: materializes the lower-triangular Toeplitz matmul.

    Used as the oracle in tests and for tiny L.
    """
    B, L, D = u.shape
    t = jnp.arange(L)
    idx = t[:, None] - t[None, :]  # (L, L), h index; negative => acausal
    mask = idx >= 0
    # S[d, i, j] = h[d, i - j] for i >= j else 0
    S = jnp.where(mask[None], h[:, jnp.clip(idx, 0, L - 1)], 0.0)  # (D, L, L)
    u32 = u.astype(jnp.float32)
    y = jnp.einsum("dij,bjd->bid", S.astype(jnp.float32), u32)
    return _fused_epilogue(y, u32, skip, gate, u.dtype)


@jax.named_scope("short_conv")
def short_causal_conv(
    u: jax.Array,  # (B, L, D)
    w: jax.Array,  # (D, K) short explicit filter (K ~ 3/4)
    bias: Optional[jax.Array] = None,  # (D,)
) -> jax.Array:
    """Depthwise causal FIR conv with a short explicit filter (Alg. 1 step 2).

    ``y_t = Σ_{k<K} w_k · u_{t-k}`` — implemented as K shifted adds (cheap,
    fuses well under XLA; the Pallas kernel version lives in repro.kernels).
    """
    B, L, D = u.shape
    K = w.shape[1]
    y = jnp.zeros_like(u, dtype=jnp.float32)
    u32 = u.astype(jnp.float32)
    for k in range(K):
        shifted = u32 if k == 0 else jnp.pad(u32, ((0, 0), (k, 0), (0, 0)))[:, :L]
        y = y + shifted * w[:, k][None, None, :].astype(jnp.float32)
    if bias is not None:
        y = y + bias[None, None, :].astype(jnp.float32)
    return y.astype(u.dtype)


def conv_cache_step(
    cache: jax.Array,  # (B, L_cache, D) rolling buffer of past inputs
    u_t: jax.Array,  # (B, D) new input at the current step
    h: jax.Array,  # (D, L) filter (only first L_cache+ taps used)
    skip: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array]:
    """Single-token decode for a long conv: O(L_cache·D) dot with cached
    inputs.  Cache layout: cache[:, 0] is the *newest* element (time t), so
    ``y_t = Σ_n h_n · u_{t-n} = Σ_n h_n · cache[:, n]``.

    Reference semantics for one order: the production decode path
    (``operator.hyena_decode_step``) evaluates all N orders' history dots
    in one stacked dot_general instead of calling this per order, but must
    stay numerically equivalent to it (pinned by the decode-parity tests).

    Returns (y_t (B, D), new_cache).
    """
    B, Lc, D = cache.shape
    cache = jnp.concatenate([u_t[:, None, :], cache[:, : Lc - 1]], axis=1)
    taps = h[:, :Lc].astype(jnp.float32)  # (D, Lc)
    y = jnp.einsum("bld,dl->bd", cache.astype(jnp.float32), taps)
    if skip is not None:
        y = y + u_t.astype(jnp.float32) * skip[None, :].astype(jnp.float32)
    return y.astype(u_t.dtype), cache
