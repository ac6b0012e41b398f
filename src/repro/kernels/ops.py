"""jit'd public wrappers for the Pallas kernels.

Dispatch policy (:func:`repro.kernels.platform.resolve_use_kernel`): on
TPU the Pallas lowering always runs natively.  Off-TPU the default is the
pure-jnp oracle in :mod:`repro.kernels.ref` — bit-compatible semantics by
construction (tested) — and ``use_kernel=True`` runs the *same kernel body*
in the Pallas interpreter.

Tile sizes come from the autotuned conv-plan cache (:mod:`repro.core.
autotune`, ``$REPRO_AUTOTUNE``): this module is the consultation point, so
model code never names a ``block_l``/``chunk``/``block_d``.  Explicit kwargs
from the caller always override the plan.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import autotune
from repro.kernels import flash_attention as _fa
from repro.kernels import ref as _ref
from repro.kernels import rmsnorm as _rn
from repro.kernels import short_conv as _sc
from repro.kernels import toeplitz_conv as _tc
from repro.kernels.platform import resolve_use_kernel


def _dedup(cands):
    out = []
    for c in cands:
        if c not in out:
            out.append(c)
    return out


def _short_conv_plan(shape, dtype, K: int, gated: bool):
    B, L, D = shape
    cands = _dedup(
        {"block_l": min(bl, L), "block_d": min(bd, D)}
        for bl in (128, 256, 512, 1024)
        for bd in (128, 256)
    )

    def run(**tiles):
        u = jnp.ones(shape, dtype)
        w = jnp.ones((D, K), jnp.float32)
        g = jnp.ones(shape, dtype) if gated else None
        return _sc.short_conv_gate(u, w, g, **tiles)

    # K changes the halo width and arithmetic intensity — different K,
    # different plan (the toeplitz band is keyed for the same reason)
    kind = ("short_conv_gated" if gated else "short_conv") + f"_k{K}"
    return autotune.plan_for(kind, shape, dtype, candidates=cands, run=run)


def _toeplitz_plan(shape, dtype, gated: bool, n_chunk_diags):
    B, L, D = shape
    # chunks are whole 128-lane tiles; the (block_d, C, 2C) fp32 tap
    # window bounds block_d by the scoped VMEM
    cands = _dedup(
        {"chunk": c, "block_d": min(bd, D)}
        for c in (128, 256)
        for bd in (8, 16, 32)
    )

    def run(**tiles):
        u = jnp.ones(shape, dtype)
        h = jnp.ones((D, L), jnp.float32)
        g = jnp.ones(shape, dtype) if gated else None
        return _tc.toeplitz_conv(
            u, h, None, g, n_chunk_diags=n_chunk_diags, **tiles
        )

    # the band is an approximation knob the *caller* chose — it keys the
    # plan (different compute shape) but is never searched over
    kind = "toeplitz" + ("_gated" if gated else "")
    if n_chunk_diags is not None:
        kind += f"_band{n_chunk_diags}"
    return autotune.plan_for(kind, shape, dtype, candidates=cands, run=run)


def short_conv_gate(u, w, gate=None, *, use_kernel: bool | None = None, **kw):
    use_kernel = resolve_use_kernel(use_kernel)
    if use_kernel:
        # mode() guard first: with autotune off (the default) the hot path
        # must not pay for candidate construction on every dispatch
        if (autotune.mode() != "off"
                and "block_l" not in kw and "block_d" not in kw):
            plan = _short_conv_plan(
                u.shape, u.dtype, w.shape[1], gate is not None
            )
            if plan:
                kw = {**plan, **kw}
        return _sc.short_conv_gate(u, w, gate, **kw)
    return _ref.short_conv_gate(u, w, gate)


def toeplitz_conv(u, h, skip=None, gate=None, *,
                  use_kernel: bool | None = None, **kw):
    use_kernel = resolve_use_kernel(use_kernel)
    if use_kernel:
        if (autotune.mode() != "off"
                and "chunk" not in kw and "block_d" not in kw):
            plan = _toeplitz_plan(
                u.shape, u.dtype, gate is not None, kw.get("n_chunk_diags")
            )
            if plan:
                kw = {**plan, **kw}
        return _tc.toeplitz_conv(u, h, skip, gate, **kw)
    return _ref.toeplitz_conv(
        u, h, skip, gate, n_chunk_diags=kw.get("n_chunk_diags")
    )


def flash_attention(q, k, v, *, use_kernel: bool | None = None, **kw):
    use_kernel = resolve_use_kernel(use_kernel)
    if use_kernel:
        return _fa.flash_attention(q, k, v, **kw)
    kw.pop("blk_q", None), kw.pop("blk_k", None)
    return _ref.flash_attention(q, k, v, **kw)


def rmsnorm(x, g, *, use_kernel: bool | None = None, **kw):
    use_kernel = resolve_use_kernel(use_kernel)
    if use_kernel:
        return _rn.rmsnorm(x, g, **kw)
    return _ref.rmsnorm(x, g, eps=kw.get("eps", 1e-6))
