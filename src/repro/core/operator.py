"""The order-N Hyena operator (paper Def. 3.1, Algorithms 1–3).

Forward pass (Algorithm 3), width D, order N, channel-last activations:

  1. Projection (Alg. 1): ``ẑ = Linear(u)`` with Linear: D → (N+1)·D, then a
     depthwise **short** causal conv (explicit FIR, width 3), then split into
     ``x¹..x^N, v``.
  2. Filters (Alg. 2): ``h¹..h^N`` from the implicit FFN parameterization
     (:mod:`repro.core.filters`).
  3. Recurrence: ``v ← x^n ⊙ FFTConv(h^n, v)`` for n = 1..N; output
     projection D → D.  The gate ``x^n ⊙`` is *fused into the conv backend*
     (conv_api's gated contract, DESIGN.md §7): the operator never runs a
     standalone full-tensor gate multiply.

Equivalently ``y = H(u)v`` with ``H(u) = D_x^N S_h^N ⋯ D_x^1 S_h^1`` — tested
against :mod:`repro.core.matrices`.  H3 == Hyena₂, GSS == Hyena₁ (Rmk 3.2).

The conv backend is pluggable through the :mod:`repro.core.conv_api`
registry: ``fft`` (default, O(L log L)), ``fft_local``, ``direct`` (O(L²)
oracle), ``blockfft`` (MXU four-step FFT), or ``toeplitz`` (Pallas chunked
block-Toeplitz MXU kernel — the TPU adaptation of the paper's fused CUDA
FFTConv; see DESIGN.md §2).
"""
from __future__ import annotations

import dataclasses
import weakref
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.param import Ax
from repro.core import filters as F
from repro.core.conv_api import get_conv_backend
from repro.core.fftconv import short_causal_conv


@dataclasses.dataclass(frozen=True)
class HyenaConfig:
    d_model: int
    order: int = 2
    short_filter_len: int = 3
    filter: F.FilterConfig = None  # type: ignore[assignment]
    use_bias: bool = True
    # NOTE: the long-conv backend is deliberately NOT part of this config —
    # it is an execution concern resolved exactly once, by the caller's
    # ApplyContext (repro.models.mixer_api) against repro.core.conv_api.

    def __post_init__(self):
        if self.filter is None:
            object.__setattr__(
                self, "filter", F.FilterConfig(d_model=self.d_model, order=self.order)
            )


def init_hyena(key, cfg: HyenaConfig) -> Dict[str, Any]:
    D, N = cfg.d_model, cfg.order
    k_in, k_out, k_short, k_filt = jax.random.split(key, 4)
    inner = (N + 1) * D
    params: Dict[str, Any] = {
        "in_proj": {
            "w": Ax(
                jax.random.normal(k_in, (D, inner), jnp.float32) / jnp.sqrt(D),
                ("embed", "hyena_inner"),
            ),
        },
        "out_proj": {
            "w": Ax(
                jax.random.normal(k_out, (D, D), jnp.float32) / jnp.sqrt(D),
                ("hyena_out", "embed"),
            ),
        },
        # short explicit depthwise filter over all (N+1)·D projected channels
        "short_filter": Ax(
            jax.random.normal(k_short, (inner, cfg.short_filter_len), jnp.float32)
            / jnp.sqrt(cfg.short_filter_len),
            ("hyena_inner", None),
        ),
        "filters": F.init_hyena_filter(k_filt, cfg.filter),
    }
    if cfg.use_bias:
        params["in_proj"]["b"] = Ax(jnp.zeros((inner,), jnp.float32), ("hyena_inner",))
        params["out_proj"]["b"] = Ax(jnp.zeros((D,), jnp.float32), ("embed",))
    return params


@jax.named_scope("hyena_proj")
def in_projection(params, u: jax.Array) -> jax.Array:
    """D → (N+1)·D, with its bias when the operator has one."""
    z = u @ params["in_proj"]["w"].astype(u.dtype)
    if "b" in params["in_proj"]:
        z = z + params["in_proj"]["b"].astype(u.dtype)
    return z


@jax.named_scope("hyena_proj")
def out_projection(params, v: jax.Array) -> jax.Array:
    """D → D, with its bias when the operator has one."""
    y = v @ params["out_proj"]["w"].astype(v.dtype)
    if "b" in params["out_proj"]:
        y = y + params["out_proj"]["b"].astype(v.dtype)
    return y


def _project(params, cfg: HyenaConfig, u: jax.Array):
    """Algorithm 1: linear → short depthwise causal conv → split."""
    N = cfg.order
    z = in_projection(params, u)
    z = short_causal_conv(z, params["short_filter"])  # (B, L, (N+1)·D)
    parts = jnp.split(z, N + 1, axis=-1)
    v, xs = parts[0], parts[1:]
    return v, xs


def hyena_operator(
    params, cfg: HyenaConfig, u: jax.Array, *, conv_backend: Optional[str] = None
) -> jax.Array:
    """y = Hyena_N(u), u: (B, L, D) -> (B, L, D).

    ``conv_backend`` names a :mod:`repro.core.conv_api` registration
    (default ``"fft"``); unknown names raise here, before any tracing.
    """
    B, L, D = u.shape
    backend = get_conv_backend(conv_backend)
    backend.validate_len(L)
    v, xs = _project(params, cfg, u)
    h = F.evaluate_filters(params["filters"], cfg.filter, L)  # (N, D, L)
    skip = F.filter_skip(params["filters"], cfg.filter)  # (N, D)
    for n in range(cfg.order):
        v = backend(v, h[n], skip[n], gate=xs[n]).astype(u.dtype)
    return out_projection(params, v)


# ---------------------------------------------------------------------------
# Decode path: O(L_cache) per token via cached projected inputs.
# ---------------------------------------------------------------------------


def init_decode_cache(cfg: HyenaConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Caches for single-token decode.

    - ``short``: last (short_filter_len - 1) projected inputs, per channel,
      newest-first (a tiny rolling window).
    - ``long``: the recurrence operand ``z^n`` for every order (the conv
      input at order n) stored at its **absolute position**: the value fed
      at step ``p`` lives at index ``p`` and is never moved again.  One
      dynamic write per token (no O(max_len) shift), so the history can
      live in copy-on-write paged blocks (``repro.serve.paged``) without
      dirtying every page on every step.  Positions ``>= t`` are unwritten
      (zero or stale) and masked out of the decode contraction.
    """
    D, N = cfg.d_model, cfg.order
    inner = (N + 1) * D
    return {
        "short": jnp.zeros((batch, cfg.short_filter_len - 1, inner), dtype),
        "long": jnp.zeros((N, batch, max_len, D), dtype),
        # per-row position counter (continuous batching: one request per row)
        "t": jnp.zeros((batch,), jnp.int32),
    }


# One-time host-side memo for callers that forgot precompute_decode_filters:
# the taps of a given (filter params, cfg.filter, L_cache) are evaluated on
# the FIRST fallback decode step and reused for every later token, instead of
# re-running the full filter FFN over the whole cache grid per token (a
# serving-latency cliff).  Keyed by param-leaf ids with a weakref eviction
# hook (jax arrays are weakref-able but not hashable) so updated / freed
# params drop their taps; the cache treedef is untouched (the mixer contract
# requires decode_step to preserve it for lax.scan).
_FALLBACK_TAPS: Dict[tuple, tuple] = {}


def _fallback_decode_taps(params, cfg: HyenaConfig, Lc: int):
    leaves = jax.tree_util.tree_leaves(params["filters"])
    if not leaves or any(isinstance(l, jax.core.Tracer) for l in leaves):
        # traced decode paths must precompute (prefill does); evaluating
        # here would bake the FFN into every unrolled/scanned step
        return (
            F.evaluate_filters(params["filters"], cfg.filter, Lc),
            F.filter_skip(params["filters"], cfg.filter),
        )
    key = (cfg.filter, Lc, tuple(id(l) for l in leaves))
    hit = _FALLBACK_TAPS.get(key)
    if hit is not None and all(
        r() is l for r, l in zip(hit[0], leaves)
    ):  # id-reuse guard: EVERY leaf must still be the object we memoized
        return hit[1]
    taps = (
        F.evaluate_filters(params["filters"], cfg.filter, Lc),
        F.filter_skip(params["filters"], cfg.filter),
    )
    evict = lambda _, k=key: _FALLBACK_TAPS.pop(k, None)
    _FALLBACK_TAPS[key] = (
        tuple(weakref.ref(l, evict) for l in leaves),
        taps,
    )
    return taps


def hyena_decode_step(
    params, cfg: HyenaConfig, u_t: jax.Array, cache: Dict[str, Any]
) -> Tuple[jax.Array, Dict[str, Any]]:
    """One token: u_t (B, D) -> y_t (B, D), updated cache.

    Matches ``hyena_operator`` teacher-forced outputs (tested).  The cache
    holds each order's operand at its absolute position (append-only, see
    :func:`init_decode_cache`), so with per-row cursor ``t``

        y^n_t = (h^n_0 + skip^n)·v^n_t + Σ_{p<t} h^n_{t-p}·v^n_p

    The lag taps ``h^n_{t-p}`` for ``p = 0..Lc-1`` are one contiguous slice
    of the reversed tap grid starting at ``Th-1-t`` (per row, a
    dynamic_slice); positions ``p >= t`` — unwritten or stale from a
    recycled page — are masked to zero, so the history term tolerates
    arbitrary garbage past the cursor.  All N orders then contract in one
    stacked fp32 einsum; only the cheap rank-1 correction
    ``(h^n_0 + skip^n)·v`` stays inside the sequential order loop.

    The cache length ``Lc`` may be SHORTER than the tap grid ``Th`` (a
    paged engine gathers a view just covering the live prefix); the only
    requirement is ``t < min(Lc + 1, Th)`` — positions and taps past the
    view are out of contract, exactly like decoding past ``max_len``.

    Filter taps should be precomputed (``precompute_decode_filters`` /
    mixer prefill).  A cache without taps falls back to a ONE-TIME
    host-side evaluation (memoized per filter params × cache length) —
    never the old per-token filter-FFN re-evaluation cliff.
    """
    B, Dm = u_t.shape
    N = cfg.order
    Lc = cache["long"].shape[2]
    h = cache.get("h")
    skip = cache.get("skip")
    if h is None:
        h, skip = _fallback_decode_taps(params, cfg, Lc)
    # --- projection + short conv (explicit taps over a tiny rolling window)
    z = in_projection(params, u_t)
    w = params["short_filter"]  # (inner, K)
    hist = cache["short"]  # (B, K-1, inner) newest-first
    zc = z.astype(jnp.float32) * w[:, 0].astype(jnp.float32)[None, :]
    for k in range(1, cfg.short_filter_len):
        zc = zc + hist[:, k - 1].astype(jnp.float32) * w[:, k].astype(jnp.float32)[None, :]
    new_short = jnp.concatenate(
        [z[:, None, :], hist[:, : cfg.short_filter_len - 2]], axis=1
    )
    zc = zc.astype(u_t.dtype)
    parts = jnp.split(zc, N + 1, axis=-1)
    v, xs = parts[0], parts[1:]
    # --- recurrence: one stacked history contraction for all orders over
    # the absolute-position operand cache.  The Σ_{p<t} term (the expensive
    # O(N·B·Lc·D) part) only reads the cache, never the current v^n, so all
    # orders share one einsum; per-row lag taps are a dynamic_slice of the
    # reversed grid, masked past the cursor.
    t = cache["t"]  # (B,) per-row absolute position (== tokens absorbed)
    Th = h.shape[2]
    hist32 = cache["long"].astype(jnp.float32)  # (N, B, Lc, D)
    h_rev = jnp.flip(h, axis=2).astype(jnp.float32)  # (N, D, Th)
    h_ext = jnp.pad(h_rev, ((0, 0), (0, 0), (0, Lc)))

    def row_taps(tb):
        # taps[p] = h[t - p] for p < t, else 0: slice of the reversed grid
        a = jax.lax.dynamic_slice(h_ext, (0, 0, Th - 1 - tb), (N, Dm, Lc))
        return a * (jnp.arange(Lc) < tb)[None, None, :]

    taps = jax.vmap(row_taps)(t)  # (B, N, D, Lc) fp32
    hist = jnp.einsum("nbpd,bndp->nbd", hist32, taps)  # fp32 accumulate
    h0 = (h[:, :, 0] + skip).astype(jnp.float32)  # (N, D) fused rank-1 taps
    ldtype = cache["long"].dtype
    vs = []
    for n in range(N):
        vs.append(v.astype(ldtype))
        conv_y = hist[n] + v.astype(jnp.float32) * h0[n][None, :]
        v = xs[n] * conv_y.astype(u_t.dtype)
    y = out_projection(params, v)
    rows = jnp.arange(B)
    new_long = cache["long"].at[:, rows, t].set(jnp.stack(vs))
    out_cache = dict(cache)
    out_cache.update(
        {"short": new_short, "long": new_long, "t": cache["t"] + 1}
    )
    return y, out_cache


def precompute_decode_filters(params, cfg: HyenaConfig, max_len: int, cache):
    """Evaluate filter taps once per sequence and stash them in the cache."""
    cache = dict(cache)
    cache["h"] = F.evaluate_filters(params["filters"], cfg.filter, max_len)
    cache["skip"] = F.filter_skip(params["filters"], cfg.filter)
    return cache
