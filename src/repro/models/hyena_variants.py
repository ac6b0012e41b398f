"""Hyena SE/MR/LI multi-hybrid operator variants (StripedHyena-2 style,
arXiv:2503.01868), registered as first-class token mixers.

The multi-hybrid result: interleaving *short explicit* (SE), *medium
regularized* (MR), and *long implicit* (LI) hyena layers beats any single
operator at equal compute — the short layers carry local token mixing at
FIR cost, the medium layers carry syntax-scale context from a fixed-support
implicit filter, and only the (fewer) long layers pay for the full-length
FFT conv.  All three share the Hyena projection/gating recurrence
(``repro.core.operator``); they differ only in the filter parameterization
and therefore in decode-state shape:

  ``hyena_se``  explicit taps ``(order, D, se_len)`` as *parameters*;
                train/prefill is a depthwise FIR (shifted adds — stays
                sequence-sharded under cp with SPMD halo exchange, no
                channel all-to-all); decode is a stacked short-conv dot
                over an ``(se_len-1)``-deep rolling operand window.
  ``hyena_mr``  the implicit filter FFN evaluated on a FIXED
                ``support``-point grid (taps are length-invariant, unlike
                LI's length-L grid), zero-padded to L for the full-sequence
                conv — which routes through the registry backend from
                ``ExecutionContext.conv_backend_for(L)`` (blockfft_overlap
                / fft_sp under cp), gate fused; decode is the same stacked
                window dot with ``support-1`` depth.
  ``hyena_li``  the existing full-length implicit operator
                (:class:`repro.models.hyena.HyenaMixer`) under its
                multi-hybrid name.

SE/MR decode state is O(window), not O(max_len): their cache windows are
bounded rolling buffers (newest-first, zero-padded — decode needs no
cursor masking), so both are *pinned* leaves under the paged allocator
(``cache_page_axes() == {}``; paging a bounded window buys nothing).
Multi-hybrid pattern rules (which stripings are coherent) are validated at
config registration in ``repro.configs.base``.  DESIGN.md §14.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.param import Ax
from repro.core import filters as F
from repro.core.conv_api import get_conv_backend
from repro.core.fftconv import short_causal_conv
from repro.core.operator import (
    _fallback_decode_taps,
    in_projection,
    out_projection,
)
from repro.distributed.ctx import shard
from repro.models.hyena import HyenaMixer
from repro.models.mixer_api import (
    DEFAULT_CONTEXT,
    ApplyContext,
    TokenMixer,
    register_mixer,
)


# --------------------------------------------------------------- configs

@dataclasses.dataclass(frozen=True)
class HyenaSEConfig:
    d_model: int
    order: int = 2
    se_len: int = 8  # explicit FIR taps per order (the SE filter support)
    short_filter_len: int = 3
    use_bias: bool = True


@dataclasses.dataclass(frozen=True)
class HyenaMRConfig:
    d_model: int
    order: int = 2
    support: int = 128  # fixed tap-grid length M (filters are zero past M)
    short_filter_len: int = 3
    filter: F.FilterConfig = None  # type: ignore[assignment]
    use_bias: bool = True

    def __post_init__(self):
        if self.filter is None:
            object.__setattr__(
                self,
                "filter",
                F.FilterConfig(d_model=self.d_model, order=self.order),
            )


# ------------------------------------------------- shared projection path

def _init_projection(key, d_model: int, order: int, short_filter_len: int,
                     use_bias: bool) -> Dict[str, Any]:
    """in/out projections + depthwise short filter — identical layout (and
    logical param axes) to ``operator.init_hyena``, so the TP rules and the
    block layer see the same tree shape across all hyena variants."""
    D, N = d_model, order
    k_in, k_out, k_short = jax.random.split(key, 3)
    inner = (N + 1) * D
    params: Dict[str, Any] = {
        "in_proj": {
            "w": Ax(
                jax.random.normal(k_in, (D, inner), jnp.float32)
                / jnp.sqrt(D),
                ("embed", "hyena_inner"),
            ),
        },
        "out_proj": {
            "w": Ax(
                jax.random.normal(k_out, (D, D), jnp.float32) / jnp.sqrt(D),
                ("hyena_out", "embed"),
            ),
        },
        "short_filter": Ax(
            jax.random.normal(
                k_short, (inner, short_filter_len), jnp.float32
            ) / jnp.sqrt(short_filter_len),
            ("hyena_inner", None),
        ),
    }
    if use_bias:
        params["in_proj"]["b"] = Ax(
            jnp.zeros((inner,), jnp.float32), ("hyena_inner",)
        )
        params["out_proj"]["b"] = Ax(
            jnp.zeros((D,), jnp.float32), ("embed",)
        )
    return params


def _project_seq_sharded(params, order: int, x: jax.Array, seq_axis):
    """Algorithm 1 under the residual-stream layout: linear (weights
    gathered), seq-sharded short conv (SPMD halo exchange), split."""
    z = in_projection(params, x)
    z = shard(z, "data", seq_axis, None)
    z = short_causal_conv(z, params["short_filter"])
    parts = jnp.split(z, order + 1, axis=-1)
    return z, parts[0], parts[1:]


def _decode_project(params, cfg, u_t, cache):
    """Decode-time Algorithm 1 over the tiny rolling short-conv window —
    the same math as ``operator.hyena_decode_step``'s projection block."""
    z = in_projection(params, u_t)
    w = params["short_filter"]  # (inner, K)
    hist = cache["short"]  # (B, K-1, inner) newest-first
    zc = z.astype(jnp.float32) * w[:, 0].astype(jnp.float32)[None, :]
    for k in range(1, cfg.short_filter_len):
        zc = zc + hist[:, k - 1].astype(jnp.float32) * (
            w[:, k].astype(jnp.float32)[None, :]
        )
    new_short = jnp.concatenate(
        [z[:, None, :], hist[:, : cfg.short_filter_len - 2]], axis=1
    )
    zc = zc.astype(u_t.dtype)
    parts = jnp.split(zc, cfg.order + 1, axis=-1)
    return new_short, parts[0], parts[1:]


def _newest_first(seq: jax.Array, k: int, L: int, dtype) -> jax.Array:
    """(B, L, D) -> (B, k, D) rolling window, newest at index 0, zero-padded
    short prompts (so decode needs no cursor mask)."""
    n = min(L, k)
    recent = jnp.flip(seq[:, L - n:], axis=1).astype(dtype)
    return jnp.pad(recent, ((0, 0), (0, k - n), (0, 0)))


def _window_decode(win: jax.Array, taps: jax.Array):
    """Stacked short-conv decode dot: ``win (N, B, W, D)`` newest-first
    operand windows × lag taps ``taps[:, :, 1:] (N, D, W)`` — one fp32
    einsum for all orders (window index k holds v_{t-1-k}, tap index k+1
    is lag k+1)."""
    return jnp.einsum(
        "nbkd,ndk->nbd", win.astype(jnp.float32),
        taps[:, :, 1:].astype(jnp.float32),
    )


def _roll_window(win_n: jax.Array, v: jax.Array):
    """Prepend the current operand to a newest-first window (drop oldest)."""
    W = win_n.shape[1]
    return jnp.concatenate(
        [v[:, None, :].astype(win_n.dtype), win_n[:, : W - 1]], axis=1
    )


# --------------------------------------------------------------- hyena_se

def _fir_causal_fp32(v: jax.Array, taps: jax.Array) -> jax.Array:
    """Depthwise causal FIR as shifted adds, kept in fp32 (the caller adds
    the skip term before the epilogue downcast — DESIGN.md §7 bit policy).
    Under a sequence-sharded layout the pad+slice lowers to an SPMD halo
    exchange, so SE layers never leave the cp/TP residual layout."""
    B, L, D = v.shape
    v32 = v.astype(jnp.float32)
    y = v32 * taps[:, 0].astype(jnp.float32)[None, None, :]
    for k in range(1, taps.shape[1]):
        shifted = jnp.pad(v32, ((0, 0), (k, 0), (0, 0)))[:, :L]
        y = y + shifted * taps[:, k].astype(jnp.float32)[None, None, :]
    return y


def init_hyena_se(key, cfg: HyenaSEConfig) -> Dict[str, Any]:
    k_proj, k_taps = jax.random.split(key)
    params = _init_projection(
        k_proj, cfg.d_model, cfg.order, cfg.short_filter_len, cfg.use_bias
    )
    # explicit per-order FIR taps — the whole SE filter parameterization
    params["taps"] = Ax(
        jax.random.normal(
            k_taps, (cfg.order, cfg.d_model, cfg.se_len), jnp.float32
        ) / jnp.sqrt(cfg.se_len),
        (None, "hyena_channels", None),
    )
    params["skip"] = Ax(
        jnp.ones((cfg.order, cfg.d_model), jnp.float32),
        (None, "hyena_channels"),
    )
    return params


def apply_hyena_se(
    params, cfg: HyenaSEConfig, x: jax.Array,
    ctx: Optional[ApplyContext] = None,
) -> jax.Array:
    ctx = ctx or DEFAULT_CONTEXT
    cp = getattr(ctx, "cp_axis", None)
    seq_axis = cp or "model"
    _, v, xs = _project_seq_sharded(params, cfg.order, x, seq_axis)
    taps = params["taps"]  # (N, D, K)
    skip = params["skip"]  # (N, D)
    for n in range(cfg.order):
        y = _fir_causal_fp32(v, taps[n])
        y = y + v.astype(jnp.float32) * skip[n].astype(jnp.float32)[None, None, :]
        # downcast BEFORE the gate: identical epilogue to the fused conv
        # backends (fftconv._fused_epilogue)
        v = (xs[n] * y.astype(x.dtype)).astype(x.dtype)
        v = shard(v, "data", seq_axis, None)
    return out_projection(params, v)


def init_hyena_se_cache(
    cfg: HyenaSEConfig, batch: int, max_len: int, dtype=jnp.bfloat16
):
    inner = (cfg.order + 1) * cfg.d_model
    return {
        "short": jnp.zeros(
            (batch, cfg.short_filter_len - 1, inner), dtype
        ),
        # per-order conv operand window, newest-first (bounded — pinned
        # under the paged allocator)
        "win": jnp.zeros(
            (cfg.order, batch, cfg.se_len - 1, cfg.d_model), dtype
        ),
        "t": jnp.zeros((batch,), jnp.int32),
    }


def hyena_se_prefill(
    params, cfg: HyenaSEConfig, x: jax.Array, max_len: int,
    dtype=jnp.bfloat16,
) -> Tuple[jax.Array, dict]:
    B, L, D = x.shape
    z_pre = in_projection(params, x)
    z = short_causal_conv(z_pre, params["short_filter"])
    parts = jnp.split(z, cfg.order + 1, axis=-1)
    v, xs = parts[0], parts[1:]
    taps = params["taps"]
    skip = params["skip"]
    wins = []
    for n in range(cfg.order):
        wins.append(_newest_first(v, cfg.se_len - 1, L, dtype))
        y = _fir_causal_fp32(v, taps[n])
        y = y + v.astype(jnp.float32) * skip[n].astype(jnp.float32)[None, None, :]
        v = (xs[n] * y.astype(x.dtype)).astype(x.dtype)
    out = out_projection(params, v)
    cache = {
        "short": _newest_first(z_pre, cfg.short_filter_len - 1, L, dtype),
        "win": jnp.stack(wins),
        "t": jnp.full((B,), L, jnp.int32),
    }
    return out, cache


def hyena_se_decode_step(params, cfg: HyenaSEConfig, u_t, cache):
    new_short, v, xs = _decode_project(params, cfg, u_t, cache)
    taps = params["taps"]  # (N, D, K)
    skip = params["skip"]
    hist = _window_decode(cache["win"], taps)  # (N, B, D) fp32
    h0 = (taps[:, :, 0] + skip).astype(jnp.float32)  # (N, D) fused rank-1
    new_wins = []
    for n in range(cfg.order):
        new_wins.append(_roll_window(cache["win"][n], v))
        conv_y = hist[n] + v.astype(jnp.float32) * h0[n][None, :]
        v = xs[n] * conv_y.astype(u_t.dtype)
    y = out_projection(params, v)
    out_cache = dict(cache)
    out_cache.update({
        "short": new_short,
        "win": jnp.stack(new_wins),
        "t": cache["t"] + 1,
    })
    return y, out_cache


# --------------------------------------------------------------- hyena_mr

def init_hyena_mr(key, cfg: HyenaMRConfig) -> Dict[str, Any]:
    k_proj, k_filt = jax.random.split(key)
    params = _init_projection(
        k_proj, cfg.d_model, cfg.order, cfg.short_filter_len, cfg.use_bias
    )
    params["filters"] = F.init_hyena_filter(k_filt, cfg.filter)
    return params


def _mr_taps(params, cfg: HyenaMRConfig):
    """Taps on the FIXED ``support``-point grid — length-invariant (the LI
    filter re-evaluates its positional grid per L; MR's regularization is
    exactly this pinned support), so train/prefill/decode all contract
    against identical tap values."""
    h = F.evaluate_filters(params["filters"], cfg.filter, cfg.support)
    skip = F.filter_skip(params["filters"], cfg.filter)
    return h, skip  # (N, D, M) fp32, (N, D)


def _mr_taps_to_len(h: jax.Array, L: int) -> jax.Array:
    M = h.shape[2]
    if L >= M:
        return jnp.pad(h, ((0, 0), (0, 0), (0, L - M)))
    return h[:, :, :L]


def apply_hyena_mr(
    params, cfg: HyenaMRConfig, x: jax.Array,
    ctx: Optional[ApplyContext] = None,
) -> jax.Array:
    """Same layout moves as ``apply_hyena_mixer``: cp stays seq-sharded
    (fft_sp), otherwise channel all-to-all into the conv layout — the
    full-sequence conv goes through the registry backend so MR rides
    blockfft_overlap / fft_sp exactly like LI."""
    ctx = ctx or DEFAULT_CONTEXT
    B, L, D = x.shape
    cp = getattr(ctx, "cp_axis", None)
    seq_axis = cp or "model"
    _, v, xs = _project_seq_sharded(params, cfg.order, x, seq_axis)
    if cp is not None:
        v = shard(v, "data", cp, None)
        xs = [shard(xn, "data", cp, None) for xn in xs]
    else:
        v = shard(v, "data", None, "model")
        xs = [shard(xn, "data", None, "model") for xn in xs]
    h_m, skip = _mr_taps(params, cfg)
    h = _mr_taps_to_len(h_m, L)  # (N, D, L): zero past the support
    backend = get_conv_backend(ctx.conv_backend_for(L))
    backend.validate_len(L)
    for n in range(cfg.order):
        hn = h[n] if cp is not None else shard(h[n], "model", None)
        v = backend(v, hn, skip[n], gate=xs[n]).astype(x.dtype)
        v = shard(v, "data", cp, None) if cp is not None else shard(
            v, "data", None, "model"
        )
    return out_projection(params, v)


def init_hyena_mr_cache(
    cfg: HyenaMRConfig, batch: int, max_len: int, dtype=jnp.bfloat16
):
    inner = (cfg.order + 1) * cfg.d_model
    return {
        "short": jnp.zeros(
            (batch, cfg.short_filter_len - 1, inner), dtype
        ),
        # operand window bounded by the tap support — O(M), not O(max_len)
        "win": jnp.zeros(
            (cfg.order, batch, cfg.support - 1, cfg.d_model), dtype
        ),
        "t": jnp.zeros((batch,), jnp.int32),
    }


def hyena_mr_prefill(
    params, cfg: HyenaMRConfig, x: jax.Array, max_len: int,
    dtype=jnp.bfloat16, *, conv_backend: Optional[str] = None,
) -> Tuple[jax.Array, dict]:
    backend = get_conv_backend(conv_backend)
    B, L, D = x.shape
    backend.validate_len(L)
    z_pre = in_projection(params, x)
    z = short_causal_conv(z_pre, params["short_filter"])
    parts = jnp.split(z, cfg.order + 1, axis=-1)
    v, xs = parts[0], parts[1:]
    h_m, skip = _mr_taps(params, cfg)
    h = _mr_taps_to_len(h_m, L)
    wins = []
    for n in range(cfg.order):
        wins.append(_newest_first(v, cfg.support - 1, L, dtype))
        v = backend(v, h[n], skip[n], gate=xs[n]).astype(x.dtype)
    out = out_projection(params, v)
    cache = {
        "short": _newest_first(z_pre, cfg.short_filter_len - 1, L, dtype),
        "win": jnp.stack(wins),
        "t": jnp.full((B,), L, jnp.int32),
        # fp32 taps shared across slots (params + fixed grid only)
        "h": h_m,
        "skip": skip,
    }
    return out, cache


def hyena_mr_decode_step(params, cfg: HyenaMRConfig, u_t, cache):
    h = cache.get("h")
    skip = cache.get("skip")
    if h is None:
        # one-time memoized host-side fallback (same memo as LI — it keys
        # on cfg.filter and the grid length only)
        h, skip = _fallback_decode_taps(params, cfg, cfg.support)
    new_short, v, xs = _decode_project(params, cfg, u_t, cache)
    hist = _window_decode(cache["win"], h)  # (N, B, D) fp32
    h0 = (h[:, :, 0] + skip).astype(jnp.float32)
    new_wins = []
    for n in range(cfg.order):
        new_wins.append(_roll_window(cache["win"][n], v))
        conv_y = hist[n] + v.astype(jnp.float32) * h0[n][None, :]
        v = xs[n] * conv_y.astype(u_t.dtype)
    y = out_projection(params, v)
    out_cache = dict(cache)
    out_cache.update({
        "short": new_short,
        "win": jnp.stack(new_wins),
        "t": cache["t"] + 1,
    })
    return y, out_cache


# ----------------------------------------------------------- registration

@register_mixer
class HyenaLIMixer(HyenaMixer):
    """The long implicit operator under its multi-hybrid name: identical to
    ``hyena`` in every contract — registered separately so `SE-MR-LI`
    patterns name all three variants uniformly."""

    name = "hyena_li"


@register_mixer
class HyenaSEMixer(TokenMixer):
    """Short-explicit hyena: FIR taps as parameters, O(se_len) decode
    state, no channel all-to-all (stays in the residual sharding)."""

    name = "hyena_se"
    attention_free = True
    subquadratic = True

    def make_config(self, cfg) -> HyenaSEConfig:
        return HyenaSEConfig(
            d_model=cfg.d_model,
            order=cfg.hyena_order,
            se_len=cfg.hyena_se_len,
        )

    def init(self, key, mc):
        return init_hyena_se(key, mc)

    def apply(self, params, mc, h, ctx: ApplyContext):
        return apply_hyena_se(params, mc, h, ctx)

    def init_cache(self, mc, batch, max_len, dtype):
        return init_hyena_se_cache(mc, batch, max_len, dtype)

    def prefill(self, params, mc, h, max_len, dtype, ctx: ApplyContext):
        if ctx.pos_offset:
            # window stitching across chunked prefill is unimplemented
            # (the rolling windows only see the current chunk)
            raise NotImplementedError(
                "hyena_se prefill does not support pos_offset != 0"
            )
        return hyena_se_prefill(params, mc, h, max_len, dtype)

    def decode_step(self, params, mc, h_t, cache):
        return hyena_se_decode_step(params, mc, h_t, cache)

    def cache_slot_axes(self, mc) -> dict:
        return {"win": 1}

    def cache_page_axes(self, mc) -> dict:
        return {}  # all leaves are bounded windows / cursors: pinned

    def cache_shard_axes(self, mc) -> dict:
        return {
            "short": ("cache_slots", None, "hyena_inner"),
            "win": (None, "cache_slots", None, "hyena_channels"),
        }

    def state_bytes(self, cfg, max_len: int) -> int:
        mc = self.make_config(cfg)
        D, N = mc.d_model, mc.order
        inner = (N + 1) * D
        short = (mc.short_filter_len - 1) * inner
        win = N * (mc.se_len - 1) * D
        return (short + win) * 2 + 4  # bf16 windows + int32 cursor

    def flops(self, cfg, L: int) -> float:
        mc = self.make_config(cfg)
        D, N, K = mc.d_model, mc.order, mc.short_filter_len
        proj = (N + 1) * D * D + D * D
        short = (N + 1) * D * K
        fir = N * D * mc.se_len + N * D  # taps + skip
        return 2.0 * L * (proj + short + fir)


@register_mixer
class HyenaMRMixer(TokenMixer):
    """Medium-regularized hyena: the implicit filter FFN on a fixed
    ``support`` grid — length-invariant taps, O(support) decode state, the
    full-sequence conv still on the registry (autotuned) backends."""

    name = "hyena_mr"
    attention_free = True
    subquadratic = True

    def make_config(self, cfg) -> HyenaMRConfig:
        return HyenaMRConfig(
            d_model=cfg.d_model,
            order=cfg.hyena_order,
            support=cfg.hyena_mr_support,
            filter=F.FilterConfig(
                d_model=cfg.d_model,
                order=cfg.hyena_order,
                ffn_width=cfg.hyena_filter_width,
                ffn_depth=cfg.hyena_filter_depth,
                pos_dim=cfg.hyena_pos_dim,
                sine_freq=cfg.hyena_sine_freq,
                decay_fast=cfg.hyena_decay[0],
                decay_slow=cfg.hyena_decay[1],
            ),
        )

    def init(self, key, mc):
        return init_hyena_mr(key, mc)

    def apply(self, params, mc, h, ctx: ApplyContext):
        return apply_hyena_mr(params, mc, h, ctx)

    def init_cache(self, mc, batch, max_len, dtype):
        return init_hyena_mr_cache(mc, batch, max_len, dtype)

    def prefill(self, params, mc, h, max_len, dtype, ctx: ApplyContext):
        if ctx.pos_offset:
            raise NotImplementedError(
                "hyena_mr prefill does not support pos_offset != 0"
            )
        return hyena_mr_prefill(
            params, mc, h, max_len, dtype,
            conv_backend=ctx.conv_backend_for(h.shape[1]),
        )

    def decode_step(self, params, mc, h_t, cache):
        return hyena_mr_decode_step(params, mc, h_t, cache)

    def cache_slot_axes(self, mc) -> dict:
        # taps depend only on params + the fixed grid: shared across slots
        return {"win": 1, "h": -1, "skip": -1}

    def cache_page_axes(self, mc) -> dict:
        return {}  # support-bounded windows: pinned (paging buys nothing)

    def cache_shard_axes(self, mc) -> dict:
        return {
            "short": ("cache_slots", None, "hyena_inner"),
            "win": (None, "cache_slots", None, "hyena_channels"),
            "h": (None, "hyena_channels", None),
            "skip": (None, "hyena_channels"),
        }

    def state_bytes(self, cfg, max_len: int) -> int:
        mc = self.make_config(cfg)
        D, N = mc.d_model, mc.order
        inner = (N + 1) * D
        short = (mc.short_filter_len - 1) * inner
        win = N * (mc.support - 1) * D
        taps = N * D * mc.support + N * D  # fp32 shared taps + skip
        return (short + win) * 2 + taps * 4 + 4

    def flops(self, cfg, L: int) -> float:
        import math

        mc = self.make_config(cfg)
        D, N, K = mc.d_model, mc.order, mc.short_filter_len
        fc = mc.filter
        proj = (N + 1) * D * D + D * D
        short = (N + 1) * D * K
        fftconv = 5 * N * D * math.log2(max(L, 2))
        filt = (
            fc.pos_dim * fc.ffn_width
            + (fc.ffn_depth - 1) * fc.ffn_width * fc.ffn_width
            + fc.ffn_width * N * D
        )
        return 2.0 * L * (proj + short + fftconv + filt)
