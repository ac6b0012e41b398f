"""Hyena as an LM token mixer — the paper's drop-in attention replacement.

Thin adapter over :mod:`repro.core.operator` adding activation-sharding
constraints: Hyena's long conv is depthwise, so tensor parallelism over the
channel dim is collective-free inside the operator (DESIGN.md §6); the only
TP collectives are the in/out projections' (same as Megatron attention).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import filters as F
from repro.core.conv_api import get_conv_backend
from repro.core.fftconv import short_causal_conv
from repro.core.operator import (
    HyenaConfig,
    hyena_decode_step,
    init_decode_cache,
    in_projection,
    init_hyena,
    out_projection,
    precompute_decode_filters,
)
from repro.distributed.ctx import shard
from repro.models.mixer_api import (
    DEFAULT_CONTEXT,
    ApplyContext,
    TokenMixer,
    register_mixer,
)


def init_hyena_mixer(key, cfg: HyenaConfig) -> Dict[str, Any]:
    return init_hyena(key, cfg)


def apply_hyena_mixer(
    params, cfg: HyenaConfig, x: jax.Array, ctx: Optional[ApplyContext] = None
) -> jax.Array:
    """(B, L, D) -> (B, L, D), TP over channels.

    The input arrives sequence-sharded (residual-stream layout); keeping the
    in_proj output sequence-sharded (weights gathered — MBs) and moving to
    the channel-sharded conv layout with per-tensor all-to-alls is 16× less
    traffic than all-gathering the activation (GBs) — §Perf pair A iter 3.
    """
    ctx = ctx or DEFAULT_CONTEXT
    B, L, D = x.shape
    N = cfg.order
    cp = getattr(ctx, "cp_axis", None)
    seq_axis = cp or "model"
    z = in_projection(params, x)
    z = shard(z, "data", seq_axis, None)  # seq-sharded; short conv halo-exchanges
    z = short_causal_conv(z, params["short_filter"])
    parts = jnp.split(z, N + 1, axis=-1)
    v, xs = parts[0], parts[1:]
    if cp is not None:
        # context parallelism: the sequence dim STAYS sharded through the
        # sequence-parallel conv — the channel all-to-all layout below
        # would put the full L on every chip, exactly what cp must avoid
        v = shard(v, "data", cp, None)
        xs = [shard(xn, "data", cp, None) for xn in xs]
    else:
        # conv layout: channels on model, full sequence (all-to-all, not gather)
        v = shard(v, "data", None, "model")
        xs = [shard(xn, "data", None, "model") for xn in xs]
    h = F.evaluate_filters(params["filters"], cfg.filter, L)  # (N, D, L)
    skip = F.filter_skip(params["filters"], cfg.filter)
    # length-aware routing: an ExecutionContext steers long sequences onto
    # the sequence-parallel fft_sp backend past its per-mesh threshold
    # (and cp training routes there unconditionally)
    backend = get_conv_backend(ctx.conv_backend_for(L))
    backend.validate_len(L)
    for n in range(N):
        # depthwise: channel-sharded filter in the TP layout; under cp the
        # taps stay replicated and fft_sp scatters their L dim itself
        hn = h[n] if cp is not None else shard(h[n], "model", None)
        # gate fused into the conv backend (xs[n] shares v's sharding, so
        # the fused multiply stays collective-free)
        v = backend(v, hn, skip[n], gate=xs[n]).astype(x.dtype)
        v = shard(v, "data", cp, None) if cp is not None else shard(
            v, "data", None, "model"
        )
    return out_projection(params, v)


def init_hyena_cache(cfg: HyenaConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    return init_decode_cache(cfg, batch, max_len, dtype)


def hyena_mixer_decode(params, cfg: HyenaConfig, x_t, cache):
    return hyena_decode_step(params, cfg, x_t, cache)


def hyena_prefill(
    params, cfg: HyenaConfig, x: jax.Array, max_len: int, dtype=jnp.bfloat16,
    *, conv_backend: Optional[str] = None,
) -> Tuple[jax.Array, dict]:
    """Full-sequence forward capturing the decode caches: the short-conv
    input history (newest-first rolling window) and, per order, the conv
    *operand* history at absolute positions (token ``p`` at index ``p``,
    append-only) — exactly what ``hyena_decode_step``'s stacked history
    einsum contracts against at decode time.

    The prompt's long convs run on the ``conv_backend`` registration
    (default ``fft``); decode steps themselves are cached dots and have no
    backend dimension."""
    backend = get_conv_backend(conv_backend)
    B, L, D = x.shape
    backend.validate_len(L)
    N = cfg.order
    z_pre = in_projection(params, x)
    z = short_causal_conv(z_pre, params["short_filter"])
    parts = jnp.split(z, N + 1, axis=-1)
    v, xs = parts[0], parts[1:]
    # decode filters are evaluated on the max_len grid so taps match the
    # decode-time dot exactly
    h_dec = F.evaluate_filters(params["filters"], cfg.filter, max_len)
    skip = F.filter_skip(params["filters"], cfg.filter)
    cache = init_decode_cache(cfg, B, max_len, dtype)

    def hist(seq):  # (B, L, D) -> absolute positions, zero past L
        # (prompts longer than max_len keep their last max_len values,
        # re-based to position 0 — decoding past max_len is out of
        # contract either way)
        n = min(L, max_len)
        recent = seq[:, L - n :].astype(dtype)
        return jnp.pad(recent, ((0, 0), (0, max_len - n), (0, 0)))

    def newest_first(seq, k):  # (B, L, D) -> (B, k, D) rolling window
        n = min(L, k)
        recent = jnp.flip(seq[:, L - n :], axis=1).astype(dtype)
        return jnp.pad(recent, ((0, 0), (0, k - n), (0, 0)))

    Ks = cfg.short_filter_len - 1
    short_hist = newest_first(z_pre, Ks)
    longs = []
    for n in range(N):
        longs.append(hist(v))
        v = backend(v, h_dec[n][:, :L], skip[n], gate=xs[n]).astype(x.dtype)
    y = out_projection(params, v)
    cache = dict(cache)
    cache.update({
        "short": short_hist,
        "long": jnp.stack(longs),
        "t": jnp.full((B,), L, jnp.int32),
        "h": h_dec,
        "skip": skip,
    })
    return y, cache


# ----------------------------------------------------------- registration

@register_mixer
class HyenaMixer(TokenMixer):
    """The paper's operator as a drop-in token mixer (Def. 3.1)."""

    name = "hyena"
    attention_free = True
    subquadratic = True

    def make_config(self, cfg) -> HyenaConfig:
        return HyenaConfig(
            d_model=cfg.d_model,
            order=cfg.hyena_order,
            filter=F.FilterConfig(
                d_model=cfg.d_model,
                order=cfg.hyena_order,
                ffn_width=cfg.hyena_filter_width,
                ffn_depth=cfg.hyena_filter_depth,
                pos_dim=cfg.hyena_pos_dim,
                sine_freq=cfg.hyena_sine_freq,
                decay_fast=cfg.hyena_decay[0],
                decay_slow=cfg.hyena_decay[1],
                max_support=cfg.hyena_max_support,
            ),
        )

    def init(self, key, mc):
        return init_hyena_mixer(key, mc)

    def apply(self, params, mc, h, ctx: ApplyContext):
        return apply_hyena_mixer(params, mc, h, ctx)

    def init_cache(self, mc, batch, max_len, dtype):
        return init_hyena_cache(mc, batch, max_len, dtype)

    def prefill(self, params, mc, h, max_len, dtype, ctx: ApplyContext):
        if ctx.pos_offset:
            # hyena filters are relative-lag functions with no absolute
            # position handle; a chunked prefill would need operand-history
            # stitching, which the cache layout does not support yet.
            raise NotImplementedError(
                "hyena prefill does not support pos_offset != 0"
            )
        return hyena_prefill(
            params, mc, h, max_len, dtype,
            conv_backend=ctx.conv_backend_for(h.shape[1]),
        )

    def decode_step(self, params, mc, h_t, cache):
        return hyena_mixer_decode(params, mc, h_t, cache)

    def cache_slot_axes(self, mc) -> dict:
        # "long" stacks the per-order operand histories ahead of the batch
        # dim; the decode filter taps "h"/"skip" depend only on params and
        # the max_len grid, so the pool shares one copy across slots.
        return {"long": 1, "h": -1, "skip": -1}

    def cache_page_axes(self, mc) -> dict:
        # the per-order operand history is append-only at absolute
        # positions (token p at index p; decode masks taps past the
        # cursor), so it pages exactly like attention KV — the paper's
        # O(L) operand state is the dominant per-request memory.  "short"
        # is a (K-1)-wide rolling window and "t"/"h"/"skip" are O(1) or
        # shared: pinned.
        return {"long": 2}

    def cache_shard_axes(self, mc) -> dict:
        # depthwise conv: every cache leaf's channel dim shards over the
        # model axis collective-free (the decode dot contracts per channel);
        # the operand-history time dim and the slot dim replicate.  "short"
        # holds the (N+1)·D projected-input history — the in_proj output
        # dim — so it reuses the hyena_inner rule.
        return {
            "short": ("cache_slots", None, "hyena_inner"),
            "long": (None, "cache_slots", "kv_seq", "hyena_channels"),
            "h": (None, "hyena_channels", "kv_seq"),
            "skip": (None, "hyena_channels"),
        }

    def state_bytes(self, cfg, max_len: int) -> int:
        mc = self.make_config(cfg)
        D, N = mc.d_model, mc.order
        inner = (N + 1) * D
        short = (mc.short_filter_len - 1) * inner  # projected-input history
        long = N * max_len * D  # per-order conv operand history
        # the serving cache (prefill-populated) also carries the fp32 filter
        # taps on the max_len grid plus the skip gains — batch-independent
        # but resident per layer, and the same magnitude as ``long``
        taps = N * D * max_len + N * D
        return (short + long) * 2 + taps * 4 + 4  # bf16 + fp32 + cursor

    def flops(self, cfg, L: int) -> float:
        """Paper App. A.2 accounting, ×2 for mul+add."""
        import math

        mc = self.make_config(cfg)
        D, N, K = mc.d_model, mc.order, mc.short_filter_len
        fc = mc.filter
        proj = (N + 1) * D * D + D * D  # in_proj + out_proj
        short = (N + 1) * D * K
        fftconv = 5 * N * D * math.log2(max(L, 2))
        # implicit filter FFN evaluated on the length-L grid
        filt = (
            fc.pos_dim * fc.ffn_width
            + (fc.ffn_depth - 1) * fc.ffn_width * fc.ffn_width
            + fc.ffn_width * N * D
        )
        return 2.0 * L * (proj + short + fftconv + filt)
