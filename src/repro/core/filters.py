"""Implicit Hyena filter parameterization (paper §3.3, Alg. 2, App. D.3).

A filter bank ``h ∈ R^{order × D × L}`` is produced by:

  1. ``PositionalEncoding(t)`` — truncated complex-exponential basis
     (App. D.3): ``[t, Re ρ_0..Re ρ_{K-1}, Im ρ_0..Im ρ_{K-1}]`` with
     ``ρ_k(t) = exp(i 2π k t / L)`` — dimension ``D_e = 2K + 1``.
  2. A shallow FFN with **sine** activations ``σ(x) = sin(ω x)`` (sine freq
     ``ω = 14`` in the paper's LM configs, Table A.4) mapping
     ``R^{D_e} → R^{order·D}``.
  3. An **exponential-decay window** with per-channel rates plus a learnable
     bias shift (Fig. 3.1: the bias keeps filters from being forced to zero
     past the decay horizon).

Parameter count is independent of L — the paper's *sublinear parameter
scaling* property.  Filters are evaluated once per forward pass, in parallel
across (order, D, L) — Algorithm 2.

Static hyper-parameters live in :class:`FilterConfig`; the param pytree holds
arrays only (jit-safe).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.common.param import Ax


@dataclasses.dataclass(frozen=True)
class FilterConfig:
    d_model: int
    order: int
    ffn_width: int = 64
    ffn_depth: int = 4  # number of linear layers (>= 2)
    pos_dim: int = 65  # 2K + 1
    sine_freq: float = 14.0
    decay_fast: float = 0.3
    decay_slow: float = 1.5
    normalized: bool = True
    max_support: int = 0  # >0: hard-truncate taps at this lag (explicit-FIR
    # ablation — the paper's Conv1d baseline with filter size M)


def positional_encoding(L: int, pos_dim: int, dtype=jnp.float32) -> jax.Array:
    """(L, pos_dim) truncated complex-exponential basis. pos_dim = 2K + 1."""
    K = (pos_dim - 1) // 2
    t = jnp.linspace(0.0, 1.0, L, dtype=jnp.float32)[:, None]  # (L, 1)
    if K == 0:
        return t.astype(dtype)
    k = jnp.arange(K, dtype=jnp.float32)[None, :]  # (1, K)
    ang = 2.0 * math.pi * k * t  # (L, K) — ρ_k(t) = exp(i·ang)
    z = jnp.concatenate([t, jnp.cos(ang), jnp.sin(ang)], axis=-1)
    return z.astype(dtype)


def init_hyena_filter(key, cfg: FilterConfig) -> Dict[str, Any]:
    """Params for the implicit filter FFN + window.

    Decay rates are log-spaced across channels at init ("Parameter α is
    modified across the independent channels ... to regularize filters to be
    of different lengths") and trainable.
    """
    assert cfg.ffn_depth >= 2
    dims = [cfg.pos_dim] + [cfg.ffn_width] * (cfg.ffn_depth - 1) + [
        cfg.order * cfg.d_model
    ]
    keys = jax.random.split(key, len(dims))
    layers = []
    for i in range(len(dims) - 1):
        w = jax.random.normal(keys[i], (dims[i], dims[i + 1]), jnp.float32)
        w = w / math.sqrt(dims[i])
        b = jnp.zeros((dims[i + 1],), jnp.float32)
        out_ax = "hyena_channels" if i == len(dims) - 2 else None
        layers.append({"w": Ax(w, (None, out_ax)), "b": Ax(b, (out_ax,))})
    n_ch = cfg.order * cfg.d_model
    log_rates = jnp.linspace(
        math.log(cfg.decay_fast), math.log(cfg.decay_slow), n_ch, dtype=jnp.float32
    )
    return {
        "ffn": layers,
        "decay_log_rate": Ax(log_rates, ("hyena_channels",)),
        "window_bias": Ax(jnp.zeros((n_ch,), jnp.float32), ("hyena_channels",)),
        # per-(order,channel) residual skip gain (the "D" term in SSM view)
        "skip": Ax(jnp.ones((n_ch,), jnp.float32), ("hyena_channels",)),
    }


@jax.named_scope("implicit_filter")
def evaluate_filters(params: Dict[str, Any], cfg: FilterConfig, L: int) -> jax.Array:
    """h: (order, d_model, L) float32 — Algorithm 2 (parallel across N, L)."""
    z = positional_encoding(L, cfg.pos_dim)  # (L, De)
    h = z
    n_layers = len(params["ffn"])
    for i, layer in enumerate(params["ffn"]):
        h = h @ layer["w"] + layer["b"]
        if i < n_layers - 1:
            h = jnp.sin(cfg.sine_freq * h)
    # (L, order*d_model) -> exponential-decay window modulation
    t = jnp.arange(L, dtype=jnp.float32)[:, None] / max(L, 1)
    rate = jnp.exp(params["decay_log_rate"])[None, :]  # (1, C)
    window = jnp.exp(-rate * t * 8.0)
    window = window + jax.nn.sigmoid(params["window_bias"])[None, :] * 0.1
    h = h * window  # (L, C)
    if cfg.max_support:
        h = jnp.where(
            (jnp.arange(L) < cfg.max_support)[:, None], h, 0.0
        )
    h = h.reshape(L, cfg.order, cfg.d_model).transpose(1, 2, 0)  # (order, D, L)
    if cfg.normalized:
        # unit-l1 filters stabilize deep stacks (official repo option); keeps
        # |H(u)| bounded across orders.
        h = h / (jnp.sum(jnp.abs(h), axis=-1, keepdims=True) + 1e-8)
    return h


@jax.named_scope("implicit_filter")
def filter_skip(params: Dict[str, Any], cfg: FilterConfig) -> jax.Array:
    """Per-(order, D) skip gain, shape (order, D)."""
    return params["skip"].reshape(cfg.order, cfg.d_model)
