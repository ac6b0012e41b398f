"""Shared layers: norms, channel-MLP variants, embeddings, RoPE.

Each layer runs under a ``jax.named_scope`` (``rms_norm``, ``mlp``,
``token_embed``): the name lands in its ops' HLO metadata, which a
profiler trace shows as the op's ``tf_op`` path.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.common.param import Ax


# ------------------------------------------------------------------ norms

def init_norm(d: int, kind: str = "rmsnorm") -> Dict[str, Any]:
    if kind == "rmsnorm":
        return {"g": Ax(jnp.zeros((d,), jnp.float32), ("embed",))}
    if kind == "layernorm":
        return {
            "g": Ax(jnp.zeros((d,), jnp.float32), ("embed",)),
            "b": Ax(jnp.zeros((d,), jnp.float32), ("embed",)),
        }
    raise ValueError(kind)


@jax.named_scope("rms_norm")
def apply_norm(params, x, kind: str = "rmsnorm", eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(var + eps) * (1.0 + params["g"])
    else:
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        y = (x32 - mu) * jax.lax.rsqrt(var + eps) * (1.0 + params["g"]) + params["b"]
    return y.astype(x.dtype)


# ------------------------------------------------------------------ dense

def init_dense(key, d_in, d_out, axes=("embed", "mlp"), bias=False, scale=None):
    import math

    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    p = {"w": Ax(scale * jax.random.normal(key, (d_in, d_out), jnp.float32), axes)}
    if bias:
        p["b"] = Ax(jnp.zeros((d_out,), jnp.float32), (axes[1],))
    return p


def dense(params, x):
    y = x @ params["w"].astype(x.dtype)
    if "b" in params:
        y = y + params["b"].astype(x.dtype)
    return y


# -------------------------------------------------------------------- MLP

def init_mlp(key, d_model: int, d_ff: int, kind: str = "swiglu") -> Dict[str, Any]:
    k1, k2, k3 = jax.random.split(key, 3)
    if kind in ("swiglu", "geglu"):
        return {
            "up": init_dense(k1, d_model, d_ff, ("embed", "mlp")),
            "gate": init_dense(k2, d_model, d_ff, ("embed", "mlp")),
            "down": init_dense(k3, d_ff, d_model, ("mlp", "embed")),
        }
    # gelu / squared_relu: plain 2-layer
    return {
        "up": init_dense(k1, d_model, d_ff, ("embed", "mlp")),
        "down": init_dense(k3, d_ff, d_model, ("mlp", "embed")),
    }


@jax.named_scope("mlp")
def apply_mlp(params, x, kind: str = "swiglu"):
    from repro.distributed.ctx import shard

    if kind == "swiglu":
        h = jax.nn.silu(dense(params["gate"], x)) * dense(params["up"], x)
    elif kind == "geglu":
        h = jax.nn.gelu(dense(params["gate"], x)) * dense(params["up"], x)
    elif kind == "gelu":
        h = jax.nn.gelu(dense(params["up"], x))
    elif kind == "squared_relu":  # Nemotron-4 (Primer)
        h = jnp.square(jax.nn.relu(dense(params["up"], x)))
    else:
        raise ValueError(kind)
    h = shard(h, "data", *([None] * (h.ndim - 2)), "model")
    return dense(params["down"], h)


# -------------------------------------------------------------- embedding

def init_embedding(key, vocab: int, d_model: int):
    return {
        "table": Ax(
            0.02 * jax.random.normal(key, (vocab, d_model), jnp.float32),
            ("vocab", "embed"),
        )
    }


@jax.named_scope("token_embed")
def embed(params, tokens, dtype=jnp.bfloat16):
    return params["table"].astype(dtype)[tokens]


@jax.named_scope("token_embed")
def unembed(params, x):
    return x @ params["table"].astype(x.dtype).T


# ------------------------------------------------------------------- RoPE

def rope_freqs(head_dim: int, theta: float = 10000.0) -> jax.Array:
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0):
    """x: (B, L, H, Dh), positions: (L,) or (B, L)."""
    B, L, H, Dh = x.shape
    freqs = rope_freqs(Dh, theta)  # (Dh/2,)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, L, Dh/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)
