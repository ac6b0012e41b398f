"""AdamW + schedules, built in-repo (optax is not available offline).

Matches the paper's training hyper-parameters (Table A.1/A.3): AdamW
β=(0.9, 0.98), weight decay 0.1, linear warmup + cosine decay, global-norm
gradient clipping.  Optimizer state is a plain pytree so it shards/
checkpoints exactly like parameters (FSDP shards m/v with the weights).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 6e-4
    b1: float = 0.9
    b2: float = 0.98
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: Optional[float] = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_ratio: float = 0.1
    schedule: str = "cosine"  # cosine | constant | linear


def schedule_lr(cfg: AdamWConfig, step: jax.Array) -> jax.Array:
    step = step.astype(jnp.float32)
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    if cfg.schedule == "constant":
        decay = 1.0
    elif cfg.schedule == "linear":
        frac = jnp.clip(
            (step - cfg.warmup_steps)
            / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1),
            0.0, 1.0,
        )
        decay = 1.0 - (1.0 - cfg.min_lr_ratio) * frac
    else:  # cosine
        frac = jnp.clip(
            (step - cfg.warmup_steps)
            / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1),
            0.0, 1.0,
        )
        decay = cfg.min_lr_ratio + (1.0 - cfg.min_lr_ratio) * 0.5 * (
            1.0 + jnp.cos(math.pi * frac)
        )
    return cfg.lr * warm * decay


def init_adamw(params) -> Dict[str, Any]:
    zeros = lambda p: jax.tree_util.tree_map(jnp.zeros_like, p)
    return {"m": zeros(params), "v": zeros(params), "step": jnp.zeros((), jnp.int32)}


def global_norm(tree) -> jax.Array:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves)
    )


@jax.named_scope("optimizer")
def adamw_update(
    cfg: AdamWConfig, grads, opt_state, params
) -> Tuple[Any, Dict[str, Any], Dict[str, jax.Array]]:
    """Returns (new_params, new_opt_state, metrics)."""
    step = opt_state["step"] + 1
    lr = schedule_lr(cfg, step)
    gnorm = global_norm(grads)
    if cfg.clip_norm is not None:
        scale = jnp.minimum(1.0, cfg.clip_norm / (gnorm + 1e-9))
        grads = jax.tree_util.tree_map(lambda g: g * scale, grads)

    b1, b2 = cfg.b1, cfg.b2
    bc1 = 1.0 - b1 ** step.astype(jnp.float32)
    bc2 = 1.0 - b2 ** step.astype(jnp.float32)

    def upd(g, m, v, p):
        g = g.astype(jnp.float32)
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        mhat = m / bc1
        vhat = v / bc2
        delta = mhat / (jnp.sqrt(vhat) + cfg.eps)
        # decoupled weight decay on tensors with >= 2 dims (skip norms/biases)
        if p.ndim >= 2:
            delta = delta + cfg.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * delta).astype(p.dtype), m, v

    flat_g, treedef = jax.tree_util.tree_flatten(grads)
    flat_m = treedef.flatten_up_to(opt_state["m"])
    flat_v = treedef.flatten_up_to(opt_state["v"])
    flat_p = treedef.flatten_up_to(params)
    out = [upd(g, m, v, p) for g, m, v, p in zip(flat_g, flat_m, flat_v, flat_p)]
    new_p = treedef.unflatten([o[0] for o in out])
    new_m = treedef.unflatten([o[1] for o in out])
    new_v = treedef.unflatten([o[2] for o in out])
    metrics = {"grad_norm": gnorm, "lr": lr}
    return new_p, {"m": new_m, "v": new_v, "step": step}, metrics
