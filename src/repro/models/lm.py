"""Full language model: embedding → scan-stacked block groups → norm →
logits, plus the canonical ``train_step``-facing loss and the decode step.

Depth is executed as ``lax.scan`` over ``n_groups`` stacked parameter
groups (HLO size is depth-independent — required to compile 80-layer 72B
configs quickly) with per-group ``jax.checkpoint`` (remat) during training.

Modality frontends (VLM/audio archs) are stubs per the assignment: the
first ``frontend_len`` positions take precomputed patch/frame embeddings
supplied by ``input_specs()`` instead of token embeddings.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.param import Ax
from repro.configs.base import ModelConfig
from repro.distributed.ctx import shard
from repro.models import blocks as B
from repro.models.layers import apply_norm, embed, init_embedding, init_norm, unembed
from repro.models.mixer_api import DEFAULT_CONTEXT, ApplyContext

IGNORE = -1  # label id excluded from the loss

# name → jax.checkpoint policy for the per-group remat of the standard path
_REMAT_POLICIES = {
    "nothing": jax.checkpoint_policies.nothing_saveable,
    "dots": jax.checkpoint_policies.checkpoint_dots,
    "dots_no_batch": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
}


def _mesh_scope(ctx: ApplyContext):
    """Honor ``ctx.mesh`` as an override of the ambient mesh: inside the
    scope, every ``shard`` constraint resolves against it."""
    import contextlib

    from repro.distributed import ctx as dctx

    return dctx.use_mesh(ctx.mesh) if ctx.mesh is not None else (
        contextlib.nullcontext()
    )


def tail_mixers(cfg: ModelConfig) -> Tuple[str, ...]:
    return cfg.pattern[: cfg.n_layers % len(cfg.pattern)]


def init_lm(key, cfg: ModelConfig) -> Dict[str, Any]:
    plen = len(cfg.pattern)
    n_groups = cfg.n_layers // plen
    k_emb, k_head, k_tail, *k_groups = jax.random.split(key, 3 + plen)
    params: Dict[str, Any] = {
        "embed": init_embedding(k_emb, cfg.vocab_size, cfg.d_model),
        "final_norm": init_norm(cfg.d_model, cfg.norm),
        "groups": [],
    }
    for p, mixer in enumerate(cfg.pattern):
        keys = jax.random.split(k_groups[p], n_groups)
        stacked = jax.vmap(lambda k: B.init_block(k, cfg, mixer))(keys)
        params["groups"].append(stacked)
    tails = tail_mixers(cfg)
    if tails:
        params["tail"] = [
            B.init_block(jax.random.fold_in(k_tail, i), cfg, m)
            for i, m in enumerate(tails)
        ]
    if not cfg.tie_embeddings:
        import math

        params["head"] = {
            "w": Ax(
                jax.random.normal(k_head, (cfg.d_model, cfg.vocab_size), jnp.float32)
                / math.sqrt(cfg.d_model),
                ("embed", "vocab"),
            )
        }
    return params


def _head(params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Logits: the embedding's transpose when tied, else the head matmul."""
    if cfg.tie_embeddings:
        return unembed(params["embed"], x)
    with jax.named_scope("lm_head"):
        return x @ params["head"]["w"].astype(x.dtype)


def forward(
    params,
    cfg: ModelConfig,
    tokens: jax.Array,  # (B, L) int32
    frontend_embeds: Optional[jax.Array] = None,  # (B, P, D)
    *,
    ctx: Optional[ApplyContext] = None,
    compute_dtype=jnp.bfloat16,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Returns (logits (B, L, V), aux losses).

    Execution options — remat(+policy), conv-backend override, layer-loop
    unrolling, decode position offset, mesh override — arrive in one
    ``ApplyContext`` instead of per-call kwargs (DESIGN.md §3).
    """
    ctx = ctx or DEFAULT_CONTEXT
    if ctx.mesh is not None:  # re-enter with ctx.mesh as the ambient mesh
        with _mesh_scope(ctx):
            return forward(
                params, cfg, tokens, frontend_embeds,
                ctx=dataclasses.replace(ctx, mesh=None),
                compute_dtype=compute_dtype,
            )
    # the sequence axis of the residual stream: 'model' (Megatron-SP)
    # unless the context names a dedicated context-parallel axis
    seq_axis = getattr(ctx, "cp_axis", None) or "model"
    cp_on = getattr(ctx, "cp_axis", None) is not None
    # under cp the token batch itself is sequence-sharded end to end
    tokens = shard(tokens, "data", seq_axis if cp_on else None)
    x = embed(params["embed"], tokens, dtype=compute_dtype)
    if frontend_embeds is not None and cfg.frontend_len:
        P = frontend_embeds.shape[1]
        x = jax.lax.dynamic_update_slice(
            x, frontend_embeds.astype(x.dtype), (0, 0, 0)
        )
    x = shard(x, "data", seq_axis if cp_on else None, None)
    plen = len(cfg.pattern)

    def group_body(x, group_params):
        # residual stream sequence-sharded over the seq axis between layers
        # (Megatron-SP): the scan carry (remat save point) is 1/TP the size
        # — required to fit 80-layer remat at 16 rows × 4K tokens per chip.
        x = shard(x, "data", seq_axis, None)
        aux_sum = jnp.zeros((2,), jnp.float32)
        for p, mixer in enumerate(cfg.pattern):
            x, aux = B.apply_block(group_params[p], cfg, mixer, x, ctx)
            if aux:
                aux_sum = aux_sum + jnp.stack(
                    [aux["moe_load_balance"], aux["moe_z_loss"]]
                )
        x = shard(x, "data", seq_axis, None)
        return x, aux_sum

    if getattr(ctx, "reversible", False):
        # Reversible dual-stream substrate (DESIGN.md §15): the scan-level
        # custom_vjp reconstructs activations in backward, so remat is
        # deliberately NOT applied here — the VJP already dictates the
        # (O(1)-in-depth) save set.  Training-only: prefill/decode below
        # never consult this flag.
        from repro.models import reversible as REV

        x, aux_stack = REV.reversible_forward(cfg, ctx, params["groups"], x)
    elif ctx.unroll:
        body = group_body
        if ctx.remat:
            body = jax.checkpoint(group_body, policy=_REMAT_POLICIES[ctx.remat_policy])
        aux_list = []
        n_groups = cfg.n_layers // len(cfg.pattern)
        for g in range(n_groups):
            gp = jax.tree_util.tree_map(lambda a: a[g], tuple(params["groups"]))
            x, a = body(x, gp)
            aux_list.append(a)
        aux_stack = jnp.stack(aux_list) if aux_list else jnp.zeros((1, 2))
    else:
        body = group_body
        if ctx.remat:
            body = jax.checkpoint(group_body, policy=_REMAT_POLICIES[ctx.remat_policy])
        x, aux_stack = jax.lax.scan(
            lambda carry, gp: body(carry, gp), x, tuple(params["groups"])
        )
    aux = {
        "moe_load_balance": jnp.sum(aux_stack[:, 0]),
        "moe_z_loss": jnp.sum(aux_stack[:, 1]),
    }
    for i, mixer in enumerate(tail_mixers(cfg)):
        x, taux = B.apply_block(params["tail"][i], cfg, mixer, x, ctx)
        for k, v in taux.items():
            aux[k] = aux[k] + v
    x = apply_norm(params["final_norm"], x, cfg.norm)
    logits = _head(params, cfg, x)
    # sequence-sharded logits: full-vocab rows live on one chip, so the loss
    # never materializes a vocab-sharded softmax nor a full (B, L, V) fp32.
    # (Under cp the loss reductions over the sharded L dim are plain jnp
    # sums — GSPMD inserts the psum over the cp axis.)
    logits = shard(logits, "data", seq_axis, None)
    return logits, aux


TRAIN_CONTEXT = ApplyContext(remat=True)


def loss_fn(
    params,
    cfg: ModelConfig,
    tokens: jax.Array,  # (B, L)
    labels: jax.Array,  # (B, L), IGNORE = masked
    frontend_embeds: Optional[jax.Array] = None,
    *,
    ctx: Optional[ApplyContext] = None,
    moe_aux_weight: float = 0.01,
    z_loss_weight: float = 1e-4,
    compute_dtype=jnp.bfloat16,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    logits, aux = forward(
        params, cfg, tokens, frontend_embeds,
        ctx=ctx or TRAIN_CONTEXT, compute_dtype=compute_dtype,
    )
    with jax.named_scope("lm_head"):
        logits = logits.astype(jnp.float32)
        mask = (labels != IGNORE).astype(jnp.float32)
        safe_labels = jnp.where(labels == IGNORE, 0, labels)
        logz = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(
            logits, safe_labels[..., None], axis=-1)[..., 0]
        nll = (logz - ll) * mask
        denom = jnp.maximum(jnp.sum(mask), 1.0)
        loss = jnp.sum(nll) / denom
        zl = jnp.sum(jnp.square(logz) * mask) / denom
    total = loss + z_loss_weight * zl
    if cfg.moe:
        total = total + moe_aux_weight * (
            aux["moe_load_balance"] + aux["moe_z_loss"]
        )
    metrics = {
        "loss": loss,
        "z_loss": zl,
        "total_loss": total,
        "tokens": jnp.sum(mask),
        **aux,
    }
    return total, metrics


# ----------------------------------------------------------------- decode

def prefill(
    params,
    cfg: ModelConfig,
    tokens: jax.Array,  # (B, L) prompt
    max_len: int,
    frontend_embeds: Optional[jax.Array] = None,
    dtype=jnp.bfloat16,
    compute_dtype=None,
    *,
    ctx: Optional[ApplyContext] = None,
) -> Tuple[jax.Array, Any]:
    """Prompt forward pass returning (logits (B, L, V), populated caches).
    compute_dtype defaults to the cache dtype."""
    ctx = ctx or DEFAULT_CONTEXT
    if ctx.mesh is not None:
        with _mesh_scope(ctx):
            return prefill(
                params, cfg, tokens, max_len, frontend_embeds, dtype,
                compute_dtype, ctx=dataclasses.replace(ctx, mesh=None),
            )
    compute_dtype = compute_dtype or dtype
    x = embed(params["embed"], tokens, dtype=compute_dtype)
    if frontend_embeds is not None and cfg.frontend_len:
        x = jax.lax.dynamic_update_slice(
            x, frontend_embeds.astype(x.dtype), (0, 0, 0)
        )

    def group_body(x, group_params):
        caches = []
        for p, mixer in enumerate(cfg.pattern):
            x, c = B.block_prefill(
                group_params[p], cfg, mixer, x, max_len, dtype, ctx
            )
            caches.append(c)
        return x, tuple(caches)

    x, group_caches = jax.lax.scan(group_body, x, tuple(params["groups"]))
    caches = {"groups": list(group_caches)}
    tails = tail_mixers(cfg)
    if tails:
        tail_caches = []
        for i, mixer in enumerate(tails):
            x, c = B.block_prefill(
                params["tail"][i], cfg, mixer, x, max_len, dtype, ctx
            )
            tail_caches.append(c)
        caches["tail"] = tail_caches
    x = apply_norm(params["final_norm"], x, cfg.norm)
    logits = _head(params, cfg, x)
    return logits.astype(jnp.float32), caches


def init_caches(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    plen = len(cfg.pattern)
    n_groups = cfg.n_layers // plen
    groups = []
    for mixer in cfg.pattern:
        one = B.init_block_cache(cfg, mixer, batch, max_len, dtype)
        stacked = jax.tree_util.tree_map(
            lambda a: jnp.zeros((n_groups,) + a.shape, a.dtype), one
        )
        groups.append(stacked)
    caches = {"groups": groups}
    tails = tail_mixers(cfg)
    if tails:
        caches["tail"] = [
            B.init_block_cache(cfg, m, batch, max_len, dtype) for m in tails
        ]
    return caches


# ----------------------------------------------------- cache slot pooling
#
# Continuous-batching serving (repro.serve) keeps ONE pooled cache tree
# whose batch dim is a fixed pool of request slots.  The helpers below lift
# the per-mixer slot contract (TokenMixer.cache_slot_axes / cache_slice /
# cache_insert / cache_reset) over the full LM cache structure
# ``{"groups": [stacked per-pattern trees], "tail": [per-layer trees]}`` —
# group caches are lax.scan-stacked, so their slot axis is the mixer's
# axis + 1.  All ops are pure functions of (pool, slot) and jit-compatible
# (``slot`` may be traced).


def cache_slot_axes(cfg: ModelConfig, caches) -> Dict[str, Any]:
    """Pytree of ints matching ``caches``: slot axis per leaf, -1 = shared
    across slots (e.g. hyena's decode filter taps)."""
    from repro.models.mixer_api import get_mixer

    def axes_for(mixer: str, cache, shift: int):
        m = get_mixer(mixer)
        spec = m.cache_slot_axes(m.make_config(cfg))
        return {
            k: (-1 if spec.get(k, 0) < 0 else spec.get(k, 0) + shift)
            for k in cache
        }

    axes: Dict[str, Any] = {
        "groups": [
            axes_for(mx, caches["groups"][p], 1)
            for p, mx in enumerate(cfg.pattern)
        ]
    }
    if "tail" in caches:
        axes["tail"] = [
            axes_for(mx, caches["tail"][i], 0)
            for i, mx in enumerate(tail_mixers(cfg))
        ]
    return axes


def cache_page_axes(cfg: ModelConfig, caches) -> Dict[str, Any]:
    """Pytree of ints matching ``caches``: the append-only time axis per
    leaf from each mixer's ``cache_page_axes`` spec, or -1 for pinned
    leaves (bounded state the paged allocator keeps dense).  Scan-stacked
    group caches shift the axis by one, like ``cache_slot_axes``.

    Validates the paging contract here, once per tree: a paged leaf's time
    axis must sit immediately after its slot axis (the block gather/scatter
    moves slot->blocks and time->page as one adjacent pair)."""
    from repro.models.mixer_api import get_mixer

    def axes_for(mixer: str, cache, shift: int):
        m = get_mixer(mixer)
        mc = m.make_config(cfg)
        spec = m.cache_page_axes(mc)
        slots = m.cache_slot_axes(mc)
        for k, ax in spec.items():
            if k not in cache:
                raise ValueError(
                    f"mixer '{mixer}' cache_page_axes names '{k}' but the "
                    f"cache has keys {sorted(cache)}"
                )
            if ax != slots.get(k, 0) + 1:
                raise ValueError(
                    f"mixer '{mixer}' leaf '{k}': paged time axis {ax} "
                    f"must be slot axis {slots.get(k, 0)} + 1"
                )
        return {
            k: (spec[k] + shift if k in spec else -1) for k in cache
        }

    axes: Dict[str, Any] = {
        "groups": [
            axes_for(mx, caches["groups"][p], 1)
            for p, mx in enumerate(cfg.pattern)
        ]
    }
    if "tail" in caches:
        axes["tail"] = [
            axes_for(mx, caches["tail"][i], 0)
            for i, mx in enumerate(tail_mixers(cfg))
        ]
    return axes


def cache_shard_axes(cfg: ModelConfig, caches) -> Dict[str, Any]:
    """Pytree of logical-axes tuples (or None = replicate) matching
    ``caches``, collected from each mixer's ``cache_shard_axes`` spec.

    The spec describes the *unstacked* per-layer leaf; scan-stacked group
    caches carry one extra leading dim, which the rule engine treats as a
    replicated stack dim (same convention as scan-stacked params)."""
    from repro.models.mixer_api import get_mixer

    def axes_for(mixer: str, cache):
        m = get_mixer(mixer)
        spec = m.cache_shard_axes(m.make_config(cfg))
        return {k: spec.get(k) for k in cache}

    axes: Dict[str, Any] = {
        "groups": [
            axes_for(mx, caches["groups"][p])
            for p, mx in enumerate(cfg.pattern)
        ]
    }
    if "tail" in caches:
        axes["tail"] = [
            axes_for(mx, caches["tail"][i])
            for i, mx in enumerate(tail_mixers(cfg))
        ]
    return axes


def cache_shardings(cfg: ModelConfig, caches, mesh, *, fsdp: bool = False,
                    data_axes: Tuple[str, ...] = ("data",)):
    """Rule-driven NamedShardings for a decode-cache tree (works on value
    trees and on ShapeDtypeStruct trees alike): model-axis-sharded
    heads/channels, replicated cursors — DESIGN.md §9."""
    from repro.distributed.sharding import tree_shardings

    return tree_shardings(
        cache_shard_axes(cfg, caches), caches, mesh,
        fsdp=fsdp, data_axes=data_axes,
    )


def make_slot_pool(cfg: ModelConfig, one_cache, n_slots: int):
    """Expand a single-request cache (e.g. the first prefill's, batch 1)
    into an ``n_slots``-wide zeroed pool; shared leaves keep one copy.

    Shared leaves are *copied*, not aliased: the pool is buffer-donated
    through every jitted update, and the very first insert passes the same
    prefill cache as a non-donated argument — donating a buffer that is
    simultaneously another live input is illegal on GPU/TPU.
    """
    axes = cache_slot_axes(cfg, one_cache)

    def expand(ax, leaf):
        if ax < 0:
            return jnp.array(leaf)  # fresh buffer (donation-safe)
        shape = list(leaf.shape)
        shape[ax] = n_slots
        return jnp.zeros(tuple(shape), leaf.dtype)

    return jax.tree_util.tree_map(expand, axes, one_cache)


def slot_insert(cfg: ModelConfig, caches, slot, one):
    """Scatter a batch-1 cache (fresh prefill) into ``slot`` of the pool.
    Shared leaves take the incoming value (identical for every request)."""
    from repro.models.mixer_api import slot_insert_leaf

    axes = cache_slot_axes(cfg, caches)
    return jax.tree_util.tree_map(
        lambda ax, pool, new: slot_insert_leaf(pool, new, slot, ax),
        axes, caches, one,
    )


def slot_reset(cfg: ModelConfig, caches, slot):
    """Zero one slot across every per-slot leaf — pure function, so an
    evicted request's state cannot leak into the slot's next occupant."""
    from repro.models.mixer_api import slot_zero_leaf

    axes = cache_slot_axes(cfg, caches)
    return jax.tree_util.tree_map(
        lambda ax, leaf: slot_zero_leaf(leaf, slot, ax), axes, caches
    )


def mask_slots(cfg: ModelConfig, new_caches, old_caches, active: jax.Array):
    """Slot-masked cache update: keep ``new`` where ``active`` (bool (S,)),
    freeze ``old`` elsewhere.  Applied after a pooled decode step so free
    slots hold exactly their reset state (scheduler invariant I3)."""
    axes = cache_slot_axes(cfg, new_caches)

    def pick(ax, new, old):
        if ax < 0:
            return new
        shape = [1] * new.ndim
        shape[ax] = active.shape[0]
        return jnp.where(active.reshape(shape), new, old)

    return jax.tree_util.tree_map(pick, axes, new_caches, old_caches)


def decode_step(
    params, cfg: ModelConfig, token_t: jax.Array, caches,
    compute_dtype=jnp.bfloat16, *, ctx: Optional[ApplyContext] = None,
) -> Tuple[jax.Array, Any]:
    """One decode step: token_t (B,) int32 -> (logits (B, V), new caches)."""
    ctx = ctx or DEFAULT_CONTEXT
    if ctx.mesh is not None:
        with _mesh_scope(ctx):
            return decode_step(
                params, cfg, token_t, caches, compute_dtype,
                ctx=dataclasses.replace(ctx, mesh=None),
            )
    unroll = ctx.unroll
    x = embed(params["embed"], token_t[:, None], dtype=compute_dtype)[:, 0]  # (B, D)
    x = shard(x, "data", None)

    def group_body(x, xs):
        group_params, cache = xs
        new_caches = []
        for p, mixer in enumerate(cfg.pattern):
            x, c = B.block_decode(group_params[p], cfg, mixer, x, cache[p])
            new_caches.append(c)
        return x, tuple(new_caches)

    if unroll:
        n_groups = cfg.n_layers // len(cfg.pattern)
        outs = []
        for g in range(n_groups):
            take = lambda t: jax.tree_util.tree_map(lambda a: a[g], t)
            x, cs = group_body(
                x, (take(tuple(params["groups"])), take(tuple(caches["groups"])))
            )
            outs.append(cs)
        new_groups = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *outs
        ) if outs else ()
    else:
        x, new_groups = jax.lax.scan(
            group_body, x, (tuple(params["groups"]), tuple(caches["groups"]))
        )
    out_caches = {"groups": list(new_groups)}
    tails = tail_mixers(cfg)
    if tails:
        new_tail = []
        for i, mixer in enumerate(tails):
            x, c = B.block_decode(params["tail"][i], cfg, mixer, x, caches["tail"][i])
            new_tail.append(c)
        out_caches["tail"] = new_tail
    x = apply_norm(params["final_norm"], x, cfg.norm)
    logits = _head(params, cfg, x)
    logits = shard(logits, "data", "model")
    return logits.astype(jnp.float32), out_caches
