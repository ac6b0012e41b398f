"""Layers that the program names itself: its ``jax.named_scope`` names,
which a profiler trace shows in each device op's ``tf_op`` path.

``layers.json`` attributes ops by source file and ``tf_op`` substring;
the ops it leaves as ``other`` are attributed here, to the first scope
that is a component of their ``tf_op`` path.  A scope reads ``/<name>/``
inside the layer scan and where no transformation wraps it (the
optimizer), and ``(<name>)`` where ``jvp`` or ``transpose`` wraps it (the
embedding, the final norm and the LM head).  No name holds ``fft``, which
the long conv's ``tf_op`` rule matches, and no pattern matches a
parameter's path (``state['params']...['mlp']...``).

The recomputed forward of remat is cut along another axis: its ops hold
``rematted_computation`` in their ``tf_op`` and also belong to a layer.
"""
from __future__ import annotations

from typing import List, Optional

# (layer, scope name in the program), in the order they are tried
SCOPES = (
    ("token embed", "token_embed"),
    ("rms norm", "rms_norm"),
    ("hyena proj", "hyena_proj"),
    ("short conv", "short_conv"),
    ("implicit filter", "implicit_filter"),
    # the long conv's own ops that layers.json's source rule misses
    ("long conv scope", "long_conv"),
    ("mlp", "mlp"),
    ("lm head", "lm_head"),
    ("optimizer", "optimizer"),
)
REMAT = "rematted_computation"
TRAIN = "train_step"


def scope_layer(tf_op: str) -> Optional[str]:
    """The first scope layer whose name is a component of ``tf_op``."""
    for layer, name in SCOPES:
        if f"/{name}/" in tf_op or f"({name})" in tf_op:
            return layer
    return None


def layer_of(op) -> str:
    """The op's layer: ``layers.json``'s, else its scope's, else other."""
    if op.layer != "other":
        return op.layer
    return scope_layer(op.tf_op) or "other"


def step_ops(reduced) -> List:
    """The leaf ops inside runs of the train step, all devices."""
    from bench.trace_reduce import _inside

    return _inside(reduced.ops, reduced.program_runs(TRAIN))


def ms_per_step(ctx, pick) -> Optional[float]:
    """Device milliseconds per train step (per chip) of the step's ops
    that ``pick(op)`` selects; None where it selects none."""
    runs = ctx.reduced.program_runs(TRAIN)
    t = sum(o.dur for o in step_ops(ctx.reduced) if pick(o))
    if not runs or t <= 0:
        return None
    return 1e3 * t / len(runs)


def layer_ms(ctx, layer: str) -> Optional[float]:
    """``ms_per_step`` of the ops that ``scope_layer`` gives ``layer`` and
    ``layers.json`` leaves as other."""
    return ms_per_step(
        ctx, lambda o: o.layer == "other" and scope_layer(o.tf_op) == layer)
