"""recompute_ms.train: device milliseconds per train step (per chip) of
remat's recomputed forward: the ops whose ``tf_op`` holds JAX's
``rematted_computation``, whatever their layer.

This cuts the step's time along another axis than the layer metrics:
those ops also count in ``long_conv_ms.train``, ``mlp_ms.train`` and the
rest, so the two are never added together."""
from bench import scopes


def read(ctx, peaks):
    return scopes.ms_per_step(ctx, lambda o: scopes.REMAT in o.tf_op)
