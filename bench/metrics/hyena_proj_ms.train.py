"""hyena_proj_ms.train: device milliseconds per train step (per chip) of
the Hyena operator's in- and out-projections with their biases (scope
``hyena_proj``), forward, recomputed and backward."""
from bench import scopes


def read(ctx, peaks):
    return scopes.layer_ms(ctx, "hyena proj")
