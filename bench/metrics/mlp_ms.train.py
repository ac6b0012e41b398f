"""mlp_ms.train: device milliseconds per train step (per chip) of the
blocks' MLPs (scope ``mlp``), forward, recomputed and backward."""
from bench import scopes


def read(ctx, peaks):
    return scopes.layer_ms(ctx, "mlp")
