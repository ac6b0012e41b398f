"""optimizer_ms.train: device milliseconds per train step (per chip) of
AdamW with the gradients' global norm and the clip (scope
``optimizer``)."""
from bench import scopes


def read(ctx, peaks):
    return scopes.layer_ms(ctx, "optimizer")
