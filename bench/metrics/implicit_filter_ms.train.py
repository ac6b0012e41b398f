"""implicit_filter_ms.train: device milliseconds per train step (per
chip) of the implicit filter: its FFN, decay window and skip gains (scope
``implicit_filter``), forward, recomputed and backward."""
from bench import scopes


def read(ctx, peaks):
    return scopes.layer_ms(ctx, "implicit filter")
