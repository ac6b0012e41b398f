"""lm_head_ms.train: device milliseconds per train step (per chip) of the
LM head's matmul and the loss's logsumexp, cross-entropy and z-loss
(scope ``lm_head``), forward and backward."""
from bench import scopes


def read(ctx, peaks):
    return scopes.layer_ms(ctx, "lm head")
