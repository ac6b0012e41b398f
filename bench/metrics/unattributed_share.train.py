"""unattributed_share.train: the share of the train step's leaf-op device
time that neither ``layers.json`` nor a scope of the program
(``scopes.py``) attributes to a layer."""
from bench import scopes


def read(ctx, peaks):
    ops = scopes.step_ops(ctx.reduced)
    total = sum(o.dur for o in ops)
    if total <= 0:
        return None
    other = sum(o.dur for o in ops if scopes.layer_of(o) == "other")
    return 100.0 * other / total
