"""idle_share.train: the share of the traced window in which no leaf op
ran on the device (averaged over the chips)."""


def read(ctx, peaks):
    if ctx.reduced.busy_s <= 0:
        return None
    return 100.0 * ctx.reduced.idle_share
