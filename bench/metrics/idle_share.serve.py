"""idle_share.serve: the share of the traced serving window in which no
leaf op ran on the device."""


def read(ctx, peaks):
    if ctx.reduced.busy_s <= 0:
        return None
    return 100.0 * ctx.reduced.idle_share
