"""mfu.decode: the pooled decode step's least time over its device time.

For each engine step in the traced window, the least time of the decode
is the larger of its FLOPs and its bytes over the peaks
(``work.decode_work``: the weights once, each active slot's conv history
up to its own cursor, the taps up to the furthest cursor); summed, over
the device time of the decode program runs.
"""
from bench import work


def read(ctx, peaks):
    runs = ctx.reduced.program_runs("decode")
    steps = [c for c in ctx.counters["decode_cursors"] if c]
    if not runs or not steps or peaks is None:
        return None
    least = sum(work.least_time_s(*work.decode_work(ctx.dims, c), peaks)
                for c in steps)
    return 100.0 * least / sum(r.dur for r in runs)
