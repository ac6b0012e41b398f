"""mfu.train: the whole train step's share of the chips' peak FLOP/s.

Model FLOPs of the steps whose program ran inside the traced window
(App. A.2 counts, ``work.train_step_flops``), over the time from the
first such step's start to the last one's end, the chips and the peak.
"""
from bench import work


def read(ctx, peaks):
    runs = ctx.reduced.program_runs("train_step")
    if not runs or peaks is None:
        return None
    chips = ctx.shapes["chips"]
    n = len(runs) // chips
    span = max(r.end for r in runs) - min(r.start for r in runs)
    flops = n * work.train_step_flops(ctx.dims, ctx.shapes["batch"],
                                      ctx.shapes["seq_len"])
    return 100.0 * flops / (span * chips * peaks["flops_per_s"])
