"""long_conv_roofline.train: the long conv's least time on the chip
(``work.train_conv_work``: forward and both backward convs, per layer and
order) over the device time of the ops attributed to the long conv."""
from bench import work


def read(ctx, peaks):
    runs = ctx.reduced.program_runs("train_step")
    t = ctx.reduced.layer_time("long conv", within="train_step")
    if not runs or t <= 0 or peaks is None:
        return None
    n = len(runs)  # step runs over all chips: each chip's share of work
    f, b = work.train_conv_work(ctx.dims, ctx.shapes["batch"],
                                ctx.shapes["seq_len"])
    chips = ctx.shapes["chips"]
    return 100.0 * (n / chips) * work.least_time_s(f, b, peaks) / t
