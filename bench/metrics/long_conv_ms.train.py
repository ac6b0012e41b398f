"""long_conv_ms.train: device milliseconds of the long conv's ops per
train step (per chip)."""


def read(ctx, peaks):
    runs = ctx.reduced.program_runs("train_step")
    t = ctx.reduced.layer_time("long conv", within="train_step")
    if not runs or t <= 0:
        return None
    return 1e3 * t / len(runs)
