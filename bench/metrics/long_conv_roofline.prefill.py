"""long_conv_roofline.prefill: the long conv's least time in the traced
window's prefills (``work.prefill_conv_work`` per prompt) over the device
time of long-conv ops inside prefill program runs."""
from bench import work


def read(ctx, peaks):
    lens = [n for step in ctx.counters["prefill_lens"] for n in step]
    t = ctx.reduced.layer_time("long conv", within="prefill")
    if not lens or t <= 0 or peaks is None:
        return None
    least = sum(work.least_time_s(*work.prefill_conv_work(ctx.dims, L),
                                  peaks) for L in lens)
    return 100.0 * least / t
