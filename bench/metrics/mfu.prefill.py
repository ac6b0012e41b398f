"""mfu.prefill: the admission prefills' share of the chip's peak FLOP/s.

Model FLOPs of the prompts prefilled in the traced window (App. A.2
counts over each prompt, the filter taps on the ``max_len`` grid, the LM
head for the last position only) over the device time of the prefill
program runs, over the peak.
"""
from bench import work


def read(ctx, peaks):
    runs = ctx.reduced.program_runs("prefill")
    lens = [n for step in ctx.counters["prefill_lens"] for n in step]
    if not runs or not lens or peaks is None:
        return None
    d, grid = ctx.dims, ctx.shapes["max_len"]
    flops = sum(
        d.n_layers * (L * work.layer_token_flops(d, L)
                      + work.filter_flops(d, grid))
        + 2.0 * d.d_model * d.vocab_size for L in lens)
    t = sum(r.dur for r in runs)
    return 100.0 * flops / (t * peaks["flops_per_s"])
