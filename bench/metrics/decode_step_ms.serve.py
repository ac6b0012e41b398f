"""decode_step_ms.serve: mean device milliseconds of one pooled decode
program run in the traced window."""


def read(ctx, peaks):
    runs = ctx.reduced.program_runs("decode")
    if not runs:
        return None
    return 1e3 * sum(r.dur for r in runs) / len(runs)
