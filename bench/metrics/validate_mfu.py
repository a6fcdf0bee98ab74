"""Whole validation step's share of the chips' bfloat16 peak: the work the
completed verdicts of the traced window require (encoder forward at real
token counts for passages and queries, plus scoring), over window time x
peak x chips."""

from bench import trace


def read(ctx):
    window = trace.window_s(ctx.trace)
    if not ctx.trace.ops or ctx.verdicts == 0 or window <= 0:
        return None
    peak = ctx.peaks["bf16_flops"] * ctx.cell.chips
    return 100.0 * ctx.verdicts * ctx.verdict_flops() / (window * peak)
