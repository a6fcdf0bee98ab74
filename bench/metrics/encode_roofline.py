"""Encoder ops of the fused step against their roofline: the least time the
chip needs for the passage encode at real token counts plus the scoring
(the larger of FLOPs over the bf16 peak and the weight bytes read once per
chunk over the HBM bandwidth), over the device time of the fused step's
instructions that are not the merge.  At these shapes the FLOP bound is
the larger by about tenfold."""

from bench import flops, trace


def read(ctx):
    ops = [o for o in ctx.fused_step_ops() if not ctx.is_merge(o)]
    spent = trace.op_seconds(ctx.trace, ops)
    if ctx.verdicts == 0 or spent <= 0:
        return None
    cfg = ctx.cell.config
    n, d = len(ctx.traffic.p_lens), cfg["transformer"]["d_model"]
    work = ctx.encode_flops() + flops.scoring_flops(len(ctx.traffic.q_lens),
                                                    n, d)
    chunks = -(-n // ctx.chunk)
    least = max(work / ctx.peaks["bf16_flops"],
                chunks * flops.weight_bytes(cfg) / ctx.peaks[
                    "hbm_bytes_per_s"])
    return 100.0 * ctx.verdicts * least / (spent * ctx.cell.chips)
