"""The held experts' grouped products against their roofline: the least
time the chip needs for the routed work of the traced verdicts (the
program's ``moe.held_assignments`` x 2 x 3 x d_model x moe_d_ff FLOPs at the
bf16 peak) or for reading the held experts' weights once per chunk and
layer (at the HBM bandwidth), whichever is larger, over the device time of
the ``experts`` scope (``bench/expert_work.py``)."""

from bench import expert_work, program_trace


def read(ctx):
    sc = program_trace.scopes()
    assignments = expert_work.held_assignments(ctx.verdicts)
    if sc is None or assignments is None:
        return None
    spent = expert_work.scope_seconds(ctx.trace, sc, ("experts",))
    if spent <= 0:
        return None
    chunks = -(-len(ctx.traffic.p_lens) // ctx.chunk)
    cfg = ctx.cell.config
    least = max(expert_work.assignment_flops(cfg, assignments)
                / ctx.peaks["bf16_flops"],
                ctx.verdicts * expert_work.weight_bytes(cfg, chunks)
                / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (spent * ctx.cell.chips)
