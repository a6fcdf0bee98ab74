"""Encoder ops of the fused step against their roofline, by the program's
own scopes: ``encode_roofline``'s least time for the passage encode at real
tokens plus scoring, over the device time of the instructions in the
``encode`` and ``score`` scopes (``Telemetry.device_scopes``)."""

from bench import program_trace


def read(ctx):
    sc = program_trace.scopes()
    if sc is None or ctx.verdicts == 0:
        return None
    spent = program_trace.scope_seconds(ctx.trace, sc, ("encode", "score"))
    if spent <= 0:
        return None
    least = program_trace.least_encode_s(ctx)
    return 100.0 * ctx.verdicts * least / (spent * ctx.cell.chips)
