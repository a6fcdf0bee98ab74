"""Seconds per verdict in which the validator builds the run lists from
the host copy of the top-k carry: the program's ``finalize.build`` spans
in the traced window, per verdict (``finalize.fetch``, the copy itself, is
not in it)."""

from bench import program_trace


def read(ctx):
    return program_trace.span_s_per_verdict(ctx, "finalize.build")
