"""Seconds per verdict in which the validator computes the verdict's
metrics from its run lists (``compute_metrics`` in
``ValidationSuite.run_unit``): the program's ``metrics`` spans in the
traced window, per verdict."""

from bench import program_trace


def read(ctx):
    return program_trace.span_s_per_verdict(ctx, "metrics")
