"""Seconds per verdict in which host garbage collection paused the
validator: the program's ``gc`` spans (one per collection, timed by its
``gc.callbacks`` hook) in the traced window, per verdict."""

from bench import program_trace


def read(ctx):
    return program_trace.span_s_per_verdict(ctx, "gc")
