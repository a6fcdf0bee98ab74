"""Seconds per verdict in the validator's host finalize
(``StreamTopKStage.finalize``): the mean of the engine's
``timings["retrieve_s"]`` over the traced verdicts."""


def read(ctx):
    vals = [t["retrieve_s"] for t in ctx.timings if "retrieve_s" in t]
    return sum(vals) / len(vals) if vals else None
