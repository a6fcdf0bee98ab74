"""Share of the device's busy time spent in the top-k merge of the fused
step (``_merge_topk`` in ``StreamTopKStage``: the sort over queries x
(k + chunk) candidates and the gather of their ids), classified by HLO
instruction in the trace (``Context.is_merge``)."""

from bench import trace


def read(ctx):
    busy = trace.busy_s(ctx.trace)
    merge = [o for o in ctx.trace.ops if ctx.is_merge(o)]
    if busy <= 0 or not merge:
        return None
    return 100.0 * trace.op_seconds(ctx.trace, merge) / busy
