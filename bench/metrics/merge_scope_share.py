"""Share of the device's busy time spent in the fused step's ``merge``
scope (the running top-k fold: ``_merge_topk`` in ``StreamTopKStage``),
classified by the scope the program itself names each instruction with
(``Telemetry.device_scopes``), not by shape."""

from bench import program_trace, trace


def read(ctx):
    sc = program_trace.scopes()
    busy = trace.busy_s(ctx.trace)
    if sc is None or busy <= 0:
        return None
    merge = program_trace.scope_seconds(ctx.trace, sc, ("merge",))
    return 100.0 * merge / busy if merge > 0 else None
