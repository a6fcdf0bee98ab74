"""Share of the device's busy time spent in the encoder's expert layers
(``_moe_dropless``: router, dispatch, the held experts' grouped products,
combine, shared experts): the program's ``moe`` and ``experts`` scopes
(``Telemetry.device_scopes``) over device busy time."""

from bench import expert_work, program_trace, trace


def read(ctx):
    sc = program_trace.scopes()
    busy = trace.busy_s(ctx.trace)
    if sc is None or busy <= 0:
        return None
    moe = expert_work.scope_seconds(ctx.trace, sc, ("moe", "experts"))
    return 100.0 * moe / busy if moe > 0 else None
