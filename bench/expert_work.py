"""Work and bytes of the held experts' grouped products, for
``expert_roofline``.

The encode path's expert layer (``_moe_dropless`` in the program's
``models/transformer.py``) computes, per chunk and expert layer, the SwiGLU
of each held expert over the tokens routed to it: three products of
``d_model`` x ``moe_d_ff`` for each (token, held expert) assignment.  The
program counts the assignments on the device (``moe.held_assignments``, one
observation per verdict's passage stream); the weights of the held experts
are read in the compute dtype once per chunk and layer.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from bench import program_trace
from bench import trace as trace_lib

HELD_ASSIGNMENTS = "moe.held_assignments"


def scope_seconds(trace, sc, names) -> float:
    """Device time in the window of the leaf ops in any of the scopes
    ``names``.  Besides ``Scopes.of``, an op of a registered program whose
    full text the map lacks is looked up by its head (``%name = type
    opcode``) where that is unambiguous: the profile writes a Pallas
    kernel's custom call (the grouped products) with attributes the
    compiled text does not show in that form, though no shorter."""
    def scope(op):
        found = sc.of(op)
        if found is None and op.module in sc.programs:
            found = sc.by_head.get(sc.head(op.text))
        return found
    return trace_lib.op_seconds(trace, [o for o in trace.leaves()
                                        if scope(o) in names])


def assignment_flops(cfg: dict, assignments: float) -> float:
    """FLOPs of ``assignments`` (token, held expert) SwiGLUs."""
    t = cfg["transformer"]
    return 2.0 * 3 * t["d_model"] * t["moe_d_ff"] * assignments


def weight_bytes(cfg: dict, chunks: int) -> float:
    """Bytes of the held experts' weights, read once per chunk and expert
    layer in the compute dtype."""
    t = cfg["transformer"]
    held = t.get("moe_experts_held") or t["moe_num_experts"]
    layers = t["n_layers"] - t["first_k_dense"]
    return (float(chunks) * layers * held * 3 * t["d_model"] * t["moe_d_ff"]
            * jnp.dtype(cfg["compute_dtype"]).itemsize)


def held_assignments(verdicts: int) -> Optional[float]:
    """The assignments of the last ``verdicts`` passage streams, from the
    program's live telemetry; ``None`` where the program records none."""
    for tel in program_trace.live_telemetry():
        hist = tel.metrics.get(HELD_ASSIGNMENTS)
        recent = getattr(hist, "recent", None)
        if recent is not None:
            vals = recent(verdicts)
            if verdicts > 0 and len(vals) == verdicts:
                return float(sum(vals))
    return None
