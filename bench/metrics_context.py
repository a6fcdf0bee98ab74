"""What a per-layer metric reader is given: the traced run's readings."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List

from bench import flops
from bench.cells import Cell
from bench.trace import Op, Trace
from bench.traffic import Traffic


@dataclasses.dataclass
class Context:
    cell: Cell
    trace: Trace                 # the traced window
    traffic: Traffic
    verdicts: int                # verdicts completed in the traced window
    device: Dict[str, Any]       # as the result line reports it
    timings: List[Dict[str, float]]  # the engine's timings, one per verdict
    staging_wait_s: float        # engine.staging_wait_s summed over them
    chunk: int                   # passages per fused step (the store's)

    def is_merge(self, op: Op) -> bool:
        """An instruction of the top-k merge: a sort, or one that holds the
        (queries, k + chunk) candidates or the (queries x k) carry ids.  The
        trace names instructions by their HLO text, with no source path, so
        the merge is told from the encoder by the shapes only it has."""
        q = len(self.traffic.q_lens)
        k = min(int(self.cell.mix["k"]), len(self.traffic.p_lens))
        shapes = (f"[{q},{k + self.chunk}]", f"[{q * k}]")
        return op.leaf and (op.opcode == "sort"
                            or any(s in op.text for s in shapes))

    def fused_step_ops(self) -> List[Op]:
        """Leaf instructions of the programs that run the merge: the fused
        encode -> score -> top-k step."""
        modules = {o.module for o in self.trace.ops if self.is_merge(o)}
        return [o for o in self.trace.leaves() if o.module in modules]

    @property
    def peaks(self) -> Dict[str, float]:
        """``bench/peaks.json``'s entry for the device kind (an error for a
        kind that is not there)."""
        return flops.peaks(self.device["kind"])

    def encode_flops(self) -> float:
        """Passage encode of one verdict, at real token counts."""
        return flops.encoder_flops(self.cell.config, self.traffic.p_lens)

    def verdict_flops(self) -> float:
        """Everything one verdict requires: passage and query encode at
        real token counts, and scoring every query against every passage."""
        d = self.cell.config["transformer"]["d_model"]
        return (self.encode_flops()
                + flops.encoder_flops(self.cell.config, self.traffic.q_lens)
                + flops.scoring_flops(len(self.traffic.q_lens),
                                      len(self.traffic.p_lens), d))
