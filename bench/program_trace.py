"""The program's own names for what a traced window holds.

The validator names its layers itself (``repro.obs``): each boundary of a
verdict is a span (``loaded``, ``placed``, ``encoded``, ``staged``,
``dispatch``, ``drain``, ``finalize.fetch``, ``finalize.build``,
``metrics``, ``recorded``, and ``gc`` for a host collection), seen by the
profiler as a ``repro.<span>`` annotation, and each device instruction of
the fused step carries a named scope (``encode``, ``score``, ``merge``)
that ``Telemetry.device_scopes`` maps from the instruction's text.

The readers here take those names from the program's live ``Telemetry``
in the benchmark's own process (the harness gives the suite one in a
traced run).  A program without them (an older commit) has no
``repro.obs.live_telemetry``, and every reader then returns ``None``.

``program_spans``, ``split_gaps`` and ``scoped_ops`` work on a profile
that kept the ``repro.*`` annotations (``record_scopes.py``): idle gaps cut
at span boundaries, and device time by scope.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

from bench import flops
from bench import trace as trace_lib
from bench.trace import Op, Span, Trace

PROGRAM_PREFIX = "repro."


def live_telemetry() -> list:
    """The program's live ``Telemetry`` handles; none where the program has
    no such registry."""
    try:
        from repro.obs import live_telemetry as live
    except ImportError:
        return []
    return list(live())


# ---------------------------------------------------------------------------
# spans of the window, from the program's recent spans
# ---------------------------------------------------------------------------


def window_spans(verdicts: int) -> Optional[List[Tuple[str, float, float]]]:
    """The program's spans ``(name, start, end)`` from the start of the
    ``loaded`` span of the last ``verdicts`` verdicts to the end of the
    last ``recorded`` span: the traced window's verdicts, whole."""
    if verdicts <= 0:
        return None
    for tel in live_telemetry():
        spans = list(getattr(tel, "recent_spans", ()))
        loaded = [s for s in spans if s[0] == "loaded"]
        recorded = [s for s in spans if s[0] == "recorded"]
        if len(loaded) >= verdicts and recorded:
            lo, hi = loaded[-verdicts][1], recorded[-1][2]
            return [s for s in spans if s[1] >= lo and s[2] <= hi]
    return None


def span_s_per_verdict(ctx, name: str) -> Optional[float]:
    """Seconds per verdict in the program's ``name`` spans in the window."""
    spans = window_spans(ctx.verdicts)
    if spans is None:
        return None
    return sum(e - s for n, s, e in spans if n == name) / ctx.verdicts


# ---------------------------------------------------------------------------
# device scopes
# ---------------------------------------------------------------------------


def _cut_short(text: str) -> bool:
    """A name the profile cut short leaves a bracket open."""
    return any(text.count(a) != text.count(b) for a, b in ("()", "{}", "[]"))


class Scopes:
    """Instruction text -> scope for the ops of the registered
    ``programs`` (another program, such as the query encoder, may hold an
    instruction of the same text); a name the profile cut short is looked
    up by its head (``%name = type opcode``) where that is unambiguous."""

    def __init__(self, by_text: Dict[str, str], programs: Sequence[str]):
        from repro.obs.scopes import op_head
        self.head = op_head
        self.by_text = dict(by_text)
        self.programs = set(programs)
        heads: Dict[str, set] = {}
        for text, scope in self.by_text.items():
            heads.setdefault(op_head(text), set()).add(scope)
        self.by_head = {h: next(iter(s)) for h, s in heads.items()
                        if len(s) == 1}

    def of(self, op: Op) -> Optional[str]:
        if op.module not in self.programs:
            return None
        scope = self.by_text.get(op.text)
        if scope is None and _cut_short(op.text):
            scope = self.by_head.get(self.head(op.text))
        return scope


def scopes() -> Optional[Scopes]:
    """The device scopes the program registered, merged over its live
    ``Telemetry`` handles; ``None`` when there are none."""
    merged: Dict[str, str] = {}
    programs: set = set()
    for tel in live_telemetry():
        merged.update(getattr(tel, "device_scopes", None) or {})
        programs |= getattr(tel, "device_programs", None) or set()
    return Scopes(merged, programs) if merged else None


def scope_seconds(trace: Trace, sc: Scopes, names: Sequence[str]) -> float:
    """Device time in the window of the leaf ops in any of ``names``."""
    return trace_lib.op_seconds(trace, [o for o in trace.leaves()
                                        if sc.of(o) in names])


def least_encode_s(ctx) -> float:
    """``encode_roofline``'s least time for one verdict's passage encode at
    real tokens plus scoring: the larger of FLOPs over the bf16 peak and
    the weight bytes read once per chunk over the HBM bandwidth."""
    cfg = ctx.cell.config
    n, d = len(ctx.traffic.p_lens), cfg["transformer"]["d_model"]
    work = ctx.encode_flops() + flops.scoring_flops(len(ctx.traffic.q_lens),
                                                    n, d)
    chunks = -(-n // ctx.chunk)
    return max(work / ctx.peaks["bf16_flops"],
               chunks * flops.weight_bytes(cfg) / ctx.peaks[
                   "hbm_bytes_per_s"])


# ---------------------------------------------------------------------------
# a profile that kept the program's annotations
# ---------------------------------------------------------------------------


def program_spans(log_dir: str) -> List[Span]:
    """The ``repro.*`` host annotations of the profile under ``log_dir``."""
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PROGRAM_PREFIX):
                    start = int(e.start_ns)
                    spans.append(Span(e.name, start,
                                      start + int(e.duration_ns)))
    return spans


def _label(span: Optional[Span]) -> str:
    if span is None:
        return "outside"
    return trace_lib._HOST_LABELS.get(span.name, span.name)


def split_gaps(trace: Trace, n: int = 10) -> List[List]:
    """The ``n`` longest idle gaps of the first device in the window, each
    cut at the boundaries of the host spans (``bench.*`` and ``repro.*``)
    and each piece charged to the innermost span that covers it:
    ``[[label, seconds], ...]`` per gap, in time order."""
    lo, hi = trace.window()
    dev0 = min({o.device for o in trace.ops}, default=0)
    idle = trace_lib.gaps(trace_lib.union(
        [(o.start, o.end) for o in trace.ops if o.device == dev0], lo, hi),
        lo, hi)
    idle.sort(key=lambda g: -(g[1] - g[0]))
    out = []
    for s, e in idle[:n]:
        inside = [sp for sp in trace.spans if sp.start < e and sp.end > s
                  and (sp.name in trace_lib._HOST_LABELS
                       or sp.name.startswith(PROGRAM_PREFIX))]
        cuts = sorted({s, e} | {t for sp in inside for t in (sp.start,
                                                             sp.end)
                                if s < t < e})
        pieces: List[List] = []
        for a, b in zip(cuts, cuts[1:]):
            cover = [sp for sp in inside if sp.start <= a and sp.end >= b]
            inner = min(cover, key=lambda sp: sp.end - sp.start,
                        default=None)
            label = _label(inner)
            if pieces and pieces[-1][0] == label:
                pieces[-1][1] += (b - a) / 1e9
            else:
                pieces.append([label, (b - a) / 1e9])
        out.append(pieces)
    return out


def scoped_ops(trace: Trace, sc: Scopes, n: int = 10) -> List[List]:
    """The leaf device operations that took most time in the window, each
    label prefixed with its scope (``-`` for none)."""
    lo, hi = trace.window()
    by: Dict[str, float] = {}
    for o in trace.leaves():
        d = max(0, min(o.end, hi) - max(o.start, lo))
        if d:
            key = f"{sc.of(o) or '-'}:{trace_lib.op_label(o)}"
            by[key] = by.get(key, 0.0) + d / 1e9 / trace.n_devices
    return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:n]]


def unscoped_share(trace: Trace, sc: Scopes, modules: Sequence[str]) -> float:
    """Share of the device time of ``modules``' leaf ops in the window that
    maps to no scope."""
    ops = [o for o in trace.leaves() if o.module in modules]
    total = trace_lib.op_seconds(trace, ops)
    none = trace_lib.op_seconds(trace, [o for o in ops if sc.of(o) is None])
    return none / total if total > 0 else 0.0
