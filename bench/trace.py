"""Reduce a profiler trace of the measured window to device time.

``jax.profiler`` writes an ``.xplane.pb``; ``jax.profiler.ProfileData`` reads
it with nothing but JAX.  On a TPU v5e (JAX 0.9), device planes are named
``/device:TPU:<n>``.  Their ``XLA Modules`` line holds one event per
executed jitted program (``jit_fused_window(<hash>)``), and their ``XLA Ops``
line one event per executed HLO instruction, named by the instruction's HLO
text (``%fusion.145 = s32[6980000]{...} fusion(...)``), with no source path.
A ``while`` event spans the events of its body, so only leaf instructions
are summed.  The host plane holds the ``bench.*`` annotations that the
harness puts around the calls it makes.

Everything here works on plain lists, so ``bench/tests`` checks it on a
trace recorded on the chip and kept as a fixture.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
ANNOTATION_PREFIX = "bench."
# instructions whose event spans the events of the instructions they run
CONTAINERS = ("while", "conditional", "call")
_HLO = re.compile(r"^%(?P<name>\S+) = (?P<type>.+?) (?P<op>[a-z][a-z0-9\-]*)\(")


@dataclasses.dataclass
class Op:
    device: int
    text: str           # the instruction's HLO text, as the trace names it
    start: int          # ns, trace clock
    end: int
    module: str = ""    # the jitted program that ran it

    def __post_init__(self):
        m = _HLO.match(self.text)
        self.name = m.group("name") if m else self.text.split(" ")[0]
        self.opcode = m.group("op") if m else ""
        self.type = m.group("type") if m else ""

    @property
    def leaf(self) -> bool:
        return self.opcode not in CONTAINERS


@dataclasses.dataclass
class Span:
    name: str
    start: int
    end: int


@dataclasses.dataclass
class Trace:
    ops: List[Op]
    spans: List[Span]            # bench.* host annotations
    n_devices: int

    def leaves(self) -> List[Op]:
        return [o for o in self.ops if o.leaf]

    def window(self) -> Tuple[int, int]:
        """The ``bench.window`` annotation: the traced measured window."""
        w = [s for s in self.spans if s.name == "bench.window"]
        if not w:
            raise ValueError("no bench.window annotation in the trace")
        return w[0].start, w[0].end

    def to_json(self) -> dict:
        return {"n_devices": self.n_devices,
                "ops": [[o.device, o.text, o.start, o.end, o.module]
                        for o in self.ops],
                "spans": [[s.name, s.start, s.end] for s in self.spans]}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls(ops=[Op(*o) for o in d["ops"]],
                   spans=[Span(*s) for s in d["spans"]],
                   n_devices=d["n_devices"])


def module_name(event_name: str) -> str:
    """``jit_fused_window(4652810188605553070)`` -> ``jit_fused_window``."""
    return event_name.split("(", 1)[0]


def _attribute(ops: List[Op], modules: List[Tuple[int, int, str]]) -> None:
    """Set each op's module: the module event that contains its start."""
    modules.sort()
    starts = [m[0] for m in modules]
    for o in ops:
        i = bisect.bisect_right(starts, o.start) - 1
        if i >= 0 and modules[i][1] >= o.start:
            o.module = modules[i][2]


def load(log_dir: str) -> Trace:
    """The trace that ``jax.profiler`` wrote under ``log_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[0])
    ops, spans, devices = [], [], set()
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = int(plane.name[len(DEVICE_PREFIX):])
            dev_ops, modules = [], []
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules = [(int(e.start_ns),
                                int(e.start_ns) + int(e.duration_ns),
                                module_name(e.name)) for e in line.events]
                elif line.name == OPS_LINE:
                    devices.add(dev)
                    dev_ops = [Op(dev, e.name, int(e.start_ns),
                                  int(e.start_ns) + int(e.duration_ns))
                               for e in line.events]
            _attribute(dev_ops, modules)
            ops.extend(dev_ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(ANNOTATION_PREFIX):
                        start = int(e.start_ns)
                        spans.append(Span(e.name, start,
                                          start + int(e.duration_ns)))
    return Trace(ops=ops, spans=spans, n_devices=max(1, len(devices)))


def describe(log_dir: str, n_events: int = 40) -> dict:
    """Plane and line names, event counts, and the first events of each
    device line with all their stats: for looking at a trace by hand."""
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            entry = {"line": line.name, "events": len(evs)}
            if plane.name.startswith(DEVICE_PREFIX) or line.name == "python":
                entry["first"] = [[e.name, int(e.duration_ns),
                                   {k: str(v) for k, v in e.stats}]
                                  for e in evs[:n_events]]
            lines.append(entry)
        out.append({"plane": plane.name, "lines": lines})
    return {"path": path, "bytes": os.path.getsize(path), "planes": out}


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def union(intervals: Sequence[Tuple[int, int]], lo: int, hi: int
          ) -> List[Tuple[int, int]]:
    """Sorted, disjoint union of ``intervals`` clipped to [lo, hi]."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def covered(intervals: Sequence[Tuple[int, int]]) -> int:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Tuple[int, int]], lo: int, hi: int
         ) -> List[Tuple[int, int]]:
    """Idle intervals of [lo, hi] between the disjoint sorted ``busy``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def busy_s(trace: Trace, ops: Optional[Sequence[Op]] = None) -> float:
    """Seconds in the window during which an operation ran, averaged over
    the devices."""
    lo, hi = trace.window()
    ops = trace.ops if ops is None else ops
    total = 0
    for dev in {o.device for o in trace.ops} or {0}:
        total += covered(union([(o.start, o.end) for o in ops
                                if o.device == dev], lo, hi))
    return total / trace.n_devices / 1e9


def window_s(trace: Trace) -> float:
    lo, hi = trace.window()
    return (hi - lo) / 1e9


def op_seconds(trace: Trace, ops: Sequence[Op]) -> float:
    """Summed device time of the leaf ``ops`` inside the window, per
    device."""
    lo, hi = trace.window()
    return sum(max(0, min(o.end, hi) - max(o.start, lo)) for o in ops
               if o.leaf) / trace.n_devices / 1e9


# ---------------------------------------------------------------------------
# breakdown
# ---------------------------------------------------------------------------

# what the host was doing, innermost first: a gap is charged to the
# innermost bench annotation that covers its midpoint
_HOST_LABELS = {
    "bench.finalize": "finalize (StreamTopKStage.finalize)",
    "bench.handoff": "hand-off (snapshot to host params)",
    "bench.engine_run": "engine.run (placement, dispatch)",
    "bench.verdict": "verdict (metrics, ledger row)",
    "bench.window": "between verdicts",
}


def host_activity(trace: Trace, t: int) -> str:
    inner = None
    for s in trace.spans:
        if s.start <= t < s.end and s.name in _HOST_LABELS:
            if inner is None or (s.end - s.start) < (inner.end - inner.start):
                inner = s
    return _HOST_LABELS[inner.name] if inner is not None else "outside"


def op_label(op: Op) -> str:
    """A short readable name: program, instruction, opcode, result type."""
    return f"{op.module}/{op.name} {op.opcode} {op.type[:60]}".strip()


def breakdown(trace: Trace, n: int = 10) -> dict:
    """The leaf device operations that took most time, and the longest idle
    gaps, each with what the host was doing."""
    lo, hi = trace.window()
    by: Dict[str, float] = {}
    for o in trace.leaves():
        d = max(0, min(o.end, hi) - max(o.start, lo))
        if d:
            key = op_label(o)
            by[key] = by.get(key, 0.0) + d / 1e9 / trace.n_devices
    top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    dev0 = min({o.device for o in trace.ops}, default=0)
    idle = gaps(union([(o.start, o.end) for o in trace.ops
                       if o.device == dev0], lo, hi), lo, hi)
    idle.sort(key=lambda g: -(g[1] - g[0]))
    longest = [[host_activity(trace, (s + e) // 2), (e - s) / 1e9]
               for s, e in idle[:n]]
    return {"device_ops": [[k, v] for k, v in top], "idle_gaps": longest}


def save(trace: Trace, path: str) -> None:
    with open(path, "w") as f:
        json.dump(trace.to_json(), f)
