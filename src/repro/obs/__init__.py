"""Observability — lifecycle span tracing and a process metrics registry.

The single entry point is :class:`Telemetry`: a nullable handle threaded
through the watcher, engine, work queue, validator, control plane, and
serving tier.  Every instrumentation site follows one pattern::

    tel = self.telemetry
    if tel is not None:
        with tel.span("finalize"):
            ...

so *disabled* telemetry (the default — every constructor defaults to
``telemetry=None``) costs one attribute check and one ``is not None``
branch per site, writes no files, installs no hook, and leaves ledgers and
event logs byte-identical.  Enabled telemetry writes spans to its own trace
file (never to any ledger) and aggregates metrics in memory; nothing it
produces is ever read back by a scheduling, claim, or selection decision.

Two clocks see every span of a live ``Telemetry``:

* the JSONL tracer (when ``trace_path`` is set) records it in
  ``time.monotonic()`` seconds, which fleet processes on one host share;
* ``jax.profiler`` sees it as a ``TraceAnnotation`` named ``repro.<span>``,
  so a profile lines each span up with the device operations it waited on
  or dispatched.  Outside a profile the annotation costs a flag check; JAX
  is imported only when the first span opens.

The newest spans also stay in memory, ``recent_spans`` (``(name, start,
end)`` in monotonic seconds, bounded), for in-process readers that total
span time over an interval without a trace file.

A ``Telemetry`` can be metrics-only (``trace_path=None``): the registry
aggregates latencies for ``--obs_report`` without any span file I/O.
While any ``Telemetry`` is alive, a ``gc.callbacks`` hook times every host
garbage collection into its ``host.gc_pause_s`` histogram and a ``gc``
span.  ``device_scopes`` maps each device instruction of the programs the
engine dispatched to its named scope (``encode``/``score``/``merge``, and
``moe``/``experts`` inside an expert trunk's encode; see
:mod:`repro.obs.scopes`), and ``device_programs`` names those programs: an
instruction of another program (the query encoder) can have the same
text.  ``mark``/``since`` provide cross-stage latency
measurement (e.g. checkpoint discovery → verdict recorded) keyed on
arbitrary tuples.
"""

from __future__ import annotations

import collections
import gc
import threading
import time
import weakref
from typing import Any, Callable, Dict, Optional, Sequence

from repro.obs.metrics import (Counter, Ewma, Gauge, Histogram,
                               MetricsRegistry)
from repro.obs.scopes import SCOPES, hlo_scopes, module_name
from repro.obs.trace import LIFECYCLE_STAGES, SpanTracer, read_trace

__all__ = ["Telemetry", "MetricsRegistry", "SpanTracer", "read_trace",
           "LIFECYCLE_STAGES", "Counter", "Gauge", "Ewma", "Histogram",
           "SCOPES", "hlo_scopes", "live_telemetry", "GC_PAUSE_METRIC",
           "HELD_ASSIGNMENTS_METRIC", "MERGE_SORTS_METRIC"]

#: histogram of host garbage-collection pauses, in seconds
GC_PAUSE_METRIC = "host.gc_pause_s"
#: histogram of the (token, held expert) assignments an expert trunk's
#: passage stream computed, one observation per stream (a verdict's corpus)
HELD_ASSIGNMENTS_METRIC = "moe.held_assignments"
#: counter of the top-k merges (one sort each) the retrieval stage dispatched
MERGE_SORTS_METRIC = "merge.sorts"
ANNOTATION_PREFIX = "repro."
#: finished spans a Telemetry keeps in memory: a few hundred verdicts' worth
RECENT_SPANS = 1 << 16

_annotation_cls: Any = None         # jax.profiler.TraceAnnotation, or False


def _annotation() -> Optional[Callable]:
    """``jax.profiler.TraceAnnotation``, imported on first use; ``None``
    where JAX is not installed."""
    global _annotation_cls
    if _annotation_cls is None:
        try:
            from jax.profiler import TraceAnnotation
            _annotation_cls = TraceAnnotation
        except ImportError:
            _annotation_cls = False
    return _annotation_cls or None


class _Span:
    """One span of a :class:`Telemetry`: a profiler annotation, the JSONL
    tracer's span when there is a tracer, and an entry in
    ``recent_spans``.  ``dur`` is its length in seconds once closed."""

    __slots__ = ("tel", "name", "attrs", "ann", "inner", "t0", "dur")

    def __init__(self, tel: "Telemetry", name: str, attrs: Dict[str, Any]):
        self.tel = tel
        self.name = name
        self.attrs = attrs
        self.dur = 0.0

    def __enter__(self) -> "_Span":
        cls = _annotation()
        self.ann = cls(ANNOTATION_PREFIX + self.name) if cls else None
        if self.ann is not None:
            self.ann.__enter__()
        tracer = self.tel.tracer
        self.inner = None if tracer is None \
            else tracer.span(self.name, **self.attrs).__enter__()
        self.t0 = time.monotonic() if self.inner is None else self.inner.t0
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self.inner is not None:
            self.inner.__exit__(exc_type, exc, tb)
            self.dur = self.inner.dur
        else:
            self.dur = time.monotonic() - self.t0
        self.tel.recent_spans.append((self.name, self.t0, self.t0 + self.dur))
        if self.ann is not None:
            self.ann.__exit__(exc_type, exc, tb)


# -- host garbage collection --------------------------------------------------
# one gc.callbacks hook for every live Telemetry, installed with the first
# and removed with the last

_LIVE: "weakref.WeakSet[Telemetry]" = weakref.WeakSet()
_live_lock = threading.Lock()
_n_live = 0
_gc_open: list = []                 # [start, annotation] of the running pass


def _gc_hook(phase: str, info: Dict[str, Any]) -> None:
    if phase == "start":
        cls = _annotation_cls or None   # never imports inside a collection
        ann = cls(ANNOTATION_PREFIX + "gc") if cls else None
        if ann is not None:
            ann.__enter__()
        _gc_open[:] = [time.monotonic(), ann]
        return
    if not _gc_open:
        return
    t0, ann = _gc_open
    t1 = time.monotonic()
    del _gc_open[:]
    if ann is not None:
        ann.__exit__(None, None, None)
    for tel in list(_LIVE):
        tel._gc_pause.observe(t1 - t0)
        tel.recent_spans.append(("gc", t0, t1))


def _release() -> None:
    global _n_live
    with _live_lock:
        _n_live -= 1
        if _n_live == 0 and _gc_hook in gc.callbacks:
            gc.callbacks.remove(_gc_hook)


def live_telemetry() -> Sequence["Telemetry"]:
    """The ``Telemetry`` handles alive in this process."""
    return list(_LIVE)


class Telemetry:
    """Tracer + metrics registry + cross-stage marks, behind one handle.

    Parameters
    ----------
    trace_path:
        JSONL trace file for lifecycle spans; ``None`` for metrics-only.
    registry:
        Share an existing :class:`MetricsRegistry` (e.g. between a
        validator and its watcher policy); a fresh one is created if
        omitted.
    process / attrs:
        Tracer identity: ``process`` labels this process's timeline track
        and ``attrs`` (e.g. ``{"worker_id": "w0"}``) are stamped on every
        span/event the tracer writes.
    """

    def __init__(self, trace_path: Optional[str] = None, *,
                 registry: Optional[MetricsRegistry] = None,
                 process: Optional[str] = None,
                 attrs: Optional[Dict[str, Any]] = None):
        global _n_live
        self.metrics = registry if registry is not None else MetricsRegistry()
        self.tracer: Optional[SpanTracer] = (
            SpanTracer(trace_path, process=process, attrs=attrs)
            if trace_path else None)
        self._marks: Dict[Any, float] = {}
        self._marks_lock = threading.Lock()
        self.recent_spans: "collections.deque" = collections.deque(
            maxlen=RECENT_SPANS)
        self.device_scopes: Dict[str, str] = {}
        self.device_programs: set = set()   # their modules' names
        self._programs: set = set()
        # created here, never inside a collection: the hook takes no
        # registry lock that the collected code might hold
        self._gc_pause = self.metrics.histogram(GC_PAUSE_METRIC)
        with _live_lock:
            _LIVE.add(self)
            _n_live += 1
            if _gc_hook not in gc.callbacks:
                gc.callbacks.append(_gc_hook)
        weakref.finalize(self, _release)

    # -- tracing ------------------------------------------------------------
    def span(self, name: str, **attrs: Any) -> _Span:
        """Context manager for a lifecycle span: a ``repro.<name>``
        profiler annotation, a tracer span when there is a tracer, and an
        entry in ``recent_spans``."""
        return _Span(self, name, attrs)

    def event(self, name: str, **attrs: Any) -> None:
        tracer = self.tracer
        if tracer is not None:
            tracer.event(name, **attrs)

    def record(self, name: str, t0: float, dur: float, **attrs: Any) -> None:
        """A span after the fact (tracer only): for loops that time many
        pieces and emit one record."""
        tracer = self.tracer
        if tracer is not None:
            tracer.record(name, t0, dur, **attrs)

    def flush(self) -> None:
        tracer = self.tracer
        if tracer is not None:
            tracer.flush()

    # -- device scopes ------------------------------------------------------
    def register_program(self, fn: Callable, args: Sequence[Any]) -> None:
        """Add the device scopes of jitted ``fn`` at ``args``' shapes to
        ``device_scopes``, once per shapes (``args[0]``, the params, is the
        same tree at every call).  The compile is the one the call itself
        makes, so it comes from the compilation cache."""
        key = (fn,) + tuple((getattr(a, "shape", None),
                             str(getattr(a, "dtype", type(a).__name__)))
                            for a in args[1:])
        if key in self._programs:
            return
        self._programs.add(key)
        text = fn.lower(*args).compile().as_text()
        self.device_scopes.update(hlo_scopes(text))
        self.device_programs.add(module_name(text))

    # -- cross-stage latency marks ------------------------------------------
    def mark(self, name: str, key: Any) -> None:
        """Remember *now* (monotonic) under ``(name, key)``."""
        with self._marks_lock:
            self._marks[(name, key)] = time.monotonic()

    def since(self, name: str, key: Any, *, pop: bool = False
              ) -> Optional[float]:
        """Seconds since :meth:`mark`, or ``None`` if never marked (e.g.
        the mark lives in another fleet process)."""
        with self._marks_lock:
            t0 = (self._marks.pop((name, key), None) if pop
                  else self._marks.get((name, key)))
        return None if t0 is None else time.monotonic() - t0
