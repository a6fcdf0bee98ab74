"""The readers of the program's own span and scope names, on a trace of the
test cell recorded on a TPU v5e with them (``data/trace_v5e_scoped.json``,
written by ``record_scopes.py --tiny``)."""

import json
import os

import pytest

from bench import metrics_context, program_trace, traffic
from bench import trace as trace_lib
from bench.cells import load_cell
from bench.trace import Span, Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
FIXTURE = os.path.join(DATA, "trace_v5e_scoped.json")
NEW = ("merge_scope_share", "encode_scope_roofline", "finalize_build_s",
       "verdict_metrics_s", "gc_pause_s")


class _Program:
    """Stands in for the program's live ``Telemetry``: what the recorded
    run's program registered and spanned."""

    def __init__(self, scopes, programs, spans):
        self.device_scopes = scopes
        self.device_programs = set(programs)
        self.recent_spans = spans


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE) as f:
        d = json.load(f)
    return d, Trace.from_json(d)


@pytest.fixture
def ctx(recorded, monkeypatch):
    d, tr = recorded
    prefix = program_trace.PROGRAM_PREFIX
    spans = sorted((s.name[len(prefix):], s.start / 1e9, s.end / 1e9)
                   for s in tr.spans if s.name.startswith(prefix))
    monkeypatch.setattr(program_trace, "live_telemetry",
                        lambda: [_Program(d["scopes"], d["programs"],
                                          spans)])
    cell = load_cell("tiny-bert.tiny-k20",
                     benchmark=os.path.join(DATA, "BENCHMARK_scoped.json"),
                     data_dir=DATA)
    from repro.core.engine import chunk_geometry
    from repro.core.suite import ValidationConfig
    tr_data = traffic.generate(cell.mix, int(cell.sizes["corpus"]),
                               d["seed"])
    lo, hi = tr.window()
    verdicts = sum(1 for s in tr.spans
                   if s.name == "bench.verdict" and lo <= s.start < hi)
    chunk, _ = chunk_geometry(ValidationConfig(), len(tr_data.corpus))
    return metrics_context.Context(
        cell=cell, trace=tr, traffic=tr_data, verdicts=verdicts,
        device={"kind": d["device"]}, timings=[], staging_wait_s=0.0,
        chunk=chunk)


def _read(ctx, name):
    return ctx.cell.metric_reader(name)(ctx)


def test_every_new_reader_reads_the_recorded_window(ctx):
    assert ctx.verdicts == 4
    values = {name: _read(ctx, name) for name in NEW}
    assert all(v is not None and v >= 0 for v in values.values()), values
    assert 0 < values["merge_scope_share"] < 100
    assert 0 < values["encode_scope_roofline"] < 100
    assert values["finalize_build_s"] > 0 and values["verdict_metrics_s"] > 0


def test_scope_readers_agree_with_the_shape_readers(ctx):
    """Every instruction the shape rule calls the merge is in the merge
    scope; the scope adds the fold's own small ops that the rule misses
    (the carry's layout copies, the column iota, the scan's slice of the
    chunk bases), which weigh about 2 points at this size and a few
    hundredths of a point at the cells' sizes (PERF.md)."""
    sc = program_trace.scopes()
    leaves = ctx.trace.leaves()
    by_shape = {o.text for o in leaves if ctx.is_merge(o)}
    by_scope = {o.text for o in leaves if sc.of(o) == "merge"}
    assert by_shape and by_shape <= by_scope
    assert _read(ctx, "merge_scope_share") == pytest.approx(
        _read(ctx, "merge_device_share"), abs=2.5)
    assert _read(ctx, "encode_scope_roofline") == pytest.approx(
        _read(ctx, "encode_roofline"), abs=2.0)


def test_fused_step_time_maps_to_scopes(ctx):
    sc = program_trace.scopes()
    assert sc.programs == {"jit_fused_window", "jit_fused"}
    share = program_trace.unscoped_share(ctx.trace, sc, sorted(sc.programs))
    assert share < 0.01
    # the query encoder shares instruction texts with the fused step, but
    # is not one of its programs
    enc = [o for o in ctx.trace.leaves() if o.module == "jit_enc"]
    assert any(o.text in sc.by_text for o in enc)
    assert all(sc.of(o) is None for o in enc)


def test_build_is_inside_finalize_s(ctx, recorded):
    _, tr = recorded
    lo, hi = tr.window()
    finalize = sum(s.end - s.start for s in tr.spans
                   if s.name == "repro.finalize" and lo <= s.start < hi)
    build = _read(ctx, "finalize_build_s") * ctx.verdicts
    assert 0 < build <= finalize / 1e9


def test_readers_find_nothing_without_the_programs_names(ctx, monkeypatch):
    monkeypatch.setattr(program_trace, "live_telemetry", lambda: [])
    assert all(_read(ctx, name) is None for name in NEW)


def test_split_gaps_sum_to_the_unsplit_gaps(recorded):
    _, tr = recorded
    whole = trace_lib.breakdown(tr)["idle_gaps"]
    split = program_trace.split_gaps(tr)
    assert len(split) == len(whole)
    for pieces, (_, seconds) in zip(split, whole):
        assert sum(s for _, s in pieces) == pytest.approx(seconds, abs=1e-9)
    labels = {label for pieces in split for label, _ in pieces}
    assert any(label.startswith("repro.") for label in labels)


def test_split_gaps_charge_the_innermost_span():
    tr = Trace(ops=[trace_lib.Op(0, "%a.1 = f32[2] add(%x)", 0, 100),
                    trace_lib.Op(0, "%b.1 = f32[2] add(%x)", 1000, 1100)],
               spans=[Span("bench.window", 0, 1100),
                      Span("bench.verdict", 0, 1090),
                      Span("repro.finalize", 100, 600),
                      Span("repro.finalize.fetch", 100, 200),
                      Span("repro.metrics", 700, 900)],
               n_devices=1)
    (pieces,) = program_trace.split_gaps(tr, n=1)
    assert [(label, round(s * 1e9)) for label, s in pieces] == [
        ("repro.finalize.fetch", 100), ("repro.finalize", 400),
        ("verdict (metrics, ledger row)", 100), ("repro.metrics", 200),
        ("verdict (metrics, ledger row)", 100)]


def test_scoped_ops_carry_their_scope(ctx):
    top = program_trace.scoped_ops(ctx.trace, program_trace.scopes())
    assert top and all(label.split(":", 1)[0] in
                       ("encode", "score", "merge", "-")
                       for label, _ in top)
    assert any(label.startswith("merge:") for label, _ in top)
