"""The reduction from a trace to device time, on hand-made intervals and on
a trace recorded on a TPU v5e (``data/trace_v5e.json``, the test cell run
by ``record_trace.py --tiny``)."""

import json
import os

import pytest

from bench import trace
from bench.trace import Op, Span, Trace

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "trace_v5e.json")


def test_union_gaps_and_cover():
    busy = trace.union([(5, 8), (0, 3), (2, 4), (9, 20)], 1, 12)
    assert busy == [(1, 4), (5, 8), (9, 12)]
    assert trace.covered(busy) == 9
    assert trace.gaps(busy, 0, 15) == [(0, 1), (4, 5), (8, 9), (12, 15)]


def _op(text, start, end, module="jit_fused_window"):
    return Op(0, text, start, end, module)


def _hand_trace():
    ops = [
        # a while spans its body; the body's instructions are the leaves
        _op("%while.3 = (s32[], f32[8,4]) while(%t), body=%b", 100, 700),
        _op("%fusion.1 = bf16[64,128,768]{2,1,0} fusion(%a), kind=kOutput",
            100, 400),
        _op("%sort.6 = (f32[8,68]{0,1}, s32[8,68]{0,1}) sort(%c, %i)",
            400, 500),
        _op("%fusion.9 = s32[32]{0} fusion(s32[8,68]{1,0} %p, s32[32] %r)",
            500, 700),
        _op("%fusion.2 = f32[8,768] fusion(%q)", 900, 1000, "jit_enc"),
    ]
    spans = [Span("bench.window", 0, 1100), Span("bench.verdict", 0, 1090),
             Span("bench.engine_run", 0, 800),
             Span("bench.finalize", 700, 900)]
    return Trace(ops=ops, spans=spans, n_devices=1)


def test_hlo_text_is_parsed():
    t = _hand_trace()
    assert [(o.name, o.opcode) for o in t.ops[:3]] == [
        ("while.3", "while"), ("fusion.1", "fusion"), ("sort.6", "sort")]
    assert [o.leaf for o in t.ops] == [False, True, True, True, True]
    assert t.ops[1].type == "bf16[64,128,768]{2,1,0}"


def test_busy_idle_and_op_seconds():
    t = _hand_trace()
    assert trace.window_s(t) == pytest.approx(1100e-9)
    assert trace.busy_s(t) == pytest.approx(700e-9)
    # the while is not counted again on top of its body
    assert trace.op_seconds(t, t.ops) == pytest.approx(700e-9)


def test_breakdown_charges_gaps_to_the_host():
    b = trace.breakdown(_hand_trace())
    assert b["device_ops"][0] == ["jit_fused_window/fusion.1 fusion "
                                  "bf16[64,128,768]{2,1,0}", 300e-9]
    assert [(who, round(s * 1e9)) for who, s in b["idle_gaps"]] == [
        ("finalize (StreamTopKStage.finalize)", 200),
        ("engine.run (placement, dispatch)", 100),
        ("verdict (metrics, ledger row)", 100)]


def test_modules_are_attributed_by_time():
    ops = [Op(0, "%a.1 = f32[2] add(%x)", 10, 20),
           Op(0, "%b.1 = f32[2] add(%x)", 50, 60)]
    trace._attribute(ops, [(40, 70, "jit_enc"), (0, 30, "jit_fused")])
    assert [o.module for o in ops] == ["jit_fused", "jit_enc"]


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE) as f:
        return Trace.from_json(json.load(f))


def test_recorded_trace_reduces(recorded):
    t = recorded
    lo, hi = t.window()
    busy, window = trace.busy_s(t), trace.window_s(t)
    assert 0 < busy < window == pytest.approx((hi - lo) / 1e9)
    # leaves leave out a while's own loop overhead, and async copies can
    # overlap; either way their sum stays near the busy time
    leaf = trace.op_seconds(t, t.ops)
    assert 0.9 * busy <= leaf <= 1.5 * busy
    assert {o.module for o in t.ops} >= {"jit_fused_window", "jit_enc"}
    assert any(o.opcode == "while" for o in t.ops)
    assert any(s.name == "bench.verdict" for s in t.spans)
    b = trace.breakdown(t)
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert sum(s for _, s in b["idle_gaps"]) <= window - busy + 1e-9
