"""Checkpoint-lifecycle telemetry (repro.obs): metrics registry, span
tracer, Chrome export, the bounded validator fault ring, BudgetPolicy on
shared registry instruments, and the 2-worker fleet trace with exactly
one ``scored`` span per (step, task)."""

import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from repro.ckpt import checkpoint as ckpt
from repro.core.suite import (ValidationConfig, ValidationSuite,
                              ValidationTask)
from repro.core.validator import (CKPT_TO_VERDICT_METRIC, AsyncValidator,
                                  ErrorRing, ValidationLedger,
                                  ValidatorWorker)
from repro.core.watcher import (CHECKPOINT_CADENCE_METRIC,
                                DISCOVERY_LAG_METRIC,
                                VALIDATION_LATENCY_METRIC, BudgetPolicy,
                                CheckpointWatcher)
from repro.core.workqueue import WorkQueue
from repro.data import corpus as synthetic_ds
from repro.models.biencoder import EncoderSpec
from repro.obs import (LIFECYCLE_STAGES, Counter, Ewma, Gauge, Histogram,
                       MetricsRegistry, SpanTracer, Telemetry, read_trace)
from repro.obs import export as obs_export

DIM = 8
VOCAB = 97


def _toy_encode(params, tokens, mask):
    emb = jnp.take(params["table"], tokens, axis=0)
    m = mask.astype(emb.dtype)[..., None]
    v = (emb * m).sum(1) / jnp.clip(m.sum(1), 1e-6)
    return v / jnp.clip(jnp.linalg.norm(v, axis=-1, keepdims=True), 1e-6)


def toy_spec():
    return EncoderSpec(
        name="toy", dim=DIM, encode_query=_toy_encode,
        encode_passage=_toy_encode,
        init=lambda rng: {"table": jax.random.normal(rng, (VOCAB, DIM))},
        q_max_len=8, p_max_len=12)


@pytest.fixture(scope="module")
def ds():
    return synthetic_ds.synthetic_retrieval_dataset(7, n_passages=40,
                                                    n_queries=8, vocab=VOCAB)


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

def test_counter_gauge_basics():
    c = Counter("c")
    c.inc()
    c.inc(3)
    assert c.value == 4
    assert c.snapshot() == {"type": "counter", "value": 4}
    g = Gauge("g")
    assert g.value is None
    g.set(2.5)
    assert g.snapshot() == {"type": "gauge", "value": 2.5}


def test_ewma_matches_canonical_rule():
    e = Ewma("e", smooth=0.5)
    assert e.value is None
    e.update(4.0)
    assert e.value == 4.0               # first sample adopted exactly
    e.update(8.0)
    assert e.value == 0.5 * 4.0 + 0.5 * 8.0
    assert e.count == 2


def test_histogram_percentiles_nearest_rank():
    h = Histogram("h")
    for v in [1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0, 8.0, 9.0, 10.0]:
        h.observe(v)
    assert h.count == 10
    assert h.mean == pytest.approx(5.5)
    assert h.percentile(50) == 5.0      # nearest-rank: ceil(0.5*10)=5th
    assert h.percentile(99) == 10.0
    assert h.vmin == 1.0 and h.vmax == 10.0
    assert Histogram("empty").percentile(50) is None


def test_histogram_reservoir_is_bounded():
    h = Histogram("h", maxlen=4)
    for v in range(100):
        h.observe(float(v))
    assert h.count == 100               # totals keep the full history
    assert h.percentile(50) == 97.0     # percentiles over the recent window


def test_registry_shares_instruments_and_rejects_type_conflicts():
    reg = MetricsRegistry()
    assert reg.counter("x") is reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")
    assert reg.get("x").value == 0
    assert reg.get("never-created") is None
    assert reg.names() == ["x"]


def test_registry_snapshot_dump_render(tmp_path):
    reg = MetricsRegistry()
    reg.counter("a.count").inc(2)
    reg.histogram("b.lat_s").observe(0.5)
    reg.ewma("c.ema").update(1.0)
    out = tmp_path / "metrics.json"
    reg.dump(str(out))
    snap = json.loads(out.read_text())
    assert snap["a.count"] == {"type": "counter", "value": 2}
    assert snap["b.lat_s"]["count"] == 1
    table = reg.render()
    for name in ("metric", "a.count", "b.lat_s", "c.ema"):
        assert name in table


# ---------------------------------------------------------------------------
# Span tracer
# ---------------------------------------------------------------------------

def test_span_nesting_parent_child(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    tr = SpanTracer(path, process="p0", attrs={"worker_id": "w0"})
    with tr.span("scored", step=3, task="default") as outer:
        with tr.span("encoded", role="query") as inner:
            tr.event("published", step=3)
        tr.record("staged", time.monotonic() - 0.25, 0.25, n_batches=4)
    tr.flush()
    recs = {r["name"]: r for r in read_trace(path)}
    assert recs["scored"]["parent"] is None
    assert recs["encoded"]["parent"] == recs["scored"]["id"]
    assert recs["staged"]["parent"] == recs["scored"]["id"]
    assert recs["published"]["parent"] == recs["encoded"]["id"]
    assert recs["published"]["kind"] == "event"
    # spans carry monotonic intervals and flat attrs (defaults included)
    assert recs["scored"]["dur"] >= recs["encoded"]["dur"]
    assert all(r["worker_id"] == "w0" and r["process"] == "p0"
               for r in recs.values())
    assert recs["scored"]["step"] == 3
    assert outer.id != inner.id


def test_span_records_exception_and_propagates(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    tr = SpanTracer(path)
    with pytest.raises(ValueError):
        with tr.span("scored", step=1):
            raise ValueError("boom")
    tr.flush()
    (rec,) = read_trace(path)
    assert "ValueError" in rec["error"]


def test_tracer_buffers_until_flush(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    tr = SpanTracer(path, flush_every=1000)
    tr.event("produced", step=1)
    assert not os.path.exists(path)     # buffered, no I/O yet
    tr.flush()
    assert len(read_trace(path)) == 1
    tr.flush()                          # empty flush is a no-op
    assert len(read_trace(path)) == 1


def test_threads_do_not_adopt_each_others_spans(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    tr = SpanTracer(path)
    ready = threading.Event()
    release = threading.Event()

    def other():
        ready.wait(5)
        tr.event("discovered", step=9)
        release.set()

    t = threading.Thread(target=other)
    t.start()
    with tr.span("scored", step=1):
        ready.set()
        release.wait(5)
    t.join()
    tr.flush()
    recs = {r["name"]: r for r in read_trace(path)}
    # the event fired while `scored` was open on ANOTHER thread: no parent
    assert recs["discovered"]["parent"] is None


def test_disabled_telemetry_is_noop(tmp_path):
    tel = Telemetry(None)
    assert tel.tracer is None
    with tel.span("scored", step=1):    # no tracer: no file
        pass
    with tel.span("scored", step=2):
        pass
    tel.event("produced", step=1)
    tel.record("staged", 0.0, 1.0)
    tel.flush()
    assert os.listdir(tmp_path) == []   # wrote nothing anywhere
    tel.metrics.counter("still.works").inc()
    assert tel.metrics.get("still.works").value == 1


class _Annotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs enter/exit."""

    log: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))


def test_span_enters_trace_annotation_and_nests(tmp_path, monkeypatch):
    import repro.obs as obs
    monkeypatch.setattr(obs, "_annotation_cls", _Annotation)
    _Annotation.log = []
    tel = Telemetry(str(tmp_path / "trace.jsonl"))
    with tel.span("finalize") as outer:
        with tel.span("finalize.fetch") as inner:
            pass
    assert _Annotation.log == [
        ("enter", "repro.finalize"), ("enter", "repro.finalize.fetch"),
        ("exit", "repro.finalize.fetch"), ("exit", "repro.finalize")]
    tel.flush()
    recs = {r["name"]: r for r in read_trace(str(tmp_path / "trace.jsonl"))}
    assert recs["finalize.fetch"]["parent"] == recs["finalize"]["id"]
    assert outer.dur >= inner.dur > 0
    assert [n for n, _, _ in tel.recent_spans] == ["finalize.fetch",
                                                   "finalize"]
    # a metrics-only handle annotates too, and keeps its recent spans
    _Annotation.log = []
    quiet = Telemetry(None)
    with quiet.span("metrics"):
        pass
    assert _Annotation.log == [("enter", "repro.metrics"),
                               ("exit", "repro.metrics")]
    assert [n for n, _, _ in quiet.recent_spans] == ["metrics"]


def test_span_is_seen_by_the_profiler(tmp_path):
    import glob

    from jax.profiler import ProfileData
    tel = Telemetry(None)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tel.span("drain"):
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                         "*.xplane.pb"))[0]
    names = {e.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events}
    assert "repro.drain" in names


def test_gc_hook_times_collections_while_telemetry_lives():
    import gc

    import repro.obs as obs
    gc.collect()
    tel = Telemetry(None)
    assert obs._gc_hook in gc.callbacks
    gc.collect()
    hist = tel.metrics.get(obs.GC_PAUSE_METRIC)
    assert hist is not None and hist.count >= 1
    assert any(n == "gc" for n, _, _ in tel.recent_spans)
    assert tel in obs.live_telemetry()
    # no hook before the first handle nor after the last (a fresh process:
    # other tests here may keep handles alive)
    import subprocess
    import sys
    code = ("import gc, repro.obs as o\n"
            "assert o._gc_hook not in gc.callbacks\n"
            "t = o.Telemetry(None)\n"
            "assert o._gc_hook in gc.callbacks\n"
            "del t\n"
            "gc.collect()\n"
            "assert o._gc_hook not in gc.callbacks and not o.live_telemetry()\n")
    src = os.path.dirname(os.path.dirname(os.path.abspath(obs.__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   p for p in (src, os.environ.get("PYTHONPATH")) if p))
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   timeout=120)


def test_mark_since_cross_stage_latency():
    tel = Telemetry(None)
    assert tel.since("discovered", 5) is None      # never marked
    tel.mark("discovered", 5)
    lag = tel.since("discovered", 5)
    assert lag is not None and lag >= 0.0
    assert tel.since("discovered", 5, pop=True) is not None
    assert tel.since("discovered", 5) is None      # popped


# ---------------------------------------------------------------------------
# Chrome export + stage summaries
# ---------------------------------------------------------------------------

def test_chrome_export_round_trip(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    tr = SpanTracer(path, process="worker-0")
    with tr.span("scored", step=1, task="default"):
        tr.event("published", step=1)
    tr.flush()
    out = str(tmp_path / "chrome.json")
    doc = obs_export.write_chrome([path], out)
    loaded = json.loads(open(out).read())
    assert loaded == doc
    phases = [e["ph"] for e in loaded["traceEvents"]]
    assert "M" in phases and "X" in phases and "i" in phases
    meta = next(e for e in loaded["traceEvents"] if e["ph"] == "M")
    assert meta["args"]["name"] == "worker-0"
    span = next(e for e in loaded["traceEvents"] if e["ph"] == "X")
    assert span["name"] == "scored"
    assert span["cat"] == "lifecycle"
    assert span["dur"] >= 1.0                      # microseconds, floored
    assert span["args"]["step"] == 1
    assert span["args"]["task"] == "default"
    assert "span_id" in span["args"]
    inst = next(e for e in loaded["traceEvents"] if e["ph"] == "i")
    assert inst["name"] == "published" and inst["s"] == "t"
    assert inst["args"]["parent_id"] == span["args"]["span_id"]


def test_stage_summary_self_time_excludes_children():
    recs = [
        {"kind": "span", "name": "scored", "id": 1, "parent": None,
         "t0": 0.0, "dur": 1.0, "pid": 1, "_file": "f"},
        {"kind": "span", "name": "encoded", "id": 2, "parent": 1,
         "t0": 0.1, "dur": 0.4, "pid": 1, "_file": "f"},
        {"kind": "event", "name": "published", "id": 3, "parent": None,
         "t": 0.0, "pid": 1, "_file": "f"},
    ]
    summary = obs_export.stage_summary(recs)
    assert summary["scored"]["total_s"] == pytest.approx(1.0)
    assert summary["scored"]["self_s"] == pytest.approx(0.6)
    assert summary["encoded"]["self_s"] == pytest.approx(0.4)
    assert summary["published"]["count"] == 1
    assert summary["published"]["total_s"] == 0.0
    table = obs_export.breakdown_table(recs)
    lines = table.splitlines()
    # lifecycle order: published (event) before encoded before scored
    order = [ln.split()[0] for ln in lines[2:]]
    assert order == ["published", "encoded", "scored"]


def test_export_cli_merges_files(tmp_path, capsys):
    p0, p1 = str(tmp_path / "w0.jsonl"), str(tmp_path / "w1.jsonl")
    for i, p in enumerate((p0, p1)):
        tr = SpanTracer(p, process=f"worker-{i}")
        with tr.span("scored", step=i):
            pass
        tr.flush()
    out = str(tmp_path / "chrome.json")
    assert obs_export.main([p0, p1, "--chrome", out, "--summary"]) == 0
    printed = capsys.readouterr().out
    assert "scored" in printed
    doc = json.loads(open(out).read())
    names = {e["args"]["name"] for e in doc["traceEvents"]
             if e["ph"] == "M"}
    assert names == {"worker-0", "worker-1"}


# ---------------------------------------------------------------------------
# ErrorRing (bounded validator fault list)
# ---------------------------------------------------------------------------

def test_error_ring_caps_and_counts_drops():
    ring = ErrorRing(maxlen=3)
    for i in range(5):
        ring.append((i, f"e{i}"))
    assert len(ring) == 3
    assert ring.dropped == 2
    assert [e[0] for e in ring] == [2, 3, 4]       # newest kept
    assert ring[-1] == (4, "e4")
    assert ring[:2] == [(2, "e2"), (3, "e3")]
    assert bool(ring)
    c = Counter("validator.errors_dropped")
    ring.bind_counter(c)
    assert c.value == 2                             # pre-bind drops counted
    ring.append((5, "e5"))
    assert c.value == 3
    ring.clear()
    assert not ring and len(ring) == 0


def test_worker_error_ring_is_bounded(ds, tmp_path):
    vcfg = ValidationConfig(metrics=("MRR@10",), batch_size=8)
    suite = ValidationSuite(toy_spec(), [
        ValidationTask("default", ds.corpus, ds.queries, ds.qrels)], vcfg)
    tel = Telemetry(None)
    w = ValidatorWorker(str(tmp_path), suite, telemetry=tel, max_errors=2)
    for i in range(4):
        w.errors.append((i, "x"))
    assert len(w.errors) == 2
    assert tel.metrics.get("validator.errors_dropped").value == 2


# ---------------------------------------------------------------------------
# BudgetPolicy on shared registry instruments
# ---------------------------------------------------------------------------

def test_budget_policy_feeds_shared_registry():
    reg = MetricsRegistry()
    pol = BudgetPolicy(smooth=0.5)
    pol.bind_metrics(reg)
    pol.observe_latency(4.0)
    pol.observe_cadence(1.0)
    lat = reg.get(VALIDATION_LATENCY_METRIC)
    cad = reg.get(CHECKPOINT_CADENCE_METRIC)
    assert lat.value == 4.0 and cad.value == 1.0
    pol.observe_latency(8.0)
    assert lat.value == 0.5 * 4.0 + 0.5 * 8.0      # policy's own smooth
    # an external reader sees exactly what the policy decides from
    assert pol.select([10])                         # stride floors at >=1


def test_budget_policy_rebind_carries_state_over():
    pol = BudgetPolicy(smooth=0.5)
    pol.observe_latency(4.0)                        # on the private registry
    reg = MetricsRegistry()
    pol.bind_metrics(reg)
    assert reg.get(VALIDATION_LATENCY_METRIC).value == 4.0
    assert reg.get(VALIDATION_LATENCY_METRIC).count == 1


def test_watcher_binds_policy_and_observes_discovery(tmp_path):
    root = str(tmp_path / "ckpts")
    tel = Telemetry(str(tmp_path / "trace.jsonl"))
    pol = BudgetPolicy()
    watcher = CheckpointWatcher(root, policy=pol, telemetry=tel)
    # the policy's instruments live on the shared registry now
    assert tel.metrics.get(CHECKPOINT_CADENCE_METRIC) is not None
    ckpt.save(root, 10, {"params": {"x": jnp.zeros(2)}})
    assert watcher.poll() == [10]
    tel.flush()
    recs = [r for r in read_trace(str(tmp_path / "trace.jsonl"))
            if r["name"] == "discovered"]
    assert len(recs) == 1 and recs[0]["step"] == 10
    lag_hist = tel.metrics.get(DISCOVERY_LAG_METRIC)
    assert lag_hist is not None and lag_hist.count == 1
    assert tel.since("discovered", 10) is not None  # verdict mark is set


# ---------------------------------------------------------------------------
# Solo validator end-to-end: spans + checkpoint-to-verdict latency
# ---------------------------------------------------------------------------

def test_solo_validator_traces_full_lifecycle(ds, tmp_path):
    spec = toy_spec()
    root = str(tmp_path / "ckpts")
    params = spec.init(jax.random.PRNGKey(0))
    ckpt.save(root, 5, {"params": params})
    trace = str(tmp_path / "trace.jsonl")
    tel = Telemetry(trace, attrs={"worker_id": "solo"})
    vcfg = ValidationConfig(metrics=("MRR@10",), batch_size=8)
    suite = ValidationSuite(spec, [
        ValidationTask("default", ds.corpus, ds.queries, ds.qrels)], vcfg)
    av = AsyncValidator(root, suite, telemetry=tel,
                        ledger_path=str(tmp_path / "ledger.jsonl"))
    assert av.validate_pending() == 1
    tel.flush()
    names = {r["name"] for r in read_trace(trace)}
    assert {"discovered", "store_build", "staged", "encoded", "scored",
            "recorded"} <= names
    hist = tel.metrics.get(CKPT_TO_VERDICT_METRIC)
    assert hist is not None and hist.count == 1
    assert hist.percentile(50) is not None
    # the suite config got the handle threaded through automatically
    assert vcfg.telemetry is tel


def test_disabled_telemetry_identical_ledger(ds, tmp_path):
    """Telemetry on/off writes identical ledger rows (modulo the wall-time
    timing fields, which vary run to run regardless) and telemetry-off
    writes no trace file — the observe-never-participate acceptance
    gate."""
    spec = toy_spec()
    params = spec.init(jax.random.PRNGKey(0))

    def run(workdir, tel):
        root = os.path.join(workdir, "ckpts")
        ckpt.save(root, 5, {"params": params})
        vcfg = ValidationConfig(metrics=("MRR@10",), batch_size=8)
        suite = ValidationSuite(spec, [
            ValidationTask("default", ds.corpus, ds.queries, ds.qrels)],
            vcfg)
        led = os.path.join(workdir, "ledger.jsonl")
        av = AsyncValidator(root, suite, telemetry=tel, ledger_path=led)
        assert av.validate_pending() == 1
        rows = [json.loads(ln) for ln in open(led)]
        for row in rows:
            row.pop("timings", None)
        return rows

    off = run(str(tmp_path / "off"), None)
    on_dir = str(tmp_path / "on")
    on = run(on_dir, Telemetry(os.path.join(on_dir, "trace.jsonl")))
    assert off == on
    assert not any(f.endswith("trace.jsonl")
                   for f in os.listdir(str(tmp_path / "off")))


# ---------------------------------------------------------------------------
# 2-worker fleet: one `scored` span per (step, task), attributed
# ---------------------------------------------------------------------------

def test_two_worker_fleet_trace_attribution(ds, tmp_path):
    spec = toy_spec()
    root = str(tmp_path / "ckpts")
    for step in (1, 2):
        ckpt.save(root, step,
                  {"params": spec.init(jax.random.PRNGKey(step))})
    ledger_path = str(tmp_path / "ledger.jsonl")

    def make_worker(wid):
        trace = str(tmp_path / f"{wid}.jsonl")
        tel = Telemetry(trace, process=wid, attrs={"worker_id": wid})
        vcfg = ValidationConfig(metrics=("MRR@10",), batch_size=8,
                                telemetry=tel)
        suite = ValidationSuite(spec, [
            ValidationTask("a", ds.corpus, ds.queries, ds.qrels),
            ValidationTask("b", ds.corpus, ds.queries, ds.qrels)], vcfg)
        queue = WorkQueue(ledger_path, wid, lease_ttl=16,
                          capabilities={"mesh_size": jax.device_count()},
                          telemetry=tel)
        worker = ValidatorWorker(
            root, suite,
            ledger=ValidationLedger(ledger_path,
                                    expected_tasks=suite.task_names,
                                    telemetry=tel),
            queue=queue, worker_id=wid, telemetry=tel)
        return worker, suite, tel, trace

    w0, suite0, tel0, trace0 = make_worker("w0")
    w1, _, tel1, trace1 = make_worker("w1")
    for step in (1, 2):
        w0.queue.publish(suite0.plan_units(step))
    # alternate claim rounds until the 4-unit backlog drains
    for _ in range(16):
        if len(w0.completed) + len(w1.completed) == 4:
            break
        w0.run_once()
        w1.run_once()
    assert len(w0.completed) + len(w1.completed) == 4
    assert w0.completed and w1.completed            # both did real work
    w0.queue.refresh()      # mirror the tail events into the counters
    w1.queue.refresh()
    tel0.flush()
    tel1.flush()

    records = obs_export.load_traces([trace0, trace1])
    scored = [r for r in records if r["name"] == "scored"
              and r["kind"] == "span"]
    # exactly one scored span per (step, task) across the whole fleet
    assert sorted((r["step"], r["task"]) for r in scored) == \
        [(1, "a"), (1, "b"), (2, "a"), (2, "b")]
    # worker attribution matches the ledger rows' worker_id stamps
    rows = ValidationLedger(ledger_path).rows()
    by_unit = {(row["step"], row["task"]): row["worker_id"]
               for row in rows if "task" in row and "worker_id" in row}
    for r in scored:
        assert r["worker_id"] == by_unit[(r["step"], r["task"])]
        assert r["process"] == r["worker_id"]
    # the fleet protocol stages show up too, on the right workers
    names = {r["name"] for r in records}
    assert {"published", "claimed", "store_build", "scored",
            "recorded"} <= names
    claimed = [r for r in records if r["name"] == "claimed"]
    assert {(r["step"], r["task"]) for r in claimed} == \
        {(1, "a"), (1, "b"), (2, "a"), (2, "b")}
    for r in claimed:                   # a worker only logs claims it WON
        assert r["worker_id"] == by_unit[(r["step"], r["task"])]
    # mirrored queue counters: every handle folds the whole shared ledger,
    # so each worker's registry shows the GLOBAL publish/completion counts
    for tel in (tel0, tel1):
        assert tel.metrics.get("fleet.publish").value == 4
        assert tel.metrics.get("fleet.complete").value == 4
    claims = sum(t.metrics.get("fleet.claim").value for t in (tel0, tel1))
    assert claims >= 4
    # ckpt-to-verdict latency observed on every completed unit's worker
    total_verdicts = sum(
        t.metrics.get(CKPT_TO_VERDICT_METRIC).count
        for t in (tel0, tel1) if t.metrics.get(CKPT_TO_VERDICT_METRIC))
    assert total_verdicts == 4
    # merged Chrome export covers both worker tracks
    out = str(tmp_path / "fleet.json")
    doc = obs_export.write_chrome([trace0, trace1], out)
    tracks = {e["args"]["name"] for e in doc["traceEvents"]
              if e["ph"] == "M"}
    assert tracks == {"w0", "w1"}


def test_lifecycle_vocabulary_is_stable():
    assert LIFECYCLE_STAGES == (
        "produced", "snapshotted", "discovered", "published", "claimed",
        "loaded", "store_build", "placed", "encoded", "staged", "dispatch",
        "drain", "finalize", "finalize.fetch", "finalize.build", "scored",
        "metrics", "recorded", "selected", "promoted", "served")


def test_obs_report_prints_verdict_percentiles(capsys):
    import argparse

    from repro.core import cli
    tel = Telemetry(None)
    for v in (0.1, 0.2, 0.3):
        tel.metrics.histogram(CKPT_TO_VERDICT_METRIC).observe(v)
    args = argparse.Namespace(obs_report=True, obs_metrics=None)
    cli._obs_finish(args, tel)
    out = capsys.readouterr().out
    assert "checkpoint-to-verdict" in out
    assert "p50=" in out and "p99=" in out
    assert CKPT_TO_VERDICT_METRIC in out
