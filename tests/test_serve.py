"""Serving tier: hot-swap races, promotion failures, admission, events.

The zero-downtime claims as tests: a query racing a promotion never sees
a torn index (its answer is exactly ONE checkpoint's answer — the one it
attributes), a failed build leaves the old index serving, stacked select
events coalesce to the newest winner, and every swap is a replayable
fsync'd event carrying checkpoint/engine/score_dtype provenance.
"""

import os
import threading
import time

import jax
import numpy as np
import pytest

from benchmarks.common import toy_spec, train_toy_dr
from repro.ckpt import checkpoint as ckpt
from repro.control.events import (ACTUATION_KINDS, DECISION_KINDS,
                                  ControlEventLog)
from repro.core.suite import ValidationConfig
from repro.data import corpus as corpus_lib
from repro.serve import (AdmissionController, IndexBuilder, Promoter,
                         QueryService, ServeConfig, ServeOverloaded,
                         replay_swaps)

K = 8
SERVE_CFG = ServeConfig(scoring=ValidationConfig(k=K, batch_size=32))


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    """Corpus + 3 committed checkpoints + the bitwise-expected answers of
    every (step, query) pair, computed offline — the oracle the torn-index
    test checks every racing response against."""
    base = tmp_path_factory.mktemp("serve")
    ds = corpus_lib.synthetic_retrieval_dataset(0, n_passages=180,
                                                n_queries=10)
    spec = toy_spec(ds.vocab)
    _, snaps = train_toy_dr(ds, spec, steps=60, snapshot_every=20)
    ckdir = str(base / "ckpts")
    for step, params in snaps:
        ckpt.save(ckdir, step, {"params": params})
    builder = IndexBuilder(spec, ds.corpus, SERVE_CFG)
    expected = {}
    for step, params in snaps:
        index = builder.build(params, step)
        svc = QueryService(spec, k=K, max_batch=4)
        svc.install(index)
        for r in svc.answer([(q, ds.queries[q]) for q in ds.queries]):
            expected[(step, r.qid)] = (r.doc_ids, r.scores)
    steps = [s for s, _ in snaps]
    return {"base": base, "ds": ds, "spec": spec, "ckdir": ckdir,
            "steps": steps, "expected": expected}


def _stack(world, tmp, *, target_fn=None, events=None, **prom_kw):
    ds, spec = world["ds"], world["spec"]
    builder = IndexBuilder(spec, ds.corpus, SERVE_CFG)
    service = QueryService(spec, k=K, max_batch=4, flush_ms=2.0)
    promoter = Promoter(builder, service, world["ckdir"],
                        target_fn=target_fn, control_events=events,
                        log=str(tmp / "serve_events.jsonl"), **prom_kw)
    return builder, service, promoter


# ---------------------------------------------------------------------------
# Hot-swap races
# ---------------------------------------------------------------------------

def test_no_torn_index_under_concurrent_promotions(world, tmp_path):
    """Queries hammered across repeated promotions: every response must
    equal the offline answer of exactly the step it attributes — a torn
    read (old corpus embeddings + new params, or a half-installed
    pointer) would produce an answer matching NO single checkpoint."""
    ds = world["ds"]
    target = {"step": world["steps"][0]}
    _, service, promoter = _stack(world, tmp_path,
                                  target_fn=lambda: target["step"])
    assert promoter.poll_once()
    service.start()
    stop = threading.Event()
    failures = []
    served_steps = set()

    def client(i):
        qids = list(ds.queries)
        j = 0
        while not stop.is_set():
            qid = qids[(i + j) % len(qids)]
            j += 1
            try:
                r = service.submit(qid, ds.queries[qid], timeout=30)
            except BaseException as e:     # noqa: BLE001 — a dropped query
                failures.append(("exc", qid, repr(e)))    # IS a blackout
                return
            served_steps.add(r.step)
            want = world["expected"][(r.step, r.qid)]
            if (r.doc_ids, r.scores) != want:
                failures.append((r.step, r.qid))

    threads = [threading.Thread(target=client, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    try:
        # >= 3 promotions under sustained load, cycling through checkpoints
        flips = world["steps"][1:] + world["steps"][:1]
        for s in flips:
            time.sleep(0.05)
            target["step"] = s
            assert promoter.poll_once(), f"promotion to {s} failed"
    finally:
        stop.set()
        for t in threads:
            t.join()
        service.stop()
    assert not failures, f"torn responses: {failures[:5]}"
    assert len(promoter.swaps) >= 3
    assert service.served > 0
    assert served_steps <= set(world["steps"])


def test_failed_build_leaves_old_index_serving(world, tmp_path):
    """Two-phase discipline: a promotion dying mid-build must not touch
    the live pointer, and must leave an auditable swap_failed event."""
    ds = world["ds"]
    s1, s2 = world["steps"][:2]
    target = {"step": s1}

    def hook(step):
        if step == s2:
            raise RuntimeError("mid-build device loss")

    builder, service, promoter = _stack(world, tmp_path,
                                        target_fn=lambda: target["step"],
                                        build_hook=hook)
    assert promoter.poll_once() and service.live_step() == s1
    target["step"] = s2
    assert not promoter.poll_once()
    assert service.live_step() == s1          # old index still serving
    r = service.answer([(next(iter(ds.queries)),
                         ds.queries[next(iter(ds.queries))])])[0]
    assert r.step == s1
    assert (r.doc_ids, r.scores) == world["expected"][(s1, r.qid)]
    (step, err), = promoter.failures
    assert step == s2 and "mid-build" in str(err)
    fail_ev = [e for e in promoter.log.events() if e.kind == "swap_failed"]
    assert len(fail_ev) == 1 and fail_ev[0].step == s2
    assert fail_ev[0].payload["live_step"] == s1
    # the failure is transient: clearing it lets the next poll promote
    promoter.build_hook = None
    assert promoter.poll_once() and service.live_step() == s2


def test_verify_rejects_nonfinite_index(world, tmp_path):
    """Phase-two verify catches a checkpoint that encodes garbage (NaN
    embeddings) BEFORE the flip."""
    ds, spec = world["ds"], world["spec"]
    s1, s2 = world["steps"][:2]
    target = {"step": s1}
    builder, service, promoter = _stack(world, tmp_path,
                                        target_fn=lambda: target["step"])
    assert promoter.poll_once() and service.live_step() == s1
    poisoned = jax.tree_util.tree_map(lambda x: x * np.nan,
                                      ckpt.restore(world["ckdir"], s2)[0])
    promoter.params_extractor = lambda state: poisoned["params"]
    target["step"] = s2
    assert not promoter.poll_once()
    assert service.live_step() == s1
    assert "non-finite" in str(promoter.failures[-1][1])


def test_stacked_selects_coalesce(world, tmp_path):
    """N select events between polls collapse into ONE swap to the newest
    winner — intermediate checkpoints are never built."""
    s1, s2, s3 = world["steps"][:3]
    events = str(tmp_path / "control.jsonl")
    log = ControlEventLog(events)
    builder, service, promoter = _stack(world, tmp_path, events=events)
    log.emit("select", s1, best_step=s1)
    assert promoter.poll_once() and service.live_step() == s1
    builds_before = builder.index_builds
    log.emit("select", s2, best_step=s2)
    log.emit("select", s3, best_step=s3)
    assert promoter.poll_once()
    assert service.live_step() == s3
    assert builder.index_builds == builds_before + 1   # s2 never built
    assert not promoter.poll_once()                    # idempotent at rest


def test_select_during_inflight_swap_coalesces(world, tmp_path):
    """A select landing DURING a build doesn't deadlock and doesn't get
    lost: the in-flight swap completes, the next poll promotes the newer
    winner."""
    s1, s2, s3 = world["steps"][:3]
    events = str(tmp_path / "control.jsonl")
    log = ControlEventLog(events)

    def hook(step):
        if step == s2:                 # mid-build of s2, s3 gets selected
            log.emit("select", s3, best_step=s3)

    _, service, promoter = _stack(world, tmp_path, events=events,
                                  build_hook=hook)
    log.emit("select", s1, best_step=s1)
    assert promoter.poll_once()
    log.emit("select", s2, best_step=s2)
    assert promoter.poll_once() and service.live_step() == s2
    assert promoter.poll_once() and service.live_step() == s3
    assert [w for _, w in promoter.swaps] == [s1, s2, s3]


def test_uncommitted_selection_waits(world, tmp_path):
    """A selected-but-not-yet-durable checkpoint is not promoted (no
    failure either) — the promoter waits for the two-phase commit."""
    target = {"step": 999}
    _, service, promoter = _stack(world, tmp_path,
                                  target_fn=lambda: target["step"])
    assert not promoter.poll_once()
    assert promoter.failures == [] and service.live_step() is None


# ---------------------------------------------------------------------------
# Admission control
# ---------------------------------------------------------------------------

def test_admission_rejects_past_bound(world, tmp_path):
    """Beyond max_pending in-flight requests, submits fail fast with
    ServeOverloaded instead of queueing unboundedly; capacity frees once
    the batcher drains."""
    ds, spec = world["ds"], world["spec"]
    builder = IndexBuilder(spec, ds.corpus, SERVE_CFG)
    adm = AdmissionController(max_pending=1)
    service = QueryService(spec, k=K, max_batch=4, flush_ms=2.0,
                           admission=adm)
    params = ckpt.restore(world["ckdir"], world["steps"][0])[0]["params"]
    service.install(builder.build(params, world["steps"][0]))
    qid = next(iter(ds.queries))
    # service NOT started: the first submit occupies the one slot forever
    blocker = threading.Thread(
        target=lambda: pytest.raises(TimeoutError, service.submit, qid,
                                     ds.queries[qid], timeout=0.7))
    blocker.start()
    time.sleep(0.1)
    with pytest.raises(ServeOverloaded):
        service.submit(qid, ds.queries[qid], timeout=1.0)
    blocker.join()
    assert adm.rejected == 1 and adm.peak == 1
    # slot released after the blocked request timed out
    service.start()
    try:
        r = service.submit(qid, ds.queries[qid], timeout=30)
        assert r.step == world["steps"][0]
    finally:
        service.stop()
    assert adm.pending == 0


def test_admission_controller_counters():
    adm = AdmissionController(max_pending=2)
    assert adm.try_acquire() and adm.try_acquire()
    assert not adm.try_acquire()
    adm.release()
    assert adm.try_acquire()
    assert adm.admitted == 3 and adm.rejected == 1 and adm.peak == 2
    with pytest.raises(ValueError):
        AdmissionController(max_pending=0)


# ---------------------------------------------------------------------------
# Swap events: provenance + replay
# ---------------------------------------------------------------------------

def test_swap_events_carry_provenance_and_replay(world, tmp_path):
    """Every swap is an actuation event with full provenance, and the
    live-step timeline is re-derivable offline from the log alone."""
    ds = world["ds"]
    s1, s2 = world["steps"][:2]
    target = {"step": s1}
    _, service, promoter = _stack(world, tmp_path,
                                  target_fn=lambda: target["step"])
    assert promoter.poll_once()
    target["step"] = s2
    assert promoter.poll_once()
    evs = [e for e in promoter.log.events() if e.kind == "swap"]
    assert [e.step for e in evs] == [s1, s2]
    for e in evs:
        assert e.payload["engine"] == "serve"
        assert e.payload["score_dtype"] == "f32"
        assert e.payload["n_docs"] == len(ds.corpus)
        assert e.payload["build_s"] >= 0
    assert evs[0].payload["prev_step"] == -1
    assert evs[1].payload["prev_step"] == s1
    # offline replay reconstructs the live timeline from the fsync'd file
    timeline = replay_swaps(str(tmp_path / "serve_events.jsonl"))
    assert [(t["prev_step"], t["step"]) for t in timeline] == \
        [(-1, s1), (s1, s2)]
    # swaps are actuations: excluded from decision replay comparison
    assert {"swap", "swap_failed"} <= ACTUATION_KINDS
    assert not ({"swap", "swap_failed"} & DECISION_KINDS)
    assert promoter.log.decisions() == []


def test_background_promoter_loop(world, tmp_path):
    """The threaded promoter: select events flow to live swaps without any
    explicit polling by the caller."""
    s1, s2 = world["steps"][:2]
    events = str(tmp_path / "control.jsonl")
    log = ControlEventLog(events)
    _, service, promoter = _stack(world, tmp_path, events=events,
                                  poll_interval_s=0.02)
    promoter.start()
    try:
        log.emit("select", s1, best_step=s1)
        deadline = time.time() + 30
        while service.live_step() != s1 and time.time() < deadline:
            time.sleep(0.02)
        assert service.live_step() == s1
        log.emit("select", s2, best_step=s2)
        while service.live_step() != s2 and time.time() < deadline:
            time.sleep(0.02)
        assert service.live_step() == s2
    finally:
        promoter.stop()


# ---------------------------------------------------------------------------
# Index build economics + GC contract
# ---------------------------------------------------------------------------

def test_token_store_built_once_across_builds(world):
    """The corpus TokenStore (the checkpoint-independent half of an index
    build) is padded once at construction and shared by every promoted
    checkpoint — only the encode pass reruns."""
    ds, spec = world["ds"], world["spec"]
    builder = IndexBuilder(spec, ds.corpus, SERVE_CFG)
    store = builder.store
    p1 = ckpt.restore(world["ckdir"], world["steps"][0])[0]["params"]
    p2 = ckpt.restore(world["ckdir"], world["steps"][1])[0]["params"]
    i1, i2 = builder.build(p1, 1), builder.build(p2, 2)
    assert builder.store is store and builder.index_builds == 2
    assert i1.doc_ids is builder.doc_ids and i2.doc_ids is builder.doc_ids
    assert i1.n_docs == i2.n_docs == len(ds.corpus)


def test_promoter_protect_set(world, tmp_path):
    """The GC contract: live + in-flight-promotion steps are protected;
    nothing is protected before the first install."""
    s1, s2 = world["steps"][:2]
    target = {"step": s1}
    seen = {}

    def hook(step):
        # snapshot DURING the build: both old-live and promoting protected
        seen["mid"] = promoter.protect_set()

    _, service, promoter = _stack(world, tmp_path,
                                  target_fn=lambda: target["step"],
                                  build_hook=hook)
    assert promoter.protect_set() == set()
    assert promoter.poll_once()
    assert promoter.protect_set() == {s1}
    target["step"] = s2
    assert promoter.poll_once()
    assert seen["mid"] == {s1, s2}
    assert promoter.protect_set() == {s2}


# ---------------------------------------------------------------------------
# launch/serve.py: retrieval entry point + LM-demo compatibility
# ---------------------------------------------------------------------------

def toy_encoder_from_cli(args):
    """--encoder hook for the launch CLI test."""
    return toy_spec(503)


def test_launch_serve_is_retrieval_cli(world, tmp_path, capsys):
    """The rebuilt launch/serve.py serves retrieval queries end to end:
    promote latest committed checkpoint, answer the query file, report
    latency percentiles."""
    from repro.launch.serve import main
    ds = world["ds"]
    cdir = tmp_path / "corpus"
    cdir.mkdir()
    corpus_lib.write_jsonl(str(cdir / "c.jsonl"), ds.corpus)
    qfile = tmp_path / "q.jsonl"
    corpus_lib.write_jsonl(str(qfile), ds.queries)
    rc = main(["--candidate_dir", str(cdir), "--query_file", str(qfile),
               "--ckpts_dir", world["ckdir"], "--k", "5",
               "--max_batch", "4",
               "--encoder", "tests.test_serve:toy_encoder_from_cli"])
    assert rc == 0
    out = capsys.readouterr().out
    assert f"step={world['steps'][-1]}" in out and "p99=" in out
    assert os.path.exists(os.path.join(world["ckdir"],
                                       "serve_events.jsonl"))


def test_launch_serve_cli_docs_are_retrieval():
    """The stale LM prefill/decode surface is gone from launch/serve —
    and the demo survives, importable from launch/lm_demo."""
    import repro.launch.lm_demo as lm_demo
    import repro.launch.serve as serve
    assert "retrieval" in (serve.__doc__ or "").lower()
    assert "--arch qwen2" not in (serve.__doc__ or "")
    assert serve.serve_batch is lm_demo.serve_batch   # compat re-export
    assert callable(lm_demo.serve_batch)


def test_core_cli_serve_one_shot(world, tmp_path, capsys):
    """`asyncval --serve`: validation and serving in one process — the
    control plane picks the best checkpoint, the promoter promotes exactly
    that pick, and the one-shot serve pass answers the validation query
    file attributing it.  Swap provenance lands in <run>_serve.jsonl."""
    from repro.core.cli import main
    from repro.serve import replay_swaps
    ds = world["ds"]
    cdir = tmp_path / "corpus"
    cdir.mkdir()
    corpus_lib.write_jsonl(str(cdir / "c.jsonl"), ds.corpus)
    qfile = tmp_path / "q.jsonl"
    corpus_lib.write_jsonl(str(qfile), ds.queries)
    qrels = tmp_path / "qrels.txt"
    with open(qrels, "w") as f:
        for qid, docs in ds.qrels.items():
            for did, g in docs.items():
                f.write(f"{qid} 0 {did} {g}\n")
    outdir = tmp_path / "out"
    rc = main(["--query_file", str(qfile),
               "--candidate_dir", str(cdir),
               "--ckpts_dir", world["ckdir"],
               "--qrel_file", str(qrels),
               "--metrics", "MRR@10",
               "--keep_top_k", "3",      # control plane drives promotion
               "--run_name", "t", "--output_dir", str(outdir),
               "--serve", "--serve_k", "5", "--serve_batch", "4",
               "--encoder", "tests.test_serve:toy_encoder_from_cli"])
    assert rc == 0
    out = capsys.readouterr().out
    swaps = replay_swaps(str(outdir / "t_serve.jsonl"))
    assert len(swaps) == 1               # one-shot: exactly one promotion
    best = swaps[0]["step"]
    assert best in world["steps"]
    assert f"[serve] answered {len(ds.queries)} queries" in out
    assert f"step={best}" in out         # responses attribute the pick
    # the promoted step is the control plane's selection: its ledger MRR
    # must equal the best MRR observed (ties resolve inside the selector)
    import json
    rows = [json.loads(l) for l in open(outdir / "t_ledger.jsonl")]
    mrr = {r["step"]: r["metrics"]["MRR@10"] for r in rows}
    assert mrr[best] == max(mrr.values())
