"""Validator fleet: the ledger work queue's claim protocol, crash-safe
lease reclaim, multi-process append atomicity, capability matching, the
fleet supervisor's control pump / GC protection, and the satellite fixes
(drain_timeout, watcher high-water cache)."""

import json
import os
import subprocess
import sys
import threading
import time

import jax.numpy as jnp
import pytest

from repro.ckpt import checkpoint as ckpt
from repro.control import ControlConfig, ControlPlane, replay_ledger
from repro.control.metricspec import flatten_rows
from repro.core.jsonl import append_jsonl_atomic, read_jsonl_tolerant
from repro.core.suite import ValidationResult
from repro.core.validator import (AsyncValidator, ValidationLedger,
                                  ValidatorWorker)
from repro.core.watcher import CheckpointWatcher
from repro.core.workqueue import (WorkQueue, WorkUnit, meets,
                                  parse_capabilities, replay)

ROOT = os.path.join(os.path.dirname(__file__), "..")
SRC = os.path.join(ROOT, "src")


# ---------------------------------------------------------------------------
# WorkUnit / capabilities
# ---------------------------------------------------------------------------

def test_workunit_make_and_requires():
    u = WorkUnit.make(7, "deep", {"mesh_size": 2, "max_depth": 100})
    assert u.key == (7, "deep")
    assert u.requires_dict == {"max_depth": 100, "mesh_size": 2}
    # frozen + hashable: usable as dict keys across queue state
    assert {u: 1}[WorkUnit.make(7, "deep",
                                {"max_depth": 100, "mesh_size": 2})] == 1


def test_meets_numeric_minima_and_equality():
    assert meets({"mesh_size": 8}, {"mesh_size": 2})
    assert not meets({"mesh_size": 1}, {"mesh_size": 2})
    assert meets({"kind": "tpu"}, {"kind": "tpu"})
    assert not meets({"kind": "cpu"}, {"kind": "tpu"})
    assert not meets({}, {"mesh_size": 1})       # undeclared -> fails
    assert meets({}, {})                         # no requirements


def test_parse_capabilities():
    assert parse_capabilities("mesh_size=8,max_depth=100") == {
        "mesh_size": 8, "max_depth": 100}
    assert parse_capabilities("f=0.5, name=tpu") == {"f": 0.5, "name": "tpu"}
    assert parse_capabilities("") == {}
    with pytest.raises(ValueError):
        parse_capabilities("oops")


# ---------------------------------------------------------------------------
# Claim protocol over the shared ledger file
# ---------------------------------------------------------------------------

def _queue(path, wid, **kw):
    kw.setdefault("lease_ttl", 4)
    return WorkQueue(str(path), wid, **kw)


def test_publish_is_idempotent(tmp_path):
    q = _queue(tmp_path / "led.jsonl", "w0")
    units = [WorkUnit.make(1, "a"), WorkUnit.make(1, "b")]
    assert q.publish(units) == units
    assert q.publish(units) == []                # re-publish collapses
    assert sorted(q.state.units) == [(1, "a"), (1, "b")]


def test_claim_conflict_has_single_winner(tmp_path):
    path = tmp_path / "led.jsonl"
    a, b = _queue(path, "A"), _queue(path, "B")
    a.publish([WorkUnit.make(1)])
    unit = a.state.units[(1, "default")].unit
    assert a.try_claim(unit)
    assert not b.try_claim(unit)                 # live lease: bid loses
    # both readers agree on the holder (deterministic fold)
    assert a.refresh().holder(1) == "A"
    assert b.refresh().holder(1) == "A"
    assert any(e["event"] == "claim_lost" for e in b.state.events)


def test_lease_expires_by_sequence_and_is_reclaimed(tmp_path):
    path = tmp_path / "led.jsonl"
    a, b = _queue(path, "A"), _queue(path, "B")
    a.publish([WorkUnit.make(5)])
    unit = a.state.units[(5, "default")].unit
    assert a.try_claim(unit)
    # A dies silently; B's ticks advance the sequence clock (ttl counts
    # records SINCE the claim touched seq 1, so 5 ticks push delta to 5 > 4)
    for _ in range(5):
        assert b.refresh().claimable({}) == []   # lease still live
        b.tick()
    assert b.refresh().claimable({}) == [unit]   # now expired
    assert b.try_claim(unit)
    assert b.state.holder(5) == "B"
    reclaims = [e for e in b.state.events if e["event"] == "reclaim"]
    assert reclaims and reclaims[0]["from"] == "A"


def test_renew_keeps_lease_alive(tmp_path):
    path = tmp_path / "led.jsonl"
    a, b = _queue(path, "A"), _queue(path, "B")
    a.publish([WorkUnit.make(5)])
    unit = a.state.units[(5, "default")].unit
    assert a.try_claim(unit)
    for _ in range(10):                          # far past the ttl
        b.tick()
        a.renew(unit)
    assert b.refresh().claimable({}) == []       # heartbeats held it
    assert b.state.holder(5) == "A"


def test_abandon_reopens_then_fails_past_budget(tmp_path):
    path = tmp_path / "led.jsonl"
    q = _queue(path, "A", max_abandons=1)
    q.publish([WorkUnit.make(2)])
    unit = q.state.units[(2, "default")].unit
    assert q.try_claim(unit)
    q.abandon(unit, error="boom")
    assert q.state.units[(2, "default")].status == "open"   # retryable
    assert q.try_claim(unit)
    q.abandon(unit, error="boom again")
    # distributed retry budget exhausted: failed, no longer claimable
    assert q.state.units[(2, "default")].status == "failed"
    assert q.refresh().claimable({}) == []


def test_result_row_completes_unit_and_capability_filter(tmp_path):
    path = tmp_path / "led.jsonl"
    q = _queue(path, "A", capabilities={"mesh_size": 1})
    q.publish([WorkUnit.make(1, "small"),
               WorkUnit.make(1, "big", {"mesh_size": 8})])
    assert [u.task for u in q.claimable()] == ["small"]     # big filtered
    # a bare result row (e.g. a non-fleet validator sharing the ledger)
    # marks the unit DONE without any claim/complete record
    append_jsonl_atomic(str(path), [{"step": 1, "task": "small",
                                     "metrics": {"MRR@10": 0.5}}])
    assert q.refresh().units[(1, "small")].status == "done"
    assert q.claimable() == []


def test_replay_rederives_online_decisions(tmp_path):
    path = tmp_path / "led.jsonl"
    a, b = _queue(path, "A"), _queue(path, "B")
    a.publish([WorkUnit.make(1), WorkUnit.make(2)])
    u1 = a.state.units[(1, "default")].unit
    u2 = a.state.units[(2, "default")].unit
    assert a.try_claim(u1) and b.try_claim(u2)
    b.complete(u2)
    for _ in range(6):
        b.tick()
    assert b.try_claim(u1)                       # reclaim from dead A
    b.complete(u1)
    offline = replay(str(path), lease_ttl=4)
    assert offline.events == b.refresh().events
    assert offline.completed_units() == [(1, "default"), (2, "default")]


# ---------------------------------------------------------------------------
# Atomic multi-process appends (satellite: subprocess stress test)
# ---------------------------------------------------------------------------

def test_append_jsonl_atomic_repairs_torn_tail(tmp_path):
    path = str(tmp_path / "led.jsonl")
    append_jsonl_atomic(path, [{"a": 1}])
    with open(path, "a") as f:
        f.write('{"torn": tr')                   # crashed writer's fragment
    append_jsonl_atomic(path, [{"b": 2}])
    rows, torn = read_jsonl_tolerant(path)
    assert torn is None                          # fragment was cut, not glued
    assert rows == [{"a": 1}, {"b": 2}]


_APPENDER = """
import sys
sys.path.insert(0, {src!r})
from repro.core.jsonl import append_jsonl_atomic
path, wid = sys.argv[1], sys.argv[2]
for i in range(150):
    append_jsonl_atomic(path, [{{"kind": "tick", "worker": wid, "i": i}}])
"""


def test_multiprocess_appends_never_tear(tmp_path):
    """Two processes hammering one ledger concurrently: every record must
    load intact and per-writer order must hold (O_APPEND atomicity)."""
    path = str(tmp_path / "led.jsonl")
    script = str(tmp_path / "appender.py")
    with open(script, "w") as f:
        f.write(_APPENDER.format(src=SRC))
    procs = [subprocess.Popen([sys.executable, script, path, wid])
             for wid in ("A", "B")]
    assert [p.wait() for p in procs] == [0, 0]
    rows, torn = read_jsonl_tolerant(path)
    assert torn is None
    assert len(rows) == 300                      # nothing lost or torn
    for wid in ("A", "B"):
        seq = [r["i"] for r in rows if r["worker"] == wid]
        assert seq == list(range(150))           # per-writer FIFO


def test_ledger_and_claims_interleave_multiprocess(tmp_path):
    """Claim records and result rows from two processes land in one
    tolerant-loadable ledger; the result-row loader skips claim records."""
    path = str(tmp_path / "led.jsonl")
    script = str(tmp_path / "mixed.py")
    with open(script, "w") as f:
        f.write("""
import sys
sys.path.insert(0, {src!r})
from repro.core.workqueue import WorkQueue, WorkUnit
from repro.core.jsonl import append_jsonl_atomic
path, wid, base = sys.argv[1], sys.argv[2], int(sys.argv[3])
q = WorkQueue(path, wid)
for i in range(25):
    step = base + i
    u = WorkUnit.make(step)
    q.publish([u])
    if q.try_claim(u):
        append_jsonl_atomic(path, [{{"step": step, "task": "default",
                                     "metrics": {{"MRR@10": 0.1}},
                                     "timings": {{}}, "subset_size": 1,
                                     "worker_id": wid}}])
        q.complete(u)
""".format(src=SRC))
    procs = [subprocess.Popen([sys.executable, script, path, wid, base])
             for wid, base in (("A", "0"), ("B", "1000"))]
    assert [p.wait() for p in procs] == [0, 0]
    led = ValidationLedger(path)                 # skips kind-bearing records
    assert len(led.validated_steps) == 50
    state = replay(path)
    assert len(state.completed_units()) == 50


# ---------------------------------------------------------------------------
# In-process fleet: forced crash, reclaim, replay parity, GC protection
# ---------------------------------------------------------------------------

class _FakeFleetPipeline:
    """Deterministic two-task pipeline for fleet mechanics (no encoders)."""

    task_names = ("default", "deep")

    def plan_units(self, step):
        return [WorkUnit.make(step, "default"),
                WorkUnit.make(step, "deep", {"mesh_size": 2})]

    def run_unit(self, params, unit, engine=None, write_runs=None):
        return ValidationResult(
            step=unit.step,
            metrics={"MRR@10": 0.01 * unit.step},
            timings={"total_s": 0.001}, subset_size=3,
            engine="fake", task=unit.task)

    def validate_params(self, params, step=0, engine=None, write_runs=None):
        raise AssertionError("fleet path must go through run_unit")


def _commit_stub_ckpt(root, step):
    ckpt.save(root, step, {"params": {"x": jnp.zeros(1)}})


def _make_worker(root, ledger_path, wid, pipeline, lease_ttl=4):
    queue = WorkQueue(ledger_path, wid, capabilities={"mesh_size": 2},
                      lease_ttl=lease_ttl)
    return ValidatorWorker(
        root, pipeline,
        ledger=ValidationLedger(ledger_path,
                                expected_tasks=pipeline.task_names),
        queue=queue, worker_id=wid,
        params_extractor=lambda state: state["params"])


def test_forced_crash_fleet_reclaim_and_replay(tmp_path):
    """The acceptance scenario: worker A claims a unit and dies mid-unit;
    the survivor B reclaims the expired lease, the step completes with
    EVERY task's row, ControlPlane.replay_ledger reproduces the online
    decision sequence byte-identically, and the claimed checkpoint was
    never GC-eligible while A's lease was live."""
    from repro.launch.fleet import FleetSupervisor

    root = str(tmp_path / "ck")
    ledger_path = str(tmp_path / "ledger.jsonl")
    pipe = _FakeFleetPipeline()
    _commit_stub_ckpt(root, 1)

    ccfg = ControlConfig(metric="MRR@10")
    control = ControlPlane(None, ccfg)
    sup = FleetSupervisor(root, ledger_path, pipe.task_names,
                          control=control, plan_units=pipe.plan_units,
                          lease_ttl=4)
    assert sup.publish_pending() == 2            # both of step 1's units

    worker_a = _make_worker(root, ledger_path, "A", pipe)
    worker_b = _make_worker(root, ledger_path, "B", pipe)

    # A claims the deep unit... and crashes before executing it
    deep = worker_a.queue.refresh().units[(1, "deep")].unit
    assert worker_a.queue.try_claim(deep)

    # while A's lease is live, the checkpoint must be GC-protected
    assert 1 in sup.protect_set()
    assert not sup.step_complete(1)

    # B drains: first the open default unit, then (after the lease ages
    # out through its ticks) the reclaimed deep unit
    for _ in range(30):
        worker_b.run_once()
        sup.pump_control()
        if sup.step_complete(1):
            break
    assert sup.step_complete(1)
    assert [u.key for u in worker_b.completed] == [(1, "default"),
                                                   (1, "deep")]
    reclaims = [e for e in worker_b.queue.state.events
                if e["event"] == "reclaim"]
    assert reclaims and reclaims[0]["from"] == "A" \
        and reclaims[0]["worker"] == "B"

    # every task's row is present, stamped with the surviving worker
    led = ValidationLedger(ledger_path, expected_tasks=pipe.task_names)
    assert led.validated_steps == [1]
    assert {r["worker_id"] for r in led.rows()} == {"B"}

    # step complete + no live claims -> GC may collect it now
    assert 1 not in sup.protect_set()

    # offline fleet replay re-derives the identical decision trace
    offline = replay(ledger_path, lease_ttl=4)
    assert offline.events == worker_b.queue.refresh().events

    # and control-plane replay reproduces the online decisions byte-for-byte
    replayed = replay_ledger(led.rows(), ccfg,
                             expected_tasks=pipe.task_names,
                             group="completion")
    online = [e.to_json() for e in control.events.decisions()]
    assert online  # the completed step WAS observed online
    assert online == [e.to_json() for e in replayed.events.decisions()]


def test_two_workers_split_backlog(tmp_path):
    """Two live workers drain a multi-step backlog cooperatively: every
    unit completes exactly once, and both workers contribute."""
    root = str(tmp_path / "ck")
    ledger_path = str(tmp_path / "ledger.jsonl")
    pipe = _FakeFleetPipeline()
    workers = [_make_worker(root, ledger_path, wid, pipe, lease_ttl=32)
               for wid in ("A", "B")]
    for step in (1, 2, 3):
        _commit_stub_ckpt(root, step)
        workers[0].queue.publish(pipe.plan_units(step))
    for _ in range(40):
        done = sum(w.run_once() for w in workers)
        if not done and not workers[0].queue.refresh().claimable({}):
            break
    state = replay(ledger_path, lease_ttl=32)
    assert len(state.completed_units()) == 6     # 3 steps x 2 tasks
    by_worker = {}
    for r in ValidationLedger(ledger_path).rows():
        by_worker.setdefault(r["worker_id"], []).append(r["step"])
    assert set(by_worker) == {"A", "B"}          # both actually worked
    assert sum(len(v) for v in by_worker.values()) == 6


def test_capability_mismatch_keeps_unit_for_big_worker(tmp_path):
    root = str(tmp_path / "ck")
    ledger_path = str(tmp_path / "ledger.jsonl")
    pipe = _FakeFleetPipeline()
    _commit_stub_ckpt(root, 1)
    small = _make_worker(root, ledger_path, "small", pipe)
    small.queue.capabilities = {"mesh_size": 1}
    big = _make_worker(root, ledger_path, "big", pipe)
    small.queue.publish(pipe.plan_units(1))
    while small.run_once():
        pass
    # the small worker drained what it could; the deep unit is untouched
    assert [u.key for u in small.completed] == [(1, "default")]
    assert big.queue.refresh().units[(1, "deep")].status == "open"
    assert big.run_once() == 1
    assert [u.key for u in big.completed] == [(1, "deep")]


def test_worker_abandons_failing_unit_until_budget(tmp_path):
    class _Failing(_FakeFleetPipeline):
        def run_unit(self, params, unit, engine=None, write_runs=None):
            raise RuntimeError("engine wedged")

    root = str(tmp_path / "ck")
    ledger_path = str(tmp_path / "ledger.jsonl")
    pipe = _Failing()
    _commit_stub_ckpt(root, 1)
    w = _make_worker(root, ledger_path, "A", pipe)
    w.queue.max_abandons = 1
    w.queue.state.max_abandons = 1
    w.queue.publish([WorkUnit.make(1, "default")])
    for _ in range(5):
        w.run_once()
    st = w.queue.refresh().units[(1, "default")]
    assert st.status == "failed"                 # budget exhausted, parked
    assert len(w.errors) == 2                    # initial try + one retry


# ---------------------------------------------------------------------------
# Single-process parity: the fleet refactor must not change solo ledgers
# ---------------------------------------------------------------------------

def test_solo_validator_writes_no_fleet_records(tmp_path):
    """An AsyncValidator without a workqueue must produce rows with neither
    claim records nor worker_id keys — byte-compatible with pre-fleet
    ledgers (and with their replay)."""
    root = str(tmp_path / "ck")
    ledger_path = str(tmp_path / "ledger.jsonl")
    _commit_stub_ckpt(root, 3)

    class _Solo(_FakeFleetPipeline):
        def validate_params(self, params, step=0, engine=None,
                            write_runs=None):
            return self.run_unit(params, WorkUnit.make(step, "default"))

        task_names = ("default",)

    v = AsyncValidator(root, _Solo(), ledger_path=ledger_path,
                       params_extractor=lambda s: s["params"])
    assert v.validate_pending() == 1
    raw, torn = read_jsonl_tolerant(ledger_path)
    assert torn is None
    assert all("kind" not in r and "worker_id" not in r for r in raw)
    # insertion key order matches the pre-fleet writer exactly
    assert list(raw[0]) == ["step", "task", "metrics", "timings",
                            "subset_size", "engine", "score_dtype"]


def test_flatten_rows_completion_grouping_and_worker_ctx():
    rows = [
        {"step": 1, "task": "a", "metrics": {"m": 0.1}, "worker_id": "A",
         "engine": "fake", "score_dtype": "f32"},
        {"step": 2, "task": "a", "metrics": {"m": 0.3}, "worker_id": "B",
         "engine": "fake", "score_dtype": "f32"},
        {"kind": "tick", "worker": "B"},         # claim records are skipped
        {"step": 2, "task": "b", "metrics": {"m": 0.4}, "worker_id": "B",
         "engine": "fake", "score_dtype": "f32"},
        {"step": 1, "task": "b", "metrics": {"m": 0.2}, "worker_id": "B",
         "engine": "fake", "score_dtype": "f32"},
    ]
    # consecutive grouping shreds step 1, whose rows were interleaved
    # (step 2's happened to land adjacently, so it alone survives)
    assert [s for s, _ in flatten_rows(rows, ("a", "b"))] == [2]
    # ...completion grouping emits each step when its LAST task row lands
    obs = flatten_rows(rows, ("a", "b"), with_context=True,
                       group="completion")
    assert [(s, sorted(f)) for s, f, _ in obs] == [
        (2, ["a:m", "b:m"]), (1, ["a:m", "b:m"])]
    assert obs[0][2]["worker_id"] == "B"         # single contributor
    assert obs[1][2]["worker_id"] == "A,B"       # joined like engine
    # pre-fleet rows emit no worker_id key at all
    legacy = flatten_rows([{"step": 1, "task": "a", "metrics": {"m": 1.0},
                            "engine": "e", "score_dtype": "f32"}],
                          ("a",), with_context=True)
    assert "worker_id" not in legacy[0][2]


def test_flatten_rows_completion_requires_expected_tasks():
    with pytest.raises(ValueError, match="completion"):
        flatten_rows([], None, group="completion")
    with pytest.raises(ValueError, match="grouping"):
        flatten_rows([], ("a",), group="bogus")


# ---------------------------------------------------------------------------
# Satellite: stop(drain=True) must not hang on a wedged engine
# ---------------------------------------------------------------------------

def test_stop_drain_timeout_surfaces_wedged_run(tmp_path):
    root = str(tmp_path / "ck")
    _commit_stub_ckpt(root, 1)
    release = threading.Event()

    class _Wedged(_FakeFleetPipeline):
        task_names = ("default",)

        def validate_params(self, params, step=0, engine=None,
                            write_runs=None):
            release.wait(30.0)                  # a stuck device dispatch
            return _FakeFleetPipeline.run_unit(
                self, params, WorkUnit.make(step, "default"))

    v = AsyncValidator(root, _Wedged(),
                       params_extractor=lambda s: s["params"])
    t0 = time.monotonic()
    v.stop(drain=True, drain_timeout=0.3)       # drain hits the wedged run
    assert time.monotonic() - t0 < 5.0          # bounded, not 30s
    assert any(key == "stop" and "timed out" in msg
               for key, msg in v.errors)
    release.set()                               # unwedge the daemon thread


def test_stop_drain_timeout_bounds_wedged_loop_thread(tmp_path):
    root = str(tmp_path / "ck")
    _commit_stub_ckpt(root, 1)
    release = threading.Event()

    class _Wedged(_FakeFleetPipeline):
        task_names = ("default",)

        def validate_params(self, params, step=0, engine=None,
                            write_runs=None):
            release.wait(30.0)
            return _FakeFleetPipeline.run_unit(
                self, params, WorkUnit.make(step, "default"))

    v = AsyncValidator(root, _Wedged(), poll_interval_s=0.01,
                       params_extractor=lambda s: s["params"])
    v.start()
    time.sleep(0.2)                              # loop enters the wedged run
    t0 = time.monotonic()
    v.stop(drain=True, drain_timeout=0.3)
    assert time.monotonic() - t0 < 5.0
    assert any(key == "stop" for key, _ in v.errors)
    release.set()


def test_stop_without_timeout_still_drains(tmp_path):
    root = str(tmp_path / "ck")
    _commit_stub_ckpt(root, 1)

    class _Solo(_FakeFleetPipeline):
        task_names = ("default",)

        def validate_params(self, params, step=0, engine=None,
                            write_runs=None):
            return self.run_unit(params, WorkUnit.make(step, "default"))

    v = AsyncValidator(root, _Solo(),
                       params_extractor=lambda s: s["params"])
    v.start()
    v.stop(drain=True)                           # legacy path: unbounded
    assert v.ledger.validated_steps == [1]


# ---------------------------------------------------------------------------
# Satellite: watcher poll must not re-stat the whole root every tick
# ---------------------------------------------------------------------------

def test_watcher_poll_stats_only_new_entries(tmp_path, monkeypatch):
    """A root with 10k committed step dirs: the first poll pays one stat
    per dir, every later poll pays only for NEW entries."""
    root = tmp_path / "ck"
    root.mkdir()
    for s in range(10_000):
        d = root / f"step_{s:010d}"
        d.mkdir()
        (d / "COMMIT").write_text("{}")          # committed marker

    from repro.core import watcher as watcher_mod
    calls = {"n": 0}
    real = watcher_mod.ckpt.is_committed

    def counting(path):
        calls["n"] += 1
        return real(path)

    monkeypatch.setattr(watcher_mod.ckpt, "is_committed", counting)
    w = CheckpointWatcher(str(root))
    assert len(w.poll()) == 10_000
    assert calls["n"] == 10_000                  # cold poll: one stat each
    calls["n"] = 0
    assert w.poll() == []
    assert calls["n"] == 0                       # warm poll: zero stats
    d = root / f"step_{10_000:010d}"
    d.mkdir()
    (d / "COMMIT").write_text("{}")
    assert w.poll() == [10_000]
    assert calls["n"] == 1                       # only the new dir


def test_watcher_cache_drops_deleted_dirs(tmp_path, monkeypatch):
    """GC'd checkpoint dirs leave the cache, so a re-used step name is
    re-statted instead of trusted stale."""
    root = tmp_path / "ck"
    root.mkdir()
    d = root / "step_0000000001"
    d.mkdir()
    (d / "COMMIT").write_text("{}")
    w = CheckpointWatcher(str(root))
    assert w.poll() == [1]
    import shutil
    shutil.rmtree(d)
    assert w.poll() == []
    d.mkdir()                                    # re-created, NOT committed
    assert w.poll() == []                        # must not trust stale cache
    (d / "COMMIT").write_text("{}")
    w.requeue(1)
    assert w.poll() == [1]


def test_watcher_uncommitted_dir_not_cached(tmp_path):
    root = tmp_path / "ck"
    root.mkdir()
    d = root / "step_0000000007"
    d.mkdir()                                    # trainer mid-write
    w = CheckpointWatcher(str(root))
    assert w.poll() == []
    (d / "COMMIT").write_text("{}")              # commit lands later
    assert w.poll() == [7]


# ---------------------------------------------------------------------------
# Shared TokenStore cache across processes (tentpole assertion)
# ---------------------------------------------------------------------------

def test_mmap_token_cache_shared_across_processes(tmp_path):
    """Two tasks of one step may run in DIFFERENT processes; the mmap
    TokenStore cache + fingerprint makes the shared-corpus case safe: a
    second process maps the same pre-padded bytes instead of rebuilding,
    and reads identical tokens."""
    from repro.core.engine import TokenStore
    texts = [[1, 2, 3], [4, 5], [6]]
    cache = str(tmp_path / "token_cache")
    a = TokenStore.build(texts, max_len=4, chunk=2, backing="mmap",
                         cache_dir=cache)
    assert not a.reused                          # this build created it
    script = str(tmp_path / "reader.py")
    with open(script, "w") as f:
        f.write("""
import sys
sys.path.insert(0, {src!r})
import numpy as np
from repro.core.engine import TokenStore
texts = [[1, 2, 3], [4, 5], [6]]
b = TokenStore.build(texts, max_len=4, chunk=2, backing="mmap",
                     cache_dir={cache!r})
assert b.reused, "second process must map the cache, not rebuild it"
assert b.rebuilt_chunks == 0
np.save(sys.argv[1], np.asarray(b.tokens))
""".format(src=SRC, cache=cache))
    out = str(tmp_path / "tok.npy")
    rc = subprocess.run([sys.executable, script, out]).returncode
    assert rc == 0
    import numpy as np
    assert np.array_equal(np.load(out), np.asarray(a.tokens))


# ---------------------------------------------------------------------------
# Slow tier: real worker subprocesses over real checkpoints
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fleet_filespace(tmp_path_factory):
    """Corpus + queries + qrels + 3 toy checkpoints, shared by the slow
    fleet integration tests (each test gets its own output dir / ledger)."""
    from repro.core.metrics import write_trec_run as _wtr
    from repro.data import corpus as corpus_lib
    base = tmp_path_factory.mktemp("fleet")
    ds = corpus_lib.synthetic_retrieval_dataset(0, n_passages=200,
                                                n_queries=20)
    cdir = base / "corpus"
    cdir.mkdir()
    corpus_lib.write_jsonl(str(cdir / "split0.jsonl"), ds.corpus)
    qfile = base / "queries.jsonl"
    corpus_lib.write_jsonl(str(qfile), ds.queries)
    qrels = base / "qrels.txt"
    with open(qrels, "w") as f:
        for qid, docs in ds.qrels.items():
            for did, g in docs.items():
                f.write(f"{qid} 0 {did} {g}\n")
    sys.path.insert(0, ROOT)
    from benchmarks.common import toy_spec, train_toy_dr
    spec = toy_spec(ds.vocab)
    ckdir = base / "ckpts"
    _, snaps = train_toy_dr(ds, spec, steps=40, snapshot_every=20)
    for step, params in snaps:
        ckpt.save(str(ckdir), step, {"params": params})
    return {"base": base, "corpus_dir": cdir, "queries": qfile,
            "qrels": qrels, "ckpts": ckdir,
            "n_ckpts": len(ckpt.list_steps(str(ckdir)))}


def _worker_argv(fs, outdir, extra=()):
    return [sys.executable, "-m", "repro.core.cli",
            "--query_file", str(fs["queries"]),
            "--candidate_dir", str(fs["corpus_dir"]),
            "--ckpts_dir", str(fs["ckpts"]),
            "--qrel_file", str(fs["qrels"]),
            "--q_max_len", "10", "--p_max_len", "26",
            "--run_name", "t", "--report_to", "jsonl",
            "--output_dir", str(outdir),
            "--worker", "--lease_ttl", "8",
            "--encoder", "tests.test_cli:toy_encoder_from_cli",
            *extra]


def _worker_env():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [SRC, ROOT] + env.get("PYTHONPATH", "").split(os.pathsep))
    return env


@pytest.mark.slow
def test_fleet_launcher_two_cli_workers_drain_backlog(fleet_filespace):
    """`python -m repro.launch.fleet --workers 2 -- <cli --worker ...>`:
    two real worker processes split the checkpoint backlog through the
    shared ledger, the launcher reaps them, and the resulting ledger is
    complete, attributed, and fleet-replayable."""
    fs = fleet_filespace
    outdir = fs["base"] / "out_launcher"
    rc = subprocess.run(
        [sys.executable, "-m", "repro.launch.fleet", "--workers", "2",
         "--poll_interval", "0.2", "--"] + _worker_argv(fs, outdir),
        env=_worker_env(), cwd=ROOT, timeout=600).returncode
    assert rc == 0
    ledger_path = str(outdir / "t_ledger.jsonl")
    led = ValidationLedger(ledger_path)
    assert len(led.validated_steps) == fs["n_ckpts"]
    assert all(r.get("worker_id", "").startswith("worker-")
               for r in led.rows())
    state = replay(ledger_path, lease_ttl=8)
    assert len(state.completed_units()) == fs["n_ckpts"]
    # publication was idempotent across both discovering workers
    assert len(state.units) == fs["n_ckpts"]


@pytest.mark.slow
def test_fleet_survives_sigkilled_worker(fleet_filespace):
    """Two real workers; one is SIGKILLed mid-run.  The survivor ticks the
    dead worker's lease out, reclaims its unit, finishes the whole backlog
    and exits 0 — the ledger ends complete with no failed units."""
    fs = fleet_filespace
    outdir = fs["base"] / "out_kill"
    env = _worker_env()
    victim = subprocess.Popen(
        _worker_argv(fs, outdir, ["--worker_id", "victim"]),
        env=env, cwd=ROOT)
    survivor = subprocess.Popen(
        _worker_argv(fs, outdir, ["--worker_id", "survivor"]),
        env=env, cwd=ROOT)
    ledger_path = str(outdir / "t_ledger.jsonl")
    try:
        # let the victim get far enough to (very likely) hold a claim
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if os.path.exists(ledger_path) and any(
                    r.get("kind") == "claim" and r.get("worker") == "victim"
                    for r in read_jsonl_tolerant(ledger_path)[0]):
                break
            if victim.poll() is not None:
                break               # drained before we could kill it
            time.sleep(0.25)
        victim.kill()
        victim.wait(timeout=30)
        assert survivor.wait(timeout=600) == 0
    finally:
        for p in (victim, survivor):
            if p.poll() is None:
                p.kill()
                p.wait()
    led = ValidationLedger(ledger_path)
    assert len(led.validated_steps) == fs["n_ckpts"]     # nothing lost
    state = replay(ledger_path, lease_ttl=8)
    assert len(state.completed_units()) == fs["n_ckpts"]
    assert not [st for st in state.units.values() if st.status == "failed"]
    # the survivor finished every unit the victim left behind
    by_worker = {r.get("worker_id") for r in led.rows()}
    assert "survivor" in by_worker


# ---------------------------------------------------------------------------
# Serving-tier GC protection: the live index's checkpoint is untouchable
# ---------------------------------------------------------------------------

def test_supervisor_extra_protect_shields_serving_checkpoint(tmp_path):
    """The checkpoint backing the LIVE serving index (and one mid-
    promotion) joins the supervisor's protect_set via extra_protect, even
    after its validation completes — quality GC can never delete the
    checkpoint queries are being answered from."""
    from repro.launch.fleet import FleetSupervisor

    root = str(tmp_path / "ck")
    ledger_path = str(tmp_path / "ledger.jsonl")
    pipe = _FakeFleetPipeline()
    serving = {"steps": set()}           # stands in for Promoter.protect_set
    sup = FleetSupervisor(root, ledger_path, pipe.task_names,
                          plan_units=pipe.plan_units,
                          extra_protect=lambda: serving["steps"])
    w = _make_worker(root, ledger_path, "A", pipe, lease_ttl=32)
    for step in (1, 2):
        _commit_stub_ckpt(root, step)
    sup.publish_pending()
    while w.run_once():
        pass
    assert sup.step_complete(1) and sup.step_complete(2)
    assert sup.protect_set() == set()    # fully validated, GC-eligible...
    serving["steps"] = {1}               # ...until step 1 goes live
    assert sup.protect_set() == {1}
    serving["steps"] = {1, 2}            # live + in-flight promotion
    assert sup.protect_set() == {1, 2}


def test_async_validator_extra_protect_and_gc_end_to_end(tmp_path):
    """End to end through the real promoter: quality-aware gc_checkpoints
    driven by the validator's protect_set keeps the serving checkpoint on
    disk even when its quality rank says delete it."""
    from benchmarks.common import toy_spec, train_toy_dr
    from repro.core.suite import ValidationConfig
    from repro.data import corpus as corpus_lib
    from repro.serve import IndexBuilder, Promoter, QueryService, ServeConfig

    ds = corpus_lib.synthetic_retrieval_dataset(0, n_passages=80,
                                                n_queries=6)
    spec = toy_spec(ds.vocab)
    _, snaps = train_toy_dr(ds, spec, steps=40, snapshot_every=20)
    root = str(tmp_path / "ck")
    for step, params in snaps:
        ckpt.save(root, step, {"params": params})
    steps = [s for s, _ in snaps]

    builder = IndexBuilder(spec, ds.corpus, ServeConfig(
        scoring=ValidationConfig(k=5, batch_size=32)))
    service = QueryService(spec, k=5, max_batch=4)
    target = {"step": steps[0]}
    promoter = Promoter(builder, service, root,
                        target_fn=lambda: target["step"],
                        log=str(tmp_path / "serve_events.jsonl"))

    class _Done:
        """Pipeline stub: every step counts as fully validated."""
        task_names = ("default",)

    validator = AsyncValidator(root, _Done(),
                               extra_protect=promoter.protect_set)
    for s in steps:
        validator.ledger.record(ValidationResult(
            step=s, metrics={"MRR@10": 0.01 * s},
            timings={"total_s": 0.001}, subset_size=1, engine="fake"))
    assert validator.protect_set() == set()      # all validated, no serving

    assert promoter.poll_once()                  # steps[0] goes live
    assert validator.protect_set() == {steps[0]}

    # quality GC wants to keep only the best step -- but the live one
    # (worst-ranked, steps[0]) must survive through protect_set
    deleted = ckpt.gc_checkpoints(root, keep=[steps[-1]],
                                  protect=validator.protect_set())
    remaining = set(ckpt.list_steps(root))
    assert steps[0] in remaining and steps[-1] in remaining
    assert steps[0] not in deleted
    # the survivor still answers queries from the protected checkpoint
    qid = next(iter(ds.queries))
    assert service.answer([(qid, ds.queries[qid])])[0].step == steps[0]


def test_pool_starts_one_worker_per_tpu_host(monkeypatch):
    """libtpu lets one process hold a host's chips: the pool starts one
    worker there and refuses a second, before spawning anything."""
    from repro.launch import fleet
    monkeypatch.setattr(fleet, "host_tpu_chips", lambda: 4)
    pool = fleet.LocalWorkerPool()
    with pytest.raises(RuntimeError, match="one process holds"):
        pool.spawn([sys.executable, "-c", "pass"], 2)
    assert pool.workers == []
    pool.spawn([sys.executable, "-c", "pass"], 1)
    try:
        with pytest.raises(RuntimeError):
            pool.spawn([sys.executable, "-c", "pass"], 1)
    finally:
        pool.shutdown()
    assert len(pool.workers) == 1
    with pytest.raises(SystemExit):
        fleet.main(["--workers", "2", "--", sys.executable, "-c", "pass"])


def test_host_tpu_chips_skips_workers_pinned_off_tpu(monkeypatch):
    from repro.launch import fleet
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert fleet.host_tpu_chips() == 0
