"""AsyncValidator — the paper's contribution: validation decoupled from training.

Runs on its own mesh/pod (here: its own thread), watches the checkpoint
directory, validates every new committed checkpoint, and reports metrics.
Training NEVER blocks on it.

Crash tolerance (beyond-paper, required at scale): every completed validation
is appended to a ledger file; on restart the validator skips ledgered steps,
making validation idempotent.  The ledger also feeds checkpoint GC
protection (a checkpoint is deletable only once validated).
"""

from __future__ import annotations

import collections
import dataclasses
import os
import threading
import time
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, \
    Tuple

from repro.ckpt import checkpoint as ckpt
from repro.core.jsonl import append_jsonl_atomic, read_jsonl_tolerant
from repro.core.reporting import BaseLogger
from repro.core.suite import (SuiteResult, ValidationResult,
                              params_from_checkpoint)
from repro.core.watcher import CheckpointWatcher, Policy
from repro.core.workqueue import WorkQueue, WorkUnit

CKPT_TO_VERDICT_METRIC = "validate.ckpt_to_verdict_s"


class ErrorRing:
    """Bounded fault list — a drop-in for the validator's ``errors``.

    A long-running fleet worker that keeps hitting a poisoned unit would
    grow an unbounded ``List[tuple]``; this ring keeps the newest
    ``maxlen`` faults and counts the overflow in ``dropped`` (mirrored to
    the ``validator.errors_dropped`` counter when telemetry is bound).
    Supports the list surface existing callers use: ``append``, ``len``,
    iteration, indexing, and truthiness."""

    def __init__(self, maxlen: int = 256):
        self.maxlen = int(maxlen)
        self.dropped = 0
        self._ring: collections.deque = collections.deque(maxlen=self.maxlen)
        self._counter = None            # repro.obs.metrics.Counter, if bound

    def bind_counter(self, counter) -> None:
        if self.dropped and counter is not None:
            counter.inc(self.dropped)   # count drops from before binding
        self._counter = counter

    def append(self, item) -> None:
        if len(self._ring) == self.maxlen:
            self.dropped += 1
            if self._counter is not None:
                self._counter.inc()
        self._ring.append(item)

    def clear(self) -> None:
        self._ring.clear()

    def __len__(self) -> int:
        return len(self._ring)

    def __iter__(self) -> Iterator:
        return iter(list(self._ring))

    def __getitem__(self, i):
        if isinstance(i, slice):
            return list(self._ring)[i]
        return self._ring[i]

    def __bool__(self) -> bool:
        return bool(self._ring)

    def __repr__(self) -> str:
        return (f"ErrorRing({list(self._ring)!r}, maxlen={self.maxlen}, "
                f"dropped={self.dropped})")


class ValidationLedger:
    """Append-only record of validated (step, task) pairs (idempotent
    restarts).

    Schema v2: one JSONL row per (step, task) — a multi-task
    :class:`~repro.core.suite.ValidationSuite` appends one row per task for
    every checkpoint pass.  Schema-v1 rows (no ``"task"`` key) migrate on
    load as task ``"default"``, so pre-suite ledgers load and replay
    identically.

    ``expected_tasks`` (the suite's task names, wired by the validator)
    defines step completion: a step counts as validated only when EVERY
    expected task has a row — a crash between task rows re-validates the
    step instead of silently dropping the missing tasks.  Without it, any
    row completes the step (v1 semantics).

    Crash tolerance: a process killed mid-append leaves a torn final line;
    load ignores exactly that (the unledgered step is simply re-validated).
    A torn line anywhere ELSE means real corruption and still raises.

    Fleet sibling records: a validator fleet stores its work-queue claim
    protocol in this SAME file as ``"kind"``-keyed sibling records —
    ``unit`` / ``claim`` / ``renew`` / ``complete`` / ``abandon`` /
    ``tick`` (full schema documented in :mod:`repro.core.workqueue`).
    Result rows never carry a ``"kind"`` key, so this loader (and every
    pre-fleet consumer) skips claim records by that single test; a
    solo validator writes none, keeping its ledger byte-identical to the
    pre-fleet format.  Fleet rows additionally carry ``"worker_id"``
    attribution — omitted when empty, so solo rows are unchanged.

    Concurrency-safe: the control plane (selector / early-stop / GC) reads
    this ledger from the validator thread while ``record`` may run — a lock
    guards the row state, appends are flushed + fsync'd so no consumer (in
    this process or a crash-restarted one) can observe a torn row, and
    :meth:`rows` hands out a snapshot instead of live dicts."""

    def __init__(self, path: Optional[str],
                 expected_tasks: Optional[Sequence[str]] = None,
                 telemetry=None):
        self.path = path
        self.expected_tasks: Optional[Tuple[str, ...]] = \
            tuple(expected_tasks) if expected_tasks is not None else None
        # observation only: a `recorded` span around each fsync'd append.
        # The ledger's bytes are identical with telemetry on or off.
        self.telemetry = telemetry
        self._lock = threading.Lock()
        self._rows: List[dict] = []                    # record order
        self._index: Dict[Tuple[int, str], int] = {}   # (step, task) -> row
        self._by_step: Dict[int, set] = {}             # step -> task names
        self._torn_offset: Optional[int] = None
        if path and os.path.exists(path):
            # torn FINAL line (crash mid-append) is dropped — that step
            # simply re-validates; interior corruption raises.  Loading
            # never mutates the file (an audit may be reading a live
            # ledger); the fragment is truncated just before OUR first
            # append, by the writer that owns the file.
            rows, self._torn_offset = read_jsonl_tolerant(path,
                                                          kind="ledger row")
            for rec in rows:
                # fleet claim records (see repro.core.workqueue) live in the
                # same file as sibling record types; only kind-less rows are
                # validation results
                if "kind" not in rec:
                    self._ingest(rec)

    def _ingest(self, rec: dict) -> None:
        step = int(rec["step"])
        task = str(rec.get("task", "default"))    # v1 rows migrate here
        rec = {**rec, "step": step, "task": task}
        key = (step, task)
        if key in self._index:
            # re-record (a partially-recorded step re-validated after a
            # crash): supersede the stale row and append the fresh one at
            # the END, where its sibling task rows land too — replay groups
            # CONSECUTIVE same-step rows into one observation, so the
            # re-validated step must appear as one fresh consecutive block,
            # exactly when the online decision was made.
            self._rows[self._index[key]] = None
        self._index[key] = len(self._rows)
        self._rows.append(rec)
        self._by_step.setdefault(step, set()).add(task)

    def _completed(self, step: int) -> bool:
        tasks = self._by_step.get(step)
        if not tasks:
            return False
        if self.expected_tasks is None:
            return True                           # v1 semantics: any row
        return all(t in tasks for t in self.expected_tasks)

    def completed(self, step: int) -> bool:
        """True when every expected task has a row for ``step``."""
        with self._lock:
            return self._completed(step)

    def __contains__(self, step: int) -> bool:
        return self.completed(step)

    def tasks_for(self, step: int) -> List[str]:
        with self._lock:
            return sorted(self._by_step.get(step, ()))

    @property
    def validated_steps(self) -> List[int]:
        with self._lock:
            return sorted(s for s in self._by_step if self._completed(s))

    def rows(self) -> List[dict]:
        """Snapshot of all live rows in RECORD order (the order decisions
        were made in — offline replay of the control plane depends on it).
        Rows superseded by a re-record are omitted."""
        with self._lock:
            return [dict(rec) for rec in self._rows if rec is not None]

    def record(self, result) -> None:
        """Append one row per task: a :class:`SuiteResult` contributes every
        task's row (consecutively, so replay groups them back into one
        observation); a plain :class:`ValidationResult` contributes its own
        (task ``"default"`` unless set)."""
        results = list(result.tasks.values()) \
            if isinstance(result, SuiteResult) or hasattr(result, "tasks") \
            else [result]
        recs = []
        for r in results:
            rec = {"step": r.step,
                   "task": str(getattr(r, "task", "default")),
                   "metrics": r.metrics, "timings": r.timings,
                   "subset_size": r.subset_size,
                   # which data path scored this step — lets a cross-mode
                   # parity audit (streaming vs materialized vs sharded)
                   # attribute every ledger row long after the run.
                   "engine": getattr(r, "engine", ""),
                   # scoring precision of the row, recorded like `engine` so
                   # replay_ledger and cross-precision audits work offline.
                   "score_dtype": str(getattr(r, "score_dtype", "f32"))}
            # fleet provenance: which worker scored the row.  Only present
            # when a worker stamped it — single-process ledgers stay
            # byte-identical to pre-fleet ones.
            wid = str(getattr(r, "worker_id", "") or "")
            if wid:
                rec["worker_id"] = wid
            # hand-off provenance: present only when the row was scored from
            # a pre-durable snapshot (repro.handoff) — durable-restore rows
            # omit the key, so pre-handoff ledgers stay byte-identical.
            hand = str(getattr(r, "handoff", "") or "")
            if hand == "snapshot":
                rec["handoff"] = hand
            recs.append(rec)
        tel = self.telemetry
        with self._lock:
            for rec in recs:
                self._ingest(rec)
            if self.path:
                # atomic multi-writer append: fleet workers share one ledger
                # file, and append_jsonl_atomic also performs the writer-side
                # torn-tail repair the explicit truncate used to do
                self._torn_offset = None
                if tel is None:
                    append_jsonl_atomic(self.path, recs)
                else:
                    with tel.span("recorded", step=recs[0]["step"],
                                  task=recs[0]["task"], n_rows=len(recs)):
                        append_jsonl_atomic(self.path, recs)


class ValidatorWorker:
    """Executes validation work — the fleet's unit of scale.

    One worker = one process (or thread) with its own restore shardings,
    engine override, and capability tags.  Two modes share one execution
    body, so solo and fleet validation are the same code path:

      * **whole-step** (:meth:`run_step`): restore → every suite task →
        ledger rows.  The single-process :class:`AsyncValidator` is a thin
        instantiation over this.
      * **fleet** (:meth:`run_once` / :meth:`run_forever`): claim ONE
        (step, task) unit from the shared :class:`~repro.core.workqueue.
        WorkQueue`, heartbeat the lease while the engine runs, append the
        result row, mark the unit complete.  Failures abandon the unit so a
        peer retries it — the queue's abandon count is the DISTRIBUTED
        retry budget, derived from the ledger, never from worker state.

    ``worker_id`` stamps every ledger row this worker appends (omitted when
    empty, keeping single-process ledgers byte-identical to pre-fleet
    ones)."""

    def __init__(self, ckpt_root: str, pipeline, *,
                 ledger: Optional[ValidationLedger] = None,
                 queue: Optional[WorkQueue] = None,
                 logger: Optional[BaseLogger] = None,
                 params_extractor: Callable = params_from_checkpoint,
                 shardings: Any = None,
                 engine: Any = None,
                 worker_id: str = "",
                 heartbeat_interval_s: float = 0.25,
                 telemetry=None,
                 max_errors: int = 256,
                 snapshots: Any = None):
        self.ckpt_root = ckpt_root
        self.pipeline = pipeline
        self.queue = queue
        # lazy snapshot hand-off source (repro.handoff SnapshotChannel or
        # SnapshotSpool — anything with get(step) -> ParamSnapshot|None):
        # consulted BEFORE the durable restore, so a step can be scored
        # while its ckpt.save is still racing in the background.
        self.snapshots = snapshots
        self.logger = logger
        self.params_extractor = params_extractor
        self.shardings = shardings
        self.engine = engine
        self.worker_id = str(worker_id
                             or (queue.worker_id if queue is not None
                                 else ""))
        self.heartbeat_interval_s = heartbeat_interval_s
        expected = tuple(getattr(pipeline, "task_names", ())
                         or ("default",))
        if telemetry is None:
            # one handle per validator: a worker given none observes with
            # the suite's (the engine's spans already go there)
            telemetry = getattr(getattr(pipeline, "vcfg", None),
                                "telemetry", None)
        self.ledger = ledger if ledger is not None \
            else ValidationLedger(None, expected_tasks=expected,
                                  telemetry=telemetry)
        self.telemetry = telemetry
        self.errors = ErrorRing(max_errors)
        if telemetry is not None:
            self.errors.bind_counter(
                telemetry.metrics.counter("validator.errors_dropped"))
            if self.ledger.telemetry is None:
                self.ledger.telemetry = telemetry
        self.completed: List[WorkUnit] = []
        # last restored checkpoint, so the N units of one step (and the
        # whole-step path) pay the restore cost once
        self._params_step: Optional[int] = None
        self._params: Any = None
        self._params_handoff = ""   # "snapshot" | "" for the cached params

    # -- shared execution body ---------------------------------------------
    def load_params(self, step: int):
        if self._params_step != step:
            tel = self.telemetry
            if tel is None:
                self._load(step)
            else:
                with tel.span("loaded", step=step):
                    self._load(step)
        return self._params

    def _load(self, step: int) -> None:
        snap = self.snapshots.get(step) \
            if self.snapshots is not None else None
        if snap is not None:
            # pre-durable hand-off: reconstruct the exact state tree the
            # durable restore would produce (same treedef, same leaf bytes,
            # same shardings placement) — bit-parity is the contract,
            # provenance is the only observable difference
            self._params = self.params_extractor(
                snap.state(shardings=self.shardings))
            self._params_handoff = "snapshot"
        else:
            state, _ = ckpt.restore(self.ckpt_root, step,
                                    shardings=self.shardings)
            self._params = self.params_extractor(state)
            self._params_handoff = ""
        self._params_step = step

    @property
    def last_handoff(self) -> str:
        """``"snapshot"`` when the cached params came from the hand-off
        channel, ``""`` for a durable restore."""
        return self._params_handoff

    def invalidate_params_cache(self) -> None:
        """Drop the cached restore.  Called on validation failure: the
        cached tree may be the fault (a poisoned snapshot), and the retry —
        which reaches the worker AFTER the validator discards the snapshot —
        must re-resolve its source (then the durable checkpoint) instead of
        re-scoring the cached copy."""
        self._params_step = None
        self._params = None
        self._params_handoff = ""

    def _stamp(self, result):
        """Attach this worker's id and hand-off provenance to every row of
        ``result`` (no-op for anonymous single-process durable-restore
        workers: rows stay bit-identical)."""
        updates = {}
        if self.worker_id:
            updates["worker_id"] = self.worker_id
        if self._params_handoff:
            updates["handoff"] = self._params_handoff
        if not updates:
            return result
        if hasattr(result, "tasks"):            # SuiteResult
            return dataclasses.replace(result, tasks={
                n: dataclasses.replace(r, **updates)
                for n, r in result.tasks.items()})
        return dataclasses.replace(result, **updates)

    def log_result(self, result) -> None:
        if self.logger is None:
            return
        # reporter schema: bare names for the default task, task-qualified
        # for the rest (no default: duplicates)
        logmet = getattr(result, "log_metrics", result.metrics)
        self.logger.log(result.step,
                        {**logmet, **result.timings,
                         "subset_size": result.subset_size,
                         "engine": getattr(result, "engine", ""),
                         "score_dtype": getattr(result, "score_dtype",
                                                "f32")})

    def run_step(self, step: int):
        """Whole-checkpoint validation: restore, run EVERY suite task
        in-line, append the ledger rows.  Raises on failure with nothing
        recorded — retry policy belongs to the caller (the AsyncValidator's
        watcher requeue, or the fleet's abandon budget)."""
        params = self.load_params(step)
        try:
            result = self._stamp(self.pipeline.validate_params(
                params, step=step, engine=self.engine))
        except BaseException:
            self.invalidate_params_cache()
            raise
        self.ledger.record(result)
        if self.telemetry is not None:
            self._observe_verdict(step)
        return result

    def _observe_verdict(self, step: int) -> None:
        """Checkpoint-to-verdict latency, from the earliest mark available:
        ``produced`` (the trainer handed the state to the save path — the
        edge the lazy hand-off shortens) → ``snapshotted`` (the hand-off
        publish) → ``discovered`` (watcher poll) → COMMIT-marker mtime
        (wall clock; covers commit→verdict for cross-process fleets).
        Metrics only — never a scheduling input."""
        tel = self.telemetry
        lag = None
        for mark in ("produced", "snapshotted", "discovered"):
            lag = tel.since(mark, step)
            if lag is not None:
                break
        if lag is None:
            marker = os.path.join(ckpt._step_dir(self.ckpt_root, step),
                                  ckpt.COMMIT_MARKER)
            try:
                lag = max(0.0, time.time() - os.path.getmtime(marker))
            except OSError:
                return
        tel.metrics.histogram(CKPT_TO_VERDICT_METRIC).observe(lag)

    # -- fleet claim loop ---------------------------------------------------
    def execute_unit(self, unit: WorkUnit) -> ValidationResult:
        """Run ONE claimed (step, task) unit, heartbeating the lease (renew
        records) while the engine runs so it cannot expire mid-flight."""
        params = self.load_params(unit.step)
        stop_hb = threading.Event()
        hb = threading.Thread(target=self._heartbeat, args=(unit, stop_hb),
                              daemon=True)
        hb.start()
        try:
            result = self._stamp(self.pipeline.run_unit(
                params, unit, engine=self.engine))
        except BaseException:
            self.invalidate_params_cache()
            raise
        finally:
            stop_hb.set()
            hb.join()
        self.ledger.record(result)
        self.queue.complete(unit)   # after the row: a complete has a result
        if self.telemetry is not None:
            self._observe_verdict(unit.step)
        self.log_result(result)
        self.completed.append(unit)
        return result

    def _heartbeat(self, unit: WorkUnit, stop_evt: threading.Event) -> None:
        while not stop_evt.wait(self.heartbeat_interval_s):
            try:
                self.queue.renew(unit)
            except Exception:   # a failed heartbeat must not kill the run
                pass

    def run_once(self) -> int:
        """One scheduling round: claim and execute at most one unit.
        Returns 1 when a unit completed, 0 otherwise (appending a tick when
        peers hold live leases, so a DEAD peer's lease can age out — seq is
        the clock)."""
        if self.queue is None:
            raise RuntimeError("fleet mode requires a WorkQueue")
        state = self.queue.refresh()
        for unit in state.claimable(self.queue.capabilities):
            if not self.queue.try_claim(unit):
                continue                    # raced a peer and lost
            try:
                self.execute_unit(unit)
            except Exception as e:          # release it for a peer to retry
                self.errors.append((unit.step, f"{unit.task}: {e!r}"))
                self.queue.abandon(unit, error=repr(e))
                return 0
            return 1
        if state.blocked():
            self.queue.tick()
        return 0

    def run_forever(self, stop_event: threading.Event, *,
                    idle_wait_s: float = 0.05,
                    drained: Optional[Callable[[], bool]] = None) -> None:
        """Claim loop until ``stop_event`` is set, or ``drained()`` reports
        the backlog empty during an idle round."""
        while not stop_event.is_set():
            if self.run_once() == 0:
                if drained is not None and drained():
                    return
                stop_event.wait(idle_wait_s)


class AsyncValidator:
    """Watches ``ckpt_root`` and validates every committed checkpoint.

    ``pipeline`` is anything with ``validate_params(params, step=, engine=)``
    — a :class:`~repro.core.suite.ValidationSuite` (per-task ledger rows),
    the deprecated single-task ``ValidationPipeline`` shim, or a custom
    object.  Its optional ``task_names`` attribute defines ledger-completion
    semantics (absent -> the single ``"default"`` task).

    Since the fleet refactor this is a THIN single-worker instantiation of
    :class:`ValidatorWorker`: the watcher/retry/cap/controller loop lives
    here, execution (restore → validate → ledger) lives on ``self.worker``.
    Pass ``workqueue`` to make GC respect in-flight claims from OTHER
    workers sharing the ledger (``worker_id`` then stamps this validator's
    rows); without one, behaviour — including ledger bytes — is identical
    to the pre-fleet validator."""

    def __init__(self, ckpt_root: str, pipeline, *,
                 logger: Optional[BaseLogger] = None,
                 policy: Optional[Policy] = None,
                 max_num_valid: Optional[int] = None,
                 ledger_path: Optional[str] = None,
                 poll_interval_s: float = 0.2,
                 params_extractor: Callable = params_from_checkpoint,
                 shardings: Any = None,
                 engine: Any = None,
                 max_retries: int = 2,
                 controller: Any = None,
                 workqueue: Optional[WorkQueue] = None,
                 worker_id: str = "",
                 extra_protect: Optional[Callable[[], set]] = None,
                 telemetry=None,
                 snapshots: Any = None):
        self.ckpt_root = ckpt_root
        self.telemetry = telemetry
        self.watcher = CheckpointWatcher(ckpt_root, policy=policy,
                                         telemetry=telemetry)
        # lazy snapshot hand-off (repro.handoff.SnapshotChannel): pending
        # snapshots are validated BEFORE the watcher poll, and a publish
        # wakes the loop immediately instead of waiting out poll_interval_s.
        # The watcher remains the fallback + dedupe authority: snapshot-
        # scored steps are mark_seen'd so their eventual durable discovery
        # is consumed, and dropped/failed snapshots fall back to the
        # watcher path untouched.
        self.snapshots = snapshots
        self._wake = threading.Event()
        if snapshots is not None and hasattr(snapshots, "subscribe"):
            snapshots.subscribe(lambda step: self._wake.set())
        self.max_num_valid = max_num_valid
        # completion = a row for every suite task (single-task pipelines and
        # doubles fall back to the one "default" task = v1 semantics)
        expected = tuple(getattr(pipeline, "task_names", ()) or ("default",))
        self.workqueue = workqueue
        if telemetry is not None:
            # single-attachment convenience: thread the handle through the
            # suite config (engine spans) and queue if the caller didn't
            if workqueue is not None and workqueue.telemetry is None:
                workqueue.telemetry = telemetry
            vcfg = getattr(pipeline, "vcfg", None)
            if vcfg is not None \
                    and getattr(vcfg, "telemetry", None) is None:
                vcfg.telemetry = telemetry
        # engine injection (the `engine` kwarg): swap the validation data
        # path (streaming / materialized / custom) for THIS validator's runs
        # without rebuilding — or mutating — the pipeline's subset, stores,
        # or metric plumbing.
        self.worker = ValidatorWorker(
            ckpt_root, pipeline,
            ledger=ValidationLedger(ledger_path, expected_tasks=expected,
                                    telemetry=telemetry),
            queue=workqueue, logger=logger,
            params_extractor=params_extractor, shardings=shardings,
            engine=engine, worker_id=worker_id, telemetry=telemetry,
            snapshots=snapshots)
        self.poll_interval_s = poll_interval_s
        self.results: List[ValidationResult] = []
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # one shared fault ring (bounded; see ErrorRing): worker execution
        # faults and loop-level faults (retry exhaustion, controller bugs)
        # land together
        self.errors = self.worker.errors
        # failed-step retry budget: a checkpoint that fails validation is
        # requeued (the watcher marked it seen when poll() handed it out, so
        # without this it would be permanently swallowed); after max_retries
        # re-attempts it is given up on and stays in ``errors``.
        self.max_retries = max_retries
        self._failures: Dict[int, int] = {}
        # control-plane hook: an object with on_result(result, validator),
        # invoked after every ledger append (selection / early stopping /
        # quality-aware GC — see repro.control.plane.ControlPlane).  Runs on
        # the validator thread; controller faults are captured in ``errors``
        # so a control bug can never take validation down.
        self.controller = controller
        # additional GC protections beyond validation state — e.g. the
        # serving tier passes Promoter.protect_set so quality GC can never
        # delete the checkpoint backing the LIVE index (or one mid-swap)
        self.extra_protect = extra_protect

    # -- thin-instantiation aliases (execution state lives on the worker) --
    @property
    def pipeline(self):
        return self.worker.pipeline

    @pipeline.setter
    def pipeline(self, value):
        self.worker.pipeline = value

    @property
    def engine(self):
        return self.worker.engine

    @engine.setter
    def engine(self, value):
        self.worker.engine = value

    @property
    def logger(self):
        return self.worker.logger

    @logger.setter
    def logger(self, value):
        self.worker.logger = value

    @property
    def ledger(self) -> ValidationLedger:
        return self.worker.ledger

    @property
    def params_extractor(self):
        return self.worker.params_extractor

    @params_extractor.setter
    def params_extractor(self, value):
        self.worker.params_extractor = value

    @property
    def shardings(self):
        return self.worker.shardings    # validator-mesh layout (elastic)

    @shardings.setter
    def shardings(self, value):
        self.worker.shardings = value

    # -- core single-pass --------------------------------------------------
    def validate_pending(self) -> int:
        n = self._validate(self._snapshot_pending())
        return n + self._validate(self.watcher.poll())

    def _snapshot_pending(self) -> List[int]:
        """Claim the hand-off channel's unvalidated snapshots (ascending).
        Ledgered steps are marked validated without a claim — the channel
        can then retire them once durable."""
        if self.snapshots is None:
            return []
        steps = []
        for step in self.snapshots.pending():
            if step in self.ledger:
                self.snapshots.mark_validated(step)
                continue
            if self.snapshots.claim(step) is not None:
                steps.append(step)
        return steps

    def validate_step(self, step: int) -> int:
        """Validate one specific committed step NOW, bypassing the watcher
        policy (still ledger-idempotent, still running the full logger /
        controller path).  The control plane uses this to score a virtual
        ensemble checkpoint: under a skipping policy (stride/budget/
        latest_first) the soup's step id may never be policy-selected, and
        it must not end up policy-skipped and unscored."""
        self.watcher.mark_seen(step)           # claimed: not pending, and
        return self._validate([step],          # not counted as skipped
                              ignore_cap=True)

    def _validate(self, steps, *, ignore_cap: bool = False) -> int:
        n = 0
        for step in steps:
            # max_num_valid caps the watcher-driven loop only; an explicit
            # validate_step (the soup's scoring path) must not be silently
            # swallowed by it, or the committed ensemble stays unledgered.
            if not ignore_cap and self.max_num_valid is not None \
                    and len(self.results) >= self.max_num_valid:
                break
            if step in self.ledger:
                continue
            try:
                # restore → validate → ledger rows, on the worker
                result = self.worker.run_step(step)
            except Exception as e:      # validation must never kill training
                self.errors.append((step, repr(e)))
                if self.snapshots is not None:
                    # drop the (possibly poisoned) host copy: the retry goes
                    # through the watcher + durable restore once committed
                    self.snapshots.discard(step)
                n_fail = self._failures.get(step, 0) + 1
                self._failures[step] = n_fail
                if n_fail <= self.max_retries:
                    self.watcher.requeue(step)   # retry on a later poll
                else:
                    self.watcher.mark_seen(step)
                continue
            self._failures.pop(step, None)
            if self.snapshots is not None \
                    and self.worker.last_handoff == "snapshot":
                # verdict landed from the hand-off path: free the snapshot
                # (once durable) and consume the step's eventual watcher
                # discovery so it is never validated twice
                self.snapshots.mark_validated(step)
                self.watcher.mark_seen(step)
            self.results.append(result)
            # adaptive scheduling feedback (BudgetPolicy): observed
            # validation latency drives the stride controller.
            self.watcher.policy.observe_latency(
                float(result.timings.get("total_s", 0.0)))
            self.worker.log_result(result)
            if self.controller is not None:
                try:
                    self.controller.on_result(result, self)
                except Exception as e:
                    self.errors.append((step, f"controller: {e!r}"))
            n += 1
        return n

    # -- async (thread) mode -----------------------------------------------
    def start(self) -> None:
        assert self._thread is None

        def loop():
            while not self._stop.is_set():
                self._wake.clear()
                self.validate_pending()
                if self.max_num_valid is not None \
                        and len(self.results) >= self.max_num_valid:
                    return
                # a snapshot publish sets _wake and cuts the sleep short —
                # the hand-off path never waits out the watcher interval
                self._wake.wait(self.poll_interval_s)

        self._thread = threading.Thread(target=loop, daemon=True)
        self._thread.start()

    def stop(self, *, drain: bool = True,
             drain_timeout: Optional[float] = None) -> None:
        """Signal shutdown; with drain=True validate whatever is committed.

        ``drain_timeout`` (seconds) bounds the WHOLE shutdown — the loop
        join and the final drain pass — so a wedged engine run cannot hang
        it forever.  On expiry the timeout is surfaced in ``errors`` (key
        ``"stop"``) and the wedged daemon thread is abandoned; whatever it
        eventually ledgers is still idempotent on restart."""
        self._stop.set()
        self._wake.set()                # unblock a loop mid-sleep
        deadline = None if drain_timeout is None \
            else time.monotonic() + drain_timeout
        if self._thread is not None:
            self._thread.join(timeout=drain_timeout)
            if self._thread.is_alive():
                self.errors.append(
                    ("stop", f"drain timed out after {drain_timeout}s "
                             "waiting for the validation loop"))
                self._thread = None
                return
            self._thread = None
        if not drain:
            return
        if deadline is None:
            self.validate_pending()
            return
        t = threading.Thread(target=self._drain_guarded, daemon=True)
        t.start()
        t.join(timeout=max(0.0, deadline - time.monotonic()))
        if t.is_alive():
            self.errors.append(
                ("stop", f"drain timed out after {drain_timeout}s"))

    def _drain_guarded(self) -> None:
        try:
            self.validate_pending()
        except Exception as e:          # surfaced, never raised at shutdown
            self.errors.append(("stop", f"drain: {e!r}"))

    # -- single-GPU mode (paper: run after training completes) -------------
    def validate_all_existing(self) -> List[ValidationResult]:
        self.validate_pending()
        return self.results

    def protect_set(self) -> set:
        """Steps GC must keep: committed with a *pending* quality claim —
        not yet validated and not deliberately passed over by the watcher
        policy.  Failed-but-retrying (and given-up) steps stay protected;
        policy-skipped ones (stale/off-stride/over-budget) will never be
        validated, so protecting them would leak storage forever under
        skipping policies.

        With a fleet ``workqueue`` attached, steps under a LIVE lease held
        by ANY worker are additionally protected: a peer may be mid-restore
        on that checkpoint, and GC'ing it would turn its crash-safe claim
        into a spurious failure.

        ``extra_protect`` (constructor hook) unions in protections outside
        validation's own state — the serving tier's live/promoting
        checkpoints being the canonical case."""
        committed = set(ckpt.list_steps(self.ckpt_root))
        protected = committed - set(self.ledger.validated_steps) \
            - self.watcher.skipped
        if self.workqueue is not None:
            protected |= committed & self.workqueue.refresh().claimed_steps()
        if self.extra_protect is not None:
            protected |= set(self.extra_protect())
        return protected
