"""Multi-task ValidationSuite — the toolkit's public validation API.

One *task* is what the legacy ``ValidationPipeline`` bound for a whole run:
a (corpus, queries, qrels) triple plus its mode, sampler, metrics, and
retrieval cut-off.  A *suite* validates every checkpoint against N such
tasks in one pass — the "multiple efficient validation sets" protocol of
Cho et al. 2022 (validate against several small sets and select checkpoints
that transfer), layered on Asyncval's asynchronous loop:

    suite = ValidationSuite(spec, [
        ValidationTask("dev",     corpus, dev_q,  dev_qrels),
        ValidationTask("heldout", corpus, ho_q,   ho_qrels),
    ], ValidationConfig(engine="streaming"))
    result = suite.validate_params(params, step=1000)   # one SuiteResult
    result.tasks["dev"].metrics["MRR@10"]
    result.metrics["heldout:MRR@10"]                    # flat view

The suite owns the shared resources:

  * the encoder spec and validator mesh are bound once;
  * each task's sampler runs ONCE (the subset depends only on the baseline
    run + qrels, never on the checkpoint — the paper's §3 amortization);
  * corpus :class:`~repro.core.engine.TokenStore`\\ s are cached by
    (corpus, sampled subset, chunk geometry, backing): tasks validating the
    same sampled corpus share ONE store — padded once, staged once per
    checkpoint pass, one mmap cache dir (``store_builds`` counts actual
    builds so tests can assert the sharing);
  * one engine per task is built lazily through
    :func:`repro.core.engine.make_engine`, i.e. through the pluggable
    component registries.

``AsyncValidator`` accepts a suite anywhere it accepted a pipeline; the
ledger then keys rows by ``(step, task)`` (schema v2) and the control plane
can select / early-stop on a composite ``"task:metric"`` spec.  The legacy
single-task ``ValidationPipeline`` survives in :mod:`repro.core.pipeline`
as a deprecated shim over a one-task suite — bit-for-bit identical.
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
from typing import Any, Dict, List, Optional, Sequence, Tuple

from repro.core import metrics as metrics_lib
from repro.core.engine import (ValidationStore, chunk_geometry, doc_cache_dir,
                               make_engine)
from repro.core.engine import TokenStore
from repro.core.registry import MODES, resolve_sampler
from repro.core.samplers import SubsetResult
from repro.models.biencoder import EncoderSpec

_NULL_CM = contextlib.nullcontext()


@dataclasses.dataclass
class ValidationConfig:
    """How to validate — shared across every task of a suite.  ``metrics`` /
    ``mode`` / ``k`` double as the defaults a :class:`ValidationTask` can
    override per task."""

    metrics: tuple = ("MRR@10",)
    mode: str = "retrieval"          # retrieval | rerank | average_rank
    k: int = 100                     # retrieval cut-off
    batch_size: int = 64
    impl: str = "xla"                # xla | pallas
    mesh: Any = None                 # optional sharded retrieval mesh
    engine: str = "streaming"        # streaming | materialized (legacy)
    chunk_size: Optional[int] = None  # streaming chunk rows; None -> batch_size
    scan_window: int = 8             # chunks folded per dispatch (xla stage)
    staging: str = "double_buffered"  # double_buffered | sync host->device
    staging_depth: int = 2           # prefetch depth (2 = double buffer;
                                     # deeper for remote-storage stores)
    token_backing: str = "memory"    # memory | mmap (out-of-core TokenStore)
    mmap_dir: Optional[str] = None   # cache dir for token_backing="mmap"
    token_fingerprint: str = "fast"  # fast (O(1)) | full (content hash)
    rerank_block: Optional[int] = None  # queries per materialized rerank
                                     # candidate gather (None = auto budget)
    score_dtype: str = "f32"         # scoring precision: f32 | bf16 | int8
                                     # (precision-as-fidelity; recorded in
                                     # every ledger row like `engine`)
    rerank_compact: bool = True      # pack sparse rerank candidates into
                                     # dense pseudo-chunks before encoding
    write_run: bool = False
    output_dir: Optional[str] = None
    run_tag: str = "asyncval"
    # nullable observability handle (repro.obs.Telemetry).  None (default)
    # keeps every path span-free at the cost of one attribute check; set,
    # it receives store_build/staged/encoded/scored lifecycle spans and
    # engine metrics.  Excluded from comparisons so two configs differing
    # only in instrumentation still compare equal.
    telemetry: Any = dataclasses.field(default=None, compare=False,
                                       repr=False)


@dataclasses.dataclass
class ValidationResult:
    """One checkpoint × one task.  ``task`` is ``"default"`` for legacy
    single-task runs — exactly how schema-v1 ledger rows migrate."""

    step: int
    metrics: Dict[str, float]
    timings: Dict[str, float]
    subset_size: int
    # which data path produced the numbers ("streaming"/"materialized"/...);
    # recorded in the validator ledger so cross-mode parity can be audited
    # after the fact.
    engine: str = ""
    # scoring precision the engine ran at ("f32"/"bf16"/"int8") — ledgered
    # like `engine`, so mixed-precision histories audit and replay offline.
    score_dtype: str = "f32"
    task: str = "default"
    # which fleet worker scored this row ("" outside fleet mode — the key is
    # then omitted from the ledger row, keeping single-process ledgers
    # byte-identical to pre-fleet ones); threaded like `engine` so
    # mixed-fleet ledgers are auditable offline.
    worker_id: str = ""
    # which hand-off route supplied the params: "snapshot" when scored from
    # a host-resident pre-durable snapshot (repro.handoff), "" when restored
    # from the durable checkpoint — ledgered only when "snapshot", so
    # pre-handoff ledgers stay byte-identical (the worker_id discipline).
    handoff: str = ""


@dataclasses.dataclass
class ValidationTask:
    """One validation set: the data triple plus how to score it.  ``mode`` /
    ``metrics`` / ``k`` are per-task overrides — ``None`` (the default)
    inherits the suite :class:`ValidationConfig`'s value, so a single-task
    migration needs to state them only once.  Everything else (engine,
    staging, mesh, ...) always comes from the shared config.  ``sampler``
    is a sampler instance or a registered sampler name
    (:data:`repro.core.registry.SAMPLERS`), ``sampler_depth`` the named
    sampler's subset depth."""

    name: str
    corpus: Dict[str, list]
    queries: Dict[str, list]
    qrels: Dict[str, Dict[str, int]]
    mode: Optional[str] = None            # None -> vcfg.mode
    sampler: Any = None
    sampler_depth: int = 0                # subset depth for a NAMED sampler
                                          # (0 -> the strategy's default;
                                          # ignored for instances)
    baseline_run: Optional[Dict[str, list]] = None
    metrics: Optional[tuple] = None       # None -> vcfg.metrics
    k: Optional[int] = None               # None -> vcfg.k
    # fleet capability requirements for this task's work units (e.g.
    # {"mesh_size": 8} pins a full-corpus sharded task to big workers);
    # merged over the config-derived defaults in plan_units.  Ignored —
    # harmless — outside fleet mode.
    requires: Optional[Dict[str, Any]] = None

    def __post_init__(self):
        if not self.name or not isinstance(self.name, str):
            raise ValueError(f"task name must be a non-empty string, "
                             f"got {self.name!r}")
        if ":" in self.name:
            # "task:metric" is the composite control-metric syntax; a colon
            # in the task name would make those specs ambiguous
            raise ValueError(f"task name {self.name!r} must not contain ':'")


@dataclasses.dataclass
class SuiteResult:
    """One checkpoint × every task, in suite order.

    ``metrics`` is the flat view the ledger-independent consumers (loggers,
    control plane) key on: every metric under ``"task:metric"``, plus bare
    names for the ``"default"`` task so single-task suites keep the legacy
    schema (a v1 ledger and a v2 default-task ledger replay identically).
    """

    step: int
    tasks: Dict[str, ValidationResult]

    @property
    def metrics(self) -> Dict[str, float]:
        flat: Dict[str, float] = {}
        for name, res in self.tasks.items():
            if name == "default":
                flat.update(res.metrics)
        for name, res in self.tasks.items():
            for m, v in res.metrics.items():
                flat[f"{name}:{m}"] = v
        return flat

    @property
    def log_metrics(self) -> Dict[str, float]:
        """The reporter view (CSV/JSONL columns): bare names for the
        ``default`` task — a single-task run's schema is byte-identical to
        the legacy pipeline's — and task-qualified names for every other
        task, with no redundant ``default:``-qualified duplicates.
        (:attr:`metrics` keeps both spellings for control-metric specs.)"""
        flat: Dict[str, float] = {}
        for name, res in self.tasks.items():
            if name == "default":
                flat.update(res.metrics)
            else:
                flat.update({f"{name}:{m}": v
                             for m, v in res.metrics.items()})
        return flat

    @property
    def timings(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for res in self.tasks.values():
            for k, v in res.timings.items():
                out[k] = out.get(k, 0.0) + float(v)
        return out

    @property
    def subset_size(self) -> int:
        return sum(r.subset_size for r in self.tasks.values())

    @property
    def engine(self) -> str:
        names = {r.engine for r in self.tasks.values()}
        return names.pop() if len(names) == 1 else ",".join(sorted(names))

    @property
    def score_dtype(self) -> str:
        names = {getattr(r, "score_dtype", "f32")
                 for r in self.tasks.values()}
        return names.pop() if len(names) == 1 else ",".join(sorted(names))

    @property
    def worker_id(self) -> str:
        names = {getattr(r, "worker_id", "") for r in self.tasks.values()}
        return names.pop() if len(names) == 1 else ",".join(sorted(names))

    @property
    def handoff(self) -> str:
        names = {getattr(r, "handoff", "") or "durable"
                 for r in self.tasks.values()}
        return names.pop() if len(names) == 1 else ",".join(sorted(names))


class ValidationSuite:
    """Validate checkpoints against N tasks in one pass, sharing stores.

    ``engines`` optionally injects a pre-built engine per task name (the
    multi-task twin of the old ``ValidationPipeline(engine=...)`` hook);
    unlisted tasks build theirs lazily via :func:`make_engine`.
    """

    def __init__(self, spec: EncoderSpec, tasks: Sequence[ValidationTask],
                 vcfg: Optional[ValidationConfig] = None, *,
                 engines: Optional[Dict[str, Any]] = None):
        vcfg = vcfg if vcfg is not None else ValidationConfig()
        self.spec = spec
        self.vcfg = vcfg
        self.tasks: Dict[str, ValidationTask] = {}
        for t in tasks:
            if t.name in self.tasks:
                raise ValueError(f"duplicate task name {t.name!r}")
            # resolve the per-task overrides against the shared config NOW,
            # so every downstream consumer sees concrete values
            t = dataclasses.replace(
                t, mode=t.mode if t.mode is not None else vcfg.mode,
                metrics=tuple(t.metrics) if t.metrics is not None
                else tuple(vcfg.metrics),
                k=t.k if t.k is not None else vcfg.k)
            MODES.get(t.mode)                    # fail fast, with options
            self.tasks[t.name] = t
        if not self.tasks:
            raise ValueError("ValidationSuite needs at least one task")
        self._engines: Dict[str, Any] = dict(engines or {})
        # shared TokenStore cache: key -> store; store_builds counts actual
        # pad-and-build events (tests assert corpus-sharing tasks hit 1)
        self._stores: Dict[tuple, TokenStore] = {}
        self._store_order: Dict[tuple, int] = {}
        self.store_builds = 0
        # samplers run ONCE per task, now — the subset depends only on the
        # baseline run + qrels, never on the checkpoint (paper §3)
        self.subsets: Dict[str, SubsetResult] = {}
        self.sampler_names: Dict[str, str] = {}
        self._data: Dict[str, ValidationStore] = {}
        for name, t in self.tasks.items():
            sampler = resolve_sampler(t.sampler, depth=t.sampler_depth)
            self.sampler_names[name] = sampler.name
            subset = sampler.sample(list(t.corpus), t.baseline_run, t.qrels)
            self.subsets[name] = subset
            qids = list(t.queries)
            self._data[name] = ValidationStore(
                query_ids=qids,
                query_texts=[t.queries[q] for q in qids],
                doc_ids=subset.doc_ids,
                doc_texts=[t.corpus[d] for d in subset.doc_ids],
                per_query=subset.per_query)
        if vcfg.token_backing == "mmap":
            # assign each distinct store its cache-dir index NOW, in task
            # declaration order — if it depended on lazy engine-BUILD order,
            # a run that touched tasks in a different order would remap
            # corpora onto each other's cache dirs and rebuild both (the
            # fingerprint check keeps that safe, but the cache is defeated)
            for name, t in self.tasks.items():
                tcfg = self._task_cfg(t)
                key = self._store_key(t, self._data[name], tcfg)
                self._store_order.setdefault(key, len(self._store_order))

    # -- shared resources ----------------------------------------------------
    @property
    def task_names(self) -> Tuple[str, ...]:
        return tuple(self.tasks)

    def _task_cfg(self, task: ValidationTask) -> ValidationConfig:
        return dataclasses.replace(self.vcfg, mode=task.mode,
                                   metrics=tuple(task.metrics), k=task.k)

    def _store_key(self, task: ValidationTask, data: ValidationStore,
                   tcfg: ValidationConfig) -> tuple:
        chunk, _ = chunk_geometry(tcfg, len(data.doc_texts), tcfg.mesh)
        ids = hashlib.sha1("\x00".join(data.doc_ids).encode()).hexdigest()
        return (id(task.corpus), ids, chunk, self.spec.p_max_len,
                tcfg.token_backing, tcfg.token_fingerprint)

    def _shared_doc_store(self, task: ValidationTask, data: ValidationStore,
                          tcfg: ValidationConfig) -> TokenStore:
        """The suite-wide TokenStore cache: tasks whose sampled corpus and
        chunk geometry match share one padded store (and, for mmap backing,
        one on-disk cache directory)."""
        key = self._store_key(task, data, tcfg)
        store = self._stores.get(key)
        if store is None:
            if tcfg.token_backing == "mmap" and not tcfg.mmap_dir:
                raise ValueError("token_backing='mmap' needs mmap_dir")
            index = self._store_order.setdefault(key, len(self._store_order))
            chunk, _ = chunk_geometry(tcfg, len(data.doc_texts), tcfg.mesh)
            with self.vcfg.telemetry.span(
                    "store_build", task=task.name, n_docs=len(data.doc_texts),
                    backing=tcfg.token_backing) \
                    if self.vcfg.telemetry is not None else _NULL_CM:
                store = TokenStore.build(
                    data.doc_texts, max_len=self.spec.p_max_len, chunk=chunk,
                    backing=tcfg.token_backing,
                    cache_dir=doc_cache_dir(tcfg.mmap_dir, index),
                    fingerprint=tcfg.token_fingerprint)
            self._stores[key] = store
            self.store_builds += 1
        return store

    def engine(self, name: str):
        """The (lazily built) engine for one task — built through the
        registry-backed :func:`make_engine` with the task-effective config
        passed whole."""
        if name not in self.tasks:
            raise ValueError(f"unknown task {name!r} "
                             f"(tasks: {', '.join(self.tasks)})")
        eng = self._engines.get(name)
        if eng is None:
            task, data = self.tasks[name], self._data[name]
            tcfg = self._task_cfg(task)
            # route the corpus store through the suite cache so
            # corpus-sharing tasks pad it exactly once — for every engine
            # factory that declares `uses_token_stores = True` (the built-in
            # streaming engine does; third-party registered engines opt in
            # with the same attribute)
            from repro.core.registry import ENGINES
            factory = ENGINES.get(tcfg.engine)
            if getattr(factory, "uses_token_stores", False) \
                    and data.doc_store is None:
                data.doc_store = self._shared_doc_store(task, data, tcfg)
            eng = make_engine(self.spec, data, tcfg)
            self._engines[name] = eng
        return eng

    def build_engines(self) -> None:
        """Eagerly build every task's engine.  Long-running drivers (the
        CLI, launch/train) call this at startup so a deterministic config
        error — bad staging depth, unknown engine, a third-party factory
        that raises — fails fast, instead of being swallowed per checkpoint
        by the validator's never-kill-training catch and retry loop."""
        for name in self.tasks:
            self.engine(name)

    # -- work-unit planning (the fleet's claimable granularity) --------------
    def plan_units(self, step: int):
        """The checkpoint's validation work as independently claimable
        :class:`~repro.core.workqueue.WorkUnit`\\ s — one per task, in task
        declaration order (``validate_params`` runs exactly this plan
        in-line, so a fleet draining the units computes the same rows).

        Each unit's capability requirements derive from the task-effective
        config (``mesh_size`` = the validator mesh's device count, 1
        unsharded) merged under any explicit ``ValidationTask.requires``."""
        from repro.core.workqueue import WorkUnit
        units = []
        for name, task in self.tasks.items():
            tcfg = self._task_cfg(task)
            requires = {"mesh_size": (tcfg.mesh.devices.size
                                      if tcfg.mesh is not None else 1)}
            requires.update(task.requires or {})
            units.append(WorkUnit.make(step, name, requires))
        return units

    def run_unit(self, params, unit, *, engine=None,
                 write_runs: Optional[bool] = None) -> ValidationResult:
        """Run ONE (step, task) work unit — the per-task body of
        ``validate_params``, exposed so fleet workers can execute units
        independently (two tasks of one step may run in different
        processes; the fingerprinted mmap TokenStore cache makes the
        shared-corpus case safe — each process maps the same pre-padded
        bytes, see :meth:`_shared_doc_store`)."""
        name = getattr(unit, "task", unit if isinstance(unit, str) else None)
        if name not in self.tasks:
            raise ValueError(f"unknown task {name!r} "
                             f"(tasks: {', '.join(self.tasks)})")
        step, task = int(getattr(unit, "step", 0)), self.tasks[name]
        eng = engine if engine is not None else self.engine(name)
        tel = self.vcfg.telemetry
        if tel is None:
            run, scores, timings = eng.run(params)
        else:
            # exactly ONE scored span per (step, task) unit; the engine's
            # staged/encoded spans nest under it via the tracer's
            # thread-local parent stack
            with tel.span("scored", step=step, task=name,
                          engine=getattr(eng, "name", ""),
                          score_dtype=getattr(eng, "score_dtype", "f32")):
                run, scores, timings = eng.run(params)
        names = list(task.metrics)
        if task.mode == "average_rank" and "AverageRank" not in names:
            names.append("AverageRank")
        if tel is None:
            m = metrics_lib.compute_metrics(run, task.qrels, names)
        else:
            with tel.span("metrics", step=step, task=name):
                m = metrics_lib.compute_metrics(run, task.qrels, names,
                                                telemetry=tel)
        v = self.vcfg
        do_write = v.write_run if write_runs is None else write_runs
        if do_write and v.output_dir:
            import os
            os.makedirs(v.output_dir, exist_ok=True)
            # default task keeps the legacy file name; other tasks get
            # a task-qualified tag so runs never collide
            tag = v.run_tag if name == "default" \
                else f"{v.run_tag}.{name}"
            metrics_lib.write_trec_run(
                f"{v.output_dir}/{tag}_step{step}.trec", run, scores,
                tag=tag)
        return ValidationResult(
            step=step, metrics=m, timings=timings,
            subset_size=len(self._data[name].doc_ids),
            engine=getattr(eng, "name", ""),
            score_dtype=getattr(eng, "score_dtype", "f32"), task=name)

    # -- one checkpoint, every task -----------------------------------------
    def validate_params(self, params, step: int = 0, *, engine=None,
                        write_runs: Optional[bool] = None) -> SuiteResult:
        """Validate one checkpoint against every task.  ``engine`` overrides
        every task's engine for this call only (the AsyncValidator injection
        path) — the suite itself is never mutated.  ``write_runs`` overrides
        ``vcfg.write_run`` for this call (scoring passes — e.g. ensemble
        soup candidates — set it False so they never clobber a real
        checkpoint's TREC run file).

        This IS the work-unit pipeline run in-line: ``plan_units`` then
        ``run_unit`` per unit, in task order — a single process and a fleet
        draining the same plan produce identical rows."""
        if engine is not None and len(self.tasks) > 1:
            # an injected engine was built over ONE task's queries/corpus;
            # scoring every task with it would silently ledger garbage
            # metrics for the others (use ValidationSuite(engines={...}) to
            # inject per task instead)
            raise ValueError(
                "a single engine override cannot serve a multi-task suite "
                f"(tasks: {', '.join(self.tasks)}); pass per-task engines "
                "via ValidationSuite(engines={name: engine})")
        out: Dict[str, ValidationResult] = {}
        for unit in self.plan_units(step):
            out[unit.task] = self.run_unit(params, unit, engine=engine,
                                           write_runs=write_runs)
        return SuiteResult(step=step, tasks=out)


def params_from_checkpoint(state: Any) -> Any:
    """Default extractor: trainer saves {"params":..., "opt_state":...}."""
    return state["params"] if isinstance(state, dict) and "params" in state \
        else state
