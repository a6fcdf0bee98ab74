"""One run of one cell: set-up, the measured window, the check, the line.

The window drives ``repro.core.validator.ValidatorWorker.run_step(step)``,
the per-checkpoint unit that ``AsyncValidator`` runs: load the checkpoint's
params through the lazy hand-off (``snapshots=``), place them, encode the
queries, stream the corpus through the fused encode -> score -> top-k step,
finalize, compute the metrics, append the ledger row.  Two checkpoints made
from the seed alternate, so no restore cache serves a verdict.  Verdicts run
back to back until ``seconds`` have passed; only completed verdicts count.

The suite keeps the program's ``ValidationConfig`` defaults; the mix file
sets only the mode, k, metrics and sampler.  The harness reaches the timed
verdict's top-k lists through the worker's ``engine=`` override, which it
fills with the suite's own engine behind a pass-through that keeps the
lists of the checked checkpoint.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from bench import check, traffic
from bench.cells import ROOT, Cell

CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def prepare_jax() -> None:
    """Keep JAX's persistent compilation cache in ``.jax_cache`` at the root
    of this checkout, whatever the environment names, for every program
    however short its compile, so that only a checkout's first run compiles.
    The program's own ``enable_compile_cache`` takes the same directory from
    ``JAX_COMPILATION_CACHE_DIR``.  Call before anything compiles."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(platform: Optional[str], chips: int) -> dict:
    """The devices as JAX reports them; raises unless there are ``chips``
    of ``platform`` (``None`` accepts any, for tests)."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if platform is not None and info["platform"] != platform:
        raise RuntimeError(f"JAX found no {platform}: {info}")
    if len(devs) < chips:
        raise RuntimeError(f"the cell needs {chips} chips, JAX sees {info}")
    return info


def seed_key(seed: int, salt: int):
    """A PRNG key from a seed of any size."""
    import jax
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, seed >> 32), salt)


# ---------------------------------------------------------------------------
# the program, as a validator builds it
# ---------------------------------------------------------------------------


class Checkpoints:
    """The hand-off source: ``get(step)`` returns the host snapshot of
    checkpoint ``(step - 1) % 2``."""

    def __init__(self, snapshots, annotate: Callable):
        self.snapshots = snapshots
        self.annotate = annotate

    def which(self, step: int) -> int:
        return (step - 1) % len(self.snapshots)

    def get(self, step: int):
        from repro.handoff.snapshot import ParamSnapshot
        with self.annotate("bench.handoff"):
            base = self.snapshots[self.which(step)]
            return ParamSnapshot(step=step, leaves=base.leaves,
                                 treedef_hex=base.treedef_hex)


class Recorder:
    """The suite's engine in the worker's ``engine=`` slot: runs it
    unchanged and keeps the top-k lists of verdicts that ``keep`` names."""

    def __init__(self, engine, annotate: Callable):
        self.engine = engine
        self.name = engine.name
        self.score_dtype = engine.score_dtype
        self.annotate = annotate
        self.keep = False
        self.kept: List[tuple] = []
        self.timings: List[dict] = []

    def run(self, params):
        with self.annotate("bench.engine_run"):
            run, scores, timings = self.engine.run(params)
        self.timings.append(timings)
        if self.keep:
            self.kept.append((run, scores))
        return run, scores, timings


@dataclasses.dataclass
class Program:
    traffic: traffic.Traffic
    engine: Any
    recorder: Recorder
    worker: Any
    source: Checkpoints
    telemetry: Any
    workdir: str


def _transformer_config(cfg: dict):
    import jax.numpy as jnp

    from repro.models.transformer import TransformerConfig
    return TransformerConfig(name=cfg["name"], **cfg["transformer"],
                             param_dtype=jnp.dtype(cfg["param_dtype"]),
                             compute_dtype=jnp.dtype(cfg["compute_dtype"]))


def make_checkpoints(cell: Cell, seed: int) -> list:
    """Two checkpoints' params, made on the device from the seed by the
    configuration's reference and handed over as host snapshots."""
    from repro.handoff.snapshot import ParamSnapshot
    ref = cell.reference
    snaps = []
    for i in range(2):
        host = ref.host_copy(ref.init(seed_key(seed, i + 1), cell.config))
        snaps.append(ParamSnapshot.from_tree(i, {"params": host}))
    return snaps


def build(cell: Cell, seed: int, *, telemetry: bool,
          annotate: Callable) -> Program:
    from repro.core.suite import (ValidationConfig, ValidationSuite,
                                  ValidationTask)
    from repro.core.validator import ValidationLedger, ValidatorWorker
    from repro.models.biencoder import biencoder_spec

    mix = cell.mix
    tr = traffic.generate(mix, int(cell.sizes["corpus"]), seed)
    spec = biencoder_spec(_transformer_config(cell.config),
                          pooling=cell.config["pooling"],
                          q_max_len=mix["q_len"], p_max_len=mix["p_len"])
    tel = None
    if telemetry:
        from repro.obs import Telemetry
        tel = Telemetry()
    vcfg = ValidationConfig(metrics=tuple(mix["metrics"]), mode=mix["mode"],
                            k=int(mix["k"]), telemetry=tel)
    suite = ValidationSuite(spec, [ValidationTask(
        "default", tr.corpus, tr.queries, tr.qrels, sampler=mix["sampler"])],
        vcfg)
    suite.build_engines()
    engine = suite.engine("default")
    recorder = Recorder(engine, annotate)
    source = Checkpoints(make_checkpoints(cell, seed), annotate)
    workdir = tempfile.mkdtemp(prefix="bench-validator-")
    ledger = ValidationLedger(os.path.join(workdir, "ledger.jsonl"),
                              expected_tasks=suite.task_names)
    worker = ValidatorWorker(workdir, suite, ledger=ledger, engine=recorder,
                             snapshots=source)
    return Program(traffic=tr, engine=engine, recorder=recorder,
                   worker=worker, source=source, telemetry=tel,
                   workdir=workdir)


def warm_up(prog: Program) -> None:
    """Compile every program the window runs, on as little data as does it:
    the engine's own ``run`` over the whole query set and one scan window of
    the corpus (plus the schedule's tail), then one finalize."""
    from repro.core.engine import TokenStore, plan_schedule
    eng, store = prog.engine, prog.engine.doc_store
    window = getattr(eng.stage, "window", 1)
    tail = sum(w for _, w in plan_schedule(store.n_chunks, window)
               if w != window)
    keep = sorted(set(range(min(store.n_chunks, window)))
                  | set(range(store.n_chunks - tail, store.n_chunks)))
    short = TokenStore(tokens=store.tokens[keep], mask=store.mask[keep],
                       chunk=store.chunk,
                       n_texts=min(store.n_texts, len(keep) * store.chunk))
    eng.doc_store = short
    try:
        eng.run(prog.source.snapshots[0].state()["params"])
    finally:
        eng.doc_store = store


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------


class CompileCounter:
    """Compilations (or persistent-cache loads) in this process."""

    def __init__(self):
        from jax._src import dispatch
        self._event = dispatch.BACKEND_COMPILE_EVENT
        self.count = 0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == self._event:
            self.count += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self)


@dataclasses.dataclass
class Window:
    verdict_s: List[float]
    steps: List[int]
    results: List[Any]
    attempted: int
    failed: int
    errors: List[str]
    compiles: int
    span_s: float


def measure(prog: Program, seconds: float, annotate: Callable,
            checked: int) -> Window:
    """Verdicts back to back until ``seconds`` have passed.  The recorder
    keeps the lists of every verdict on checkpoint ``checked``."""
    verdict_s, steps, results, errors = [], [], [], []
    attempted = failed = 0
    with CompileCounter() as compiles, annotate("bench.window"):
        t0 = time.perf_counter()
        step = 0
        while time.perf_counter() - t0 < seconds:
            step += 1
            attempted += 1
            prog.recorder.keep = prog.source.which(step) == checked
            t = time.perf_counter()
            try:
                with annotate("bench.verdict"):
                    res = prog.worker.run_step(step)
            except Exception as e:     # a failed verdict ends the window
                failed += 1
                errors.append(repr(e))
                break
            verdict_s.append(time.perf_counter() - t)
            steps.append(step)
            results.append(res)
        span = time.perf_counter() - t0
    return Window(verdict_s=verdict_s, steps=steps, results=results,
                  attempted=attempted, failed=failed, errors=errors,
                  compiles=compiles.count, span_s=span)


def peak_bytes(n: int) -> Optional[int]:
    import jax
    peaks = []
    for d in jax.devices()[:n]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------


def check_sample(mix: dict, n_queries: int, seed: int) -> np.ndarray:
    """Rows of the queries whose lists are compared, drawn from the seed."""
    rng = np.random.default_rng([seed, 7])
    n = min(int(mix["check_queries"]), n_queries)
    return np.sort(rng.choice(n_queries, n, replace=False))


def reference_embeddings(cell: Cell, tr: traffic.Traffic, params,
                         precision: str):
    ref, mix = cell.reference, cell.mix
    q = check.encode_all(ref, cell.config, params, list(tr.queries.values()),
                         tr.q_lens, mix["q_len"], precision)
    p = check.encode_all(ref, cell.config, params, list(tr.corpus.values()),
                         tr.p_lens, mix["p_len"], precision)
    return q, p


def reference_for(cell: Cell, tr: traffic.Traffic, ckpt: int, seed: int,
                  embeddings=None) -> check.Reference:
    """The float32 HIGHEST reference of checkpoint ``ckpt`` over the checked
    queries; ``embeddings`` reuses (queries, passages) already computed."""
    mix = cell.mix
    if embeddings is None:
        params = cell.reference.init(seed_key(seed, ckpt + 1), cell.config)
        embeddings = reference_embeddings(cell, tr, params, "highest")
    q, p = embeddings
    return check.Reference(q, p, check_sample(mix, len(tr.q_lens), seed),
                           int(mix["k"]))


def program_answers(cell: Cell, tr: traffic.Traffic, kept: List[tuple],
                    metrics: List[dict], seed: int) -> List[dict]:
    """The checked part of each kept verdict."""
    mix = cell.mix
    qids = list(tr.queries)
    rows = check_sample(mix, len(qids), seed)
    doc_row = {d: i for i, d in enumerate(tr.corpus)}
    return [check.answers_of(run, sc, m, [qids[r] for r in rows], doc_row,
                             len(doc_row), int(mix["k"]), tr.qrels,
                             list(mix["metrics"]))
            for (run, sc), m in zip(kept, metrics)]


def verify(cell: Cell, tr: traffic.Traffic, kept: List[tuple],
           metrics: List[dict], checked: int, seed: int) -> Dict[str, float]:
    """The compared numbers for the kept verdicts of checkpoint ``checked``
    against the float32 HIGHEST reference."""
    ref = reference_for(cell, tr, checked, seed)
    return check.readings(program_answers(cell, tr, kept, metrics, seed),
                          ref)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Outcome:
    line: dict
    checks: Dict[str, dict]


def annotator(on: bool) -> Callable:
    """``annotate(name)``: a profiler annotation when tracing, else nothing."""
    if not on:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


def run(cell: Cell, *, seed: int, seconds: float, trace: bool,
        t_start: float, platform: Optional[str] = "tpu",
        log: Callable[[str], None] = lambda s: None,
        on_trace: Optional[Callable] = None) -> Outcome:
    """Set up, measure, check; returns the result line and the checks.
    ``on_trace(trace_dir, trace)`` sees the traced window before its files
    are deleted."""
    import jax

    from bench import metrics_context
    device = device_info(platform, cell.chips)
    log(f"device found at {time.perf_counter() - t_start:.1f} s")
    annotate = annotator(trace)
    prog = build(cell, seed, telemetry=trace, annotate=annotate)
    log(f"built at {time.perf_counter() - t_start:.1f} s")
    warm_up(prog)
    if trace:
        stage = prog.engine.stage
        finalize = stage.finalize

        def annotated_finalize(carry):
            with annotate("bench.finalize"):
                return finalize(carry)
        stage.finalize = annotated_finalize
    wait0 = _hist_total(prog.telemetry, "engine.staging_wait_s")
    n_timings = len(prog.recorder.timings)
    checked = prog.source.which(1)      # the first verdict's checkpoint
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    workdir = prog.workdir
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.1f} s; window of {seconds} s")
    try:
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            win = measure(prog, seconds, annotate, checked)
        finally:
            if trace:
                jax.profiler.stop_trace()
        device["memory_peak_bytes"] = peak_bytes(cell.chips)
        log(f"{len(win.steps)} verdicts in {win.span_s:.1f} s, "
            f"{win.compiles} compiles in the window")
        for t, eng_t in zip(win.verdict_s, prog.recorder.timings[n_timings:]):
            log(f"verdict {t:.3f} s: " + ", ".join(
                f"{k} {v:.3f}" for k, v in eng_t.items()))
        n_docs = len(prog.traffic.corpus)
        values: Dict[str, float] = {
            "passages_per_s": n_docs * len(win.steps)
            / max(sum(win.verdict_s), 1e-9),
            "setup_s": setup_s}
        e2e = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in cell.end_to_end if m["name"] in values}
        line = {"correct": False, "attempted": win.attempted,
                "failed": win.failed, "metrics": e2e, "device": device}
        if trace:
            from bench import trace as trace_lib
            tr_data = trace_lib.load(trace_dir)
            if on_trace is not None:
                on_trace(trace_dir, tr_data)
            ctx = metrics_context.Context(
                cell=cell, trace=tr_data, traffic=prog.traffic,
                verdicts=len(win.steps), device=device,
                timings=prog.recorder.timings[n_timings:],
                staging_wait_s=_hist_total(prog.telemetry,
                                           "engine.staging_wait_s") - wait0,
                chunk=prog.engine.doc_store.chunk)
            per_layer = {}
            for m in cell.per_layer:
                v = cell.metric_reader(m["name"])(ctx)
                if v is not None:
                    per_layer[m["name"]] = {"value": v, "unit": m["unit"]}
            line["metrics"] = per_layer
            device["busy_s"] = trace_lib.busy_s(tr_data)
            device["window_s"] = trace_lib.window_s(tr_data)
            line["breakdown"] = trace_lib.breakdown(tr_data)
        kept = prog.recorder.kept
        checked_metrics = [r.tasks["default"].metrics for s, r in
                           zip(win.steps, win.results)
                           if prog.source.which(s) == checked]
        tr = prog.traffic
        del prog
        if win.failed or not kept:
            values_c = {"failed_verdicts": float(win.failed),
                        "checked_verdicts": float(len(kept))}
            checks = check.judge(values_c, {"failed_verdicts": 0})
            checks["checked_verdicts"]["ok"] = bool(kept)
        else:
            t_ref = time.perf_counter()
            readings = verify(cell, tr, kept, checked_metrics, checked, seed)
            checks = check.judge(readings, cell.sizes.get("limits"))
            log(f"reference check {time.perf_counter() - t_ref:.1f} s")
        line["correct"] = all(c["ok"] for c in checks.values())
        line["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                          for k, c in checks.items()}
        if win.errors:
            log("verdict failed: " + win.errors[0])
        return Outcome(line=line, checks=checks)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)


def _hist_total(tel, name: str) -> float:
    if tel is None:
        return 0.0
    h = tel.metrics.get(name)
    return float(h.total) if h is not None else 0.0
