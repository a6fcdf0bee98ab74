"""Checkpoint-lifecycle telemetry, end to end: trace a full asyncval run.

Every stage a checkpoint moves through — ``produced`` by the trainer,
``discovered`` by the watcher, ``published``/``claimed`` through the fleet
work queue, ``store_build``/``staged``/``encoded``/``scored``/``recorded``
inside validation, ``selected`` by the control plane, ``promoted`` and
``served`` by the serving tier — is recorded as a span or event in
per-process JSONL trace files (``repro.obs``), merged into a single
Chrome trace-event JSON you can open in Perfetto (https://ui.perfetto.dev)
or ``chrome://tracing``.

This walkthrough runs the whole topology in one process:

  * a real :class:`~repro.train.trainer.Trainer` committing toy-DR
    checkpoints (``produced`` events);
  * a fleet supervisor (watcher + control plane) publishing each step's
    units and selecting the best checkpoint;
  * two validator workers, each with its OWN tracer (distinct
    ``worker_id``) sharing one ledger work queue;
  * a serving tier promoting the control plane's pick and answering
    queries off it.

Afterwards it exports the merged Chrome trace, prints the per-stage
latency breakdown (inclusive + self time), and the metrics-registry
report with the headline checkpoint-to-verdict p50/p99 — the paper's
"how stale is validation?" number, continuously measured.

    PYTHONPATH=src python examples/observability.py
"""

import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax

from repro.control import ControlConfig, ControlPlane
from repro.core.suite import (ValidationConfig, ValidationSuite,
                              ValidationTask)
from repro.core.validator import (CKPT_TO_VERDICT_METRIC, ValidationLedger,
                                  ValidatorWorker)
from repro.core.workqueue import WorkQueue
from repro.data import corpus as corpus_lib
from repro.launch.fleet import FleetSupervisor
from repro.launch.train import _contrastive_batches
from repro.models import nn
from repro.models.biencoder import biencoder_spec, contrastive_loss
from repro.obs import LIFECYCLE_STAGES, MetricsRegistry, Telemetry
from repro.obs.export import breakdown_table, load_traces, write_chrome
from repro.serve import (AdmissionController, IndexBuilder, Promoter,
                         QueryService, ServeConfig)
from repro.train import optim
from repro.train.trainer import Trainer, TrainerConfig
from repro.configs import registry


def main():
    workdir = tempfile.mkdtemp(prefix="asyncval_obs_")
    ckdir = os.path.join(workdir, "ckpts")
    ledger_path = os.path.join(workdir, "ledger.jsonl")
    print(f"[obs] workdir: {workdir}")

    # one shared registry: the trainer, supervisor, workers, and serving
    # tier all aggregate into the same --obs_report-style snapshot, while
    # each component writes its OWN trace file (merged at export time)
    registry_shared = MetricsRegistry()

    def telemetry(name):
        return Telemetry(os.path.join(workdir, f"trace_{name}.jsonl"),
                         registry=registry_shared, process=name,
                         attrs={"worker_id": name})

    tel_main = telemetry("main")

    # -- model + data --------------------------------------------------------
    arch = registry.get("dr-bert-base")
    cfg = arch.smoke_config()
    spec = biencoder_spec(cfg, q_max_len=12, p_max_len=28)
    ds = corpus_lib.synthetic_retrieval_dataset(0, n_passages=400,
                                                n_queries=40,
                                                vocab=cfg.vocab_size)

    # -- two fleet workers, each tracing to its own file ---------------------
    def make_worker(wid):
        tel = telemetry(wid)
        vcfg = ValidationConfig(metrics=("MRR@10", "Recall@100"),
                                batch_size=32, telemetry=tel)
        suite = ValidationSuite(spec, [
            ValidationTask("default", ds.corpus, ds.queries, ds.qrels)],
            vcfg)
        queue = WorkQueue(ledger_path, wid, lease_ttl=32,
                          capabilities={"mesh_size": jax.device_count()},
                          telemetry=tel)
        return ValidatorWorker(
            ckdir, suite,
            ledger=ValidationLedger(ledger_path,
                                    expected_tasks=suite.task_names,
                                    telemetry=tel),
            queue=queue, worker_id=wid, telemetry=tel), suite, tel

    w0, suite, _ = make_worker("w0")
    w1, _, _ = make_worker("w1")

    # -- control plane + supervisor (watcher publishes, control selects) ----
    control = ControlPlane(
        ckdir, ControlConfig(metric="MRR@10", mode="max"),
        event_path=os.path.join(workdir, "control.jsonl"),
        telemetry=tel_main)
    sup = FleetSupervisor(ckdir, ledger_path, suite.task_names,
                          control=control, plan_units=suite.plan_units,
                          lease_ttl=32, telemetry=tel_main)

    stop = threading.Event()

    def worker_loop(worker):
        while not stop.is_set():
            if not worker.run_once():
                time.sleep(0.02)

    threads = [threading.Thread(target=worker_loop, args=(w,), daemon=True)
               for w in (w0, w1)]
    for t in threads:
        t.start()

    # -- train: the Trainer emits a `produced` event per commit --------------
    print("[obs] training while 2 traced workers validate asynchronously...")
    params = nn.materialize(spec.init(jax.random.PRNGKey(0)))
    trainer = Trainer(
        TrainerConfig(total_steps=40, ckpt_every=10, ckpt_dir=ckdir,
                      log_every=10, async_save=False),
        lambda p, b: contrastive_loss(p, spec, b),
        optim.adamw(2e-3), params,
        _contrastive_batches(ds, spec, 16), telemetry=tel_main)
    trainer.run()

    # -- drain the fleet backlog --------------------------------------------
    n_ckpts = 4
    deadline = time.monotonic() + 300
    while time.monotonic() < deadline:
        sup.run_once()                      # discover + publish + pump
        state = sup.queue.refresh()
        if len(state.completed_units()) == n_ckpts:
            break
        time.sleep(0.05)
    stop.set()
    for t in threads:
        t.join(timeout=10)
    sup.run_once()                          # pump the last completions

    # -- serve off the control plane's pick (promoted + served spans) -------
    service = QueryService(spec, k=10, max_batch=8,
                           admission=AdmissionController(64),
                           telemetry=tel_main)
    promoter = Promoter(
        IndexBuilder(spec, ds.corpus, ServeConfig(
            scoring=ValidationConfig(k=10, batch_size=32))),
        service, ckdir,
        target_fn=lambda: control.selector.best_step,
        log=os.path.join(workdir, "serve.jsonl"), telemetry=tel_main)
    assert promoter.poll_once(), "promotion of the selected step failed"
    responses = service.answer(sorted(ds.queries.items())[:16])
    print(f"[obs] served {len(responses)} queries from step "
          f"{service.live_step()} (best by {control.cfg.metric}: "
          f"step {control.selector.best_step})")

    # -- export: one merged Chrome trace over all four timelines -------------
    for w in (w0, w1):
        w.telemetry.flush()
    tel_main.flush()
    traces = sorted(
        os.path.join(workdir, f) for f in os.listdir(workdir)
        if f.startswith("trace_"))
    chrome = os.path.join(workdir, "lifecycle_trace.json")
    doc = write_chrome(traces, chrome)
    records = load_traces(traces)
    seen = {r["name"] for r in records}
    missing = [s for s in LIFECYCLE_STAGES if s not in seen]
    assert not missing, f"lifecycle stages missing from trace: {missing}"
    workers_tracing = {r.get("worker_id") for r in records
                       if r["name"] == "scored"}
    assert len(workers_tracing) >= 2, "expected scored spans from 2 workers"
    print(f"\n[obs] wrote {chrome} ({len(doc['traceEvents'])} events; "
          f"open in https://ui.perfetto.dev)")
    print(f"[obs] all {len(LIFECYCLE_STAGES)} lifecycle stages traced "
          f"across workers {sorted(workers_tracing)}\n")

    # -- per-stage latency breakdown (inclusive vs self time) ----------------
    print(breakdown_table(records))

    # -- the metrics report (what `repro.core.cli --obs_report` prints) ------
    # NB: fleet.* counters are per-HANDLE mirrors of the global ledger fold;
    # three queue handles (supervisor, w0, w1) share this registry, so they
    # read 3x the per-run unit count.  In the normal deployment each process
    # has its own registry and reports the global count once.
    print()
    print(registry_shared.render())
    hist = registry_shared.get(CKPT_TO_VERDICT_METRIC)
    print(f"\n[obs] checkpoint-to-verdict: p50={hist.percentile(50):.3f}s "
          f"p99={hist.percentile(99):.3f}s over {hist.count} verdicts")


if __name__ == "__main__":
    main()
