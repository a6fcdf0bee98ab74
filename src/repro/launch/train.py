"""End-to-end training driver with asynchronous checkpoint validation.

The Asyncval deployment (paper Fig. 1b): the trainer commits checkpoints to
a directory; a decoupled validator thread watches the directory and
validates each checkpoint while training continues.  Training NEVER blocks
on validation.  Both run on the process's default device: on a multi-chip
host the trainer and the validator share the first chip.

    python -m repro.launch.train --arch dr-bert-base --steps 60 \
        --ckpt-every 10 --workdir asyncval_run [--sync] [--write-run]

The exit code is 1 when any checkpoint failed to validate.

``--sync`` runs the paper's Figure-1a baseline instead (validation inline
in the training loop) so the wall-clock pipelining win is measurable —
see benchmarks/bench_async_schedule.py.

Any registry arch trains (reduced smoke config on CPU); the retrieval
validation loop attaches to embedding-producing archs (biencoder, lm,
recsys-sequential); others validate by held-out loss.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import checkpoint as ckpt
from repro.configs import registry
from repro.control import ControlConfig, ControlPlane
from repro.core.reporting import JSONLLogger
from repro.core.suite import (ValidationConfig, ValidationSuite,
                              ValidationTask)
from repro.core.samplers import FullCorpus, RunFileTopK
from repro.core.validator import AsyncValidator
from repro.core.watcher import BudgetPolicy, Policy
from repro.data import corpus as synthetic_ds
from repro.models import nn
from repro.models import transformer as tfm
from repro.models.biencoder import biencoder_spec, contrastive_loss
from repro.train import optim
from repro.train.trainer import Trainer, TrainerConfig


def _contrastive_batches(ds, spec, batch_size: int, n_psg: int = 2):
    """Deterministic step -> batch function from the synthetic dataset."""
    qids = sorted(ds.qrels)
    docids = list(ds.corpus)
    by_qid_gold = {q: next(iter(ds.qrels[q])) for q in qids}

    def make(step: int):
        rng = np.random.default_rng(1000 + step)
        pick = rng.choice(len(qids), size=batch_size)
        q_tok, p_tok = [], []
        for i in pick:
            qid = qids[i]
            q_tok.append(ds.queries[qid])
            gold = by_qid_gold[qid]
            negs = rng.choice(len(docids), size=n_psg - 1)
            p_tok.append([ds.corpus[gold]]
                         + [ds.corpus[docids[j]] for j in negs])
        from repro.data.corpus import pad_batch
        qt, qm = pad_batch(q_tok, spec.q_max_len)
        flat = [t for ps in p_tok for t in ps]
        pt, pm = pad_batch(flat, spec.p_max_len)
        B = batch_size
        return {"q_tokens": jnp.asarray(qt), "q_mask": jnp.asarray(qm),
                "p_tokens": jnp.asarray(pt).reshape(B, n_psg, -1),
                "p_mask": jnp.asarray(pm).reshape(B, n_psg, -1)}

    return make


def run(args) -> dict:
    os.makedirs(args.workdir, exist_ok=True)
    ckpt_dir = os.path.join(args.workdir, "ckpts")

    arch = registry.get(args.arch)
    assert arch.family == "biencoder", \
        "train.py end-to-end driver targets the paper's DR bi-encoder; " \
        "other families train via examples/ or the Trainer API directly"
    cfg = arch.smoke_config() if not args.full else arch.full_config()
    cfg = dataclasses.replace(cfg, compute_dtype=jnp.float32)
    spec = biencoder_spec(cfg, q_max_len=args.q_max_len,
                          p_max_len=args.p_max_len)

    ds = synthetic_ds.synthetic_retrieval_dataset(
        args.seed, n_passages=args.corpus_size, n_queries=args.n_queries,
        vocab=cfg.vocab_size)
    baseline_run = synthetic_ds.lexical_baseline_run(ds, k=args.depth)

    params = nn.materialize(spec.init(jax.random.PRNGKey(args.seed)))
    opt = optim.adamw(args.lr)
    stop_file = os.path.join(args.workdir, "STOP")
    # control flags default off so pre-control callers (plain Args objects,
    # benchmarks) keep the classic produce-only behaviour.
    patience = getattr(args, "early_stop_patience", 0)
    min_delta = getattr(args, "early_stop_min_delta", 0.0)
    overfit_window = getattr(args, "overfit_window", 0)
    keep_top_k = getattr(args, "keep_top_k", 0)
    ensemble_top_k = getattr(args, "ensemble_top_k", 0)
    policy_kind = getattr(args, "policy", "fifo")
    handoff = getattr(args, "handoff", False)
    control_on = patience > 0 or keep_top_k > 0 or ensemble_top_k > 0
    # lazy snapshot hand-off: the trainer publishes each checkpoint's host
    # copy to a bounded channel the moment it lands; the validator scores
    # it while the durable save is still racing.  Watcher stays fallback.
    snapshots = None
    if handoff:
        from repro.handoff import SnapshotChannel, SnapshotSpool
        spool_root = getattr(args, "handoff_spool", "") or None
        snapshots = SnapshotChannel(
            capacity=getattr(args, "handoff_capacity", 2),
            spool=SnapshotSpool(spool_root) if spool_root else None)
    # a STOP marker is one run's verdict, not the workdir's: clear a stale
    # one so a restarted/continued run trains instead of halting at step 0.
    if os.path.exists(stop_file):
        os.remove(stop_file)
    tcfg = TrainerConfig(total_steps=args.steps, ckpt_every=args.ckpt_every,
                         ckpt_dir=ckpt_dir, log_every=args.ckpt_every,
                         async_save=True,
                         stop_file=stop_file if patience > 0 else None,
                         snapshots=snapshots)
    trainer = Trainer(tcfg, lambda p, b: contrastive_loss(p, spec, b),
                      opt, params,
                      _contrastive_batches(ds, spec, args.batch_size),
                      logger=JSONLLogger(os.path.join(args.workdir,
                                                      "train.jsonl")))

    sampler = (RunFileTopK(depth=args.depth) if args.subset else FullCorpus())
    vcfg = ValidationConfig(metrics=("MRR@10", "Recall@100"),
                            k=100, batch_size=args.batch_size,
                            write_run=getattr(args, "write_run", False),
                            output_dir=os.path.join(args.workdir, "runs"))
    # single-task suite named "default": ledger rows, metric names and the
    # control plane's "MRR@10" spec are exactly the legacy pipeline's.
    suite = ValidationSuite(spec, [
        ValidationTask("default", ds.corpus, ds.queries, ds.qrels,
                       sampler=sampler, baseline_run=baseline_run),
    ], vcfg)
    # fail fast on deterministic engine-config errors instead of having
    # every checkpoint's validation swallowed by the retry loop
    suite.build_engines()

    # convergence control plane: ledger-driven selection + quality-aware GC,
    # async early stop via the STOP marker, post-run checkpoint ensembling.
    control = None
    if control_on:
        ccfg = ControlConfig(metric="MRR@10", mode="max",
                             keep_top_k=keep_top_k,
                             early_stop=patience > 0,
                             patience=max(patience, 1),
                             min_delta=min_delta,
                             overfit_window=overfit_window,
                             ensemble_top_k=ensemble_top_k)
        control = ControlPlane(ckpt_dir, ccfg, stop_path=stop_file,
                               event_path=os.path.join(args.workdir,
                                                       "control.jsonl"),
                               durability=snapshots.durability
                               if snapshots is not None else None)
    policy = BudgetPolicy() if policy_kind == "budget" \
        else Policy(kind=policy_kind, stride=getattr(args, "stride", 1))
    validator = AsyncValidator(
        ckpt_dir, suite, policy=policy, controller=control,
        logger=JSONLLogger(os.path.join(args.workdir, "valid.jsonl")),
        ledger_path=os.path.join(args.workdir, "ledger.jsonl"),
        snapshots=snapshots)
    if control is not None:
        # restart: warm the ranking from the prior session's ledger so
        # quality-aware GC never forgets already-validated checkpoints
        # (old steps are skipped by idempotency and would otherwise be
        # invisible to a cold selector).
        control.rehydrate(validator.ledger.rows(),
                          expected_tasks=suite.task_names)

    def feed_control(step, m):
        if control is not None:
            control.note_train(step, m)     # overfit detector's train side

    t0 = time.time()
    if args.sync:
        # paper Fig. 1a: validate inline after each checkpoint
        def on_metrics(step, m):
            feed_control(step, m)
            if step % args.ckpt_every == 0:
                trainer.saver.wait()
                validator.validate_pending()
        trainer.run(on_metrics=on_metrics)
        validator.validate_pending()
    else:
        # paper Fig. 1b: validation decoupled, runs while training continues
        validator.start()
        trainer.run(on_metrics=feed_control)
        validator.stop(drain=True)
    if control is not None:
        # every durable save has landed (trainer.run waits the saver out):
        # release any durability-gated GC held on snapshot-scored evidence
        control.maybe_gc(validator)

    ensemble = None
    if control is not None and ensemble_top_k > 0:
        vstep = control.build_ensemble(
            lambda p: suite.validate_params(
                p, write_runs=False).metrics["MRR@10"])
        if vstep is not None:
            # policy-proof: score the soup via the normal path even when a
            # stride/budget policy would never select its step id
            validator.validate_step(vstep)
            res = next((r for r in validator.results if r.step == vstep),
                       None)
            ensemble = {"step": vstep, "members": control.ensemble_members,
                        "metrics": res.log_metrics if res else None}
    wall = time.time() - t0

    results = {
        "wall_time_s": wall,
        "mode": "sync" if args.sync else "async",
        "validated_steps": validator.ledger.validated_steps,
        "metrics": {r.step: r.log_metrics for r in validator.results},
        "errors": list(validator.errors),
        "stopped_early": trainer.stopped_early,
        "stop_verdict": trainer.stop_verdict,
        "best_step": control.selector.best_step if control else None,
        "kept_checkpoints": ckpt.list_steps(ckpt_dir) if control_on else None,
        "ensemble": ensemble,
    }
    with open(os.path.join(args.workdir, "summary.json"), "w") as f:
        json.dump(results, f, indent=1)
    print(json.dumps(results, indent=1))
    return results


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(prog="python -m repro.launch.train")
    ap.add_argument("--arch", default="dr-bert-base")
    ap.add_argument("--workdir", default="asyncval_train")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--corpus-size", type=int, default=600)
    ap.add_argument("--n-queries", type=int, default=50)
    ap.add_argument("--q-max-len", type=int, default=12)
    ap.add_argument("--p-max-len", type=int, default=28)
    ap.add_argument("--depth", type=int, default=20)
    ap.add_argument("--lr", type=float, default=2e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--subset", action="store_true")
    ap.add_argument("--sync", action="store_true")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--write-run", action="store_true",
                    help="write each validated checkpoint's TREC run to "
                         "<workdir>/runs")
    # convergence control plane (repro.control)
    ap.add_argument("--early-stop-patience", type=int, default=0,
                    help="evaluations without improvement before the "
                         "validator publishes the STOP marker (0 = off)")
    ap.add_argument("--early-stop-min-delta", type=float, default=0.0)
    ap.add_argument("--overfit-window", type=int, default=0,
                    help="history-based overfit detector window (>= 3; "
                         "0 = off)")
    ap.add_argument("--keep-top-k", type=int, default=0,
                    help="quality-aware GC: keep top-k checkpoints by "
                         "MRR@10 plus unvalidated ones (0 = keep all)")
    ap.add_argument("--ensemble-top-k", type=int, default=0,
                    help="greedy-soup the top-k checkpoints into a virtual "
                         "checkpoint after training (0 = off)")
    ap.add_argument("--policy", default="fifo",
                    choices=["fifo", "latest_first", "stride", "budget"])
    ap.add_argument("--stride", type=int, default=1)
    # lazy snapshot hand-off (repro.handoff)
    ap.add_argument("--handoff", action="store_true",
                    help="validate checkpoints from host-resident snapshots "
                         "the moment the device->host copy lands, before "
                         "the durable save commits (watcher stays the "
                         "fallback; GC/soup/promotion still wait for the "
                         "durable COMMIT)")
    ap.add_argument("--handoff-capacity", type=int, default=2,
                    help="snapshot ring size; over capacity the oldest "
                         "unclaimed snapshot is dropped and its step falls "
                         "back to the watcher path (training never blocks)")
    ap.add_argument("--handoff-spool", default="",
                    help="spill directory (e.g. under /dev/shm) mirroring "
                         "the ring for cross-process fleet workers; empty "
                         "= in-process hand-off only")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    results = run(parse_args(argv))
    return 1 if results["errors"] else 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
