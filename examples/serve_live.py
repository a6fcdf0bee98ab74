"""Live serving walkthrough — train, validate, promote, answer. One process.

The full Asyncval loop with the PR-8 serving tier closed over it:

  * a **trainer** thread runs real contrastive steps and commits a
    checkpoint every N steps (``ckpt.save``'s two-phase commit);
  * an **async validator** scores each committed checkpoint and feeds a
    :class:`ControlPlane` that ranks them (selection events);
  * a **promoter** follows the control plane's live best pick and
    hot-swaps the serving index in two phases — build off to the side,
    verify, atomic pointer flip — so the query path never blocks;
  * a **client** thread hammers :meth:`QueryService.submit` the whole
    time; every answer it gets attributes exactly one promoted
    checkpoint, scored through the validator's own encode/top-k path
    (bitwise the numbers the ledger records — see
    tests/test_serve_parity.py);
  * checkpoint **GC** runs with the serving tier's ``protect_set`` so
    the live index's backing checkpoint is never deleted out from under
    a restart.

    PYTHONPATH=src python examples/serve_live.py
"""

import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import contrastive_step, toy_spec
from repro.ckpt import checkpoint as ckpt
from repro.control import ControlConfig, ControlPlane
from repro.core.suite import (ValidationConfig, ValidationSuite,
                              ValidationTask)
from repro.core.validator import AsyncValidator
from repro.data import corpus as corpus_lib
from repro.serve import (AdmissionController, IndexBuilder, Promoter,
                         QueryService, ServeConfig, replay_swaps)

N_CKPTS = 3
STEPS_PER_CKPT = 20


def main():
    workdir = tempfile.mkdtemp(prefix="asyncval_serve_live_")
    ckdir = os.path.join(workdir, "ckpts")
    print(f"[serve-live] workdir: {workdir}")

    ds = corpus_lib.synthetic_retrieval_dataset(0, n_passages=600,
                                                n_queries=30)
    spec = toy_spec(ds.vocab)

    # -- validation + control: rank every committed checkpoint ------------
    suite = ValidationSuite(spec, [
        ValidationTask("default", ds.corpus, ds.queries, ds.qrels),
    ], ValidationConfig(metrics=("MRR@10",), k=10, batch_size=64))
    control = ControlPlane(
        ckdir, ControlConfig(metric="MRR@10", mode="max", keep_top_k=2),
        event_path=os.path.join(workdir, "control.jsonl"))

    # -- serving tier: same spec, same corpus, same scoring knobs ----------
    builder = IndexBuilder(spec, ds.corpus, ServeConfig(scoring=suite.vcfg))
    service = QueryService(spec, k=10, max_batch=8, flush_ms=2.0,
                           admission=AdmissionController(max_pending=256))
    promoter = Promoter(builder, service, ckdir,
                        target_fn=lambda: control.selector.best_step,
                        log=os.path.join(workdir, "serve.jsonl"))
    validator = AsyncValidator(ckdir, suite, controller=control,
                               ledger_path=os.path.join(workdir,
                                                        "ledger.jsonl"),
                               extra_protect=promoter.protect_set)

    # -- trainer thread: real contrastive steps, committed on a cadence ---
    def trainer():
        params = spec.init(jax.random.PRNGKey(0))
        step_fn = contrastive_step(spec)
        rng = np.random.default_rng(0)
        qids = sorted(ds.qrels)
        step = 0
        for _ in range(N_CKPTS):
            for _ in range(STEPS_PER_CKPT):
                step += 1
                pick = rng.choice(len(qids), size=32)
                q_tok = [ds.queries[qids[j]] for j in pick]
                p_tok = [ds.corpus[next(iter(ds.qrels[qids[j]]))]
                         for j in pick]
                qt, qm = corpus_lib.pad_batch(q_tok, spec.q_max_len)
                pt, pm = corpus_lib.pad_batch(p_tok, spec.p_max_len)
                params, _ = step_fn(
                    params, {"q_tokens": jnp.asarray(qt),
                             "q_mask": jnp.asarray(qm),
                             "p_tokens": jnp.asarray(pt),
                             "p_mask": jnp.asarray(pm)})
            ckpt.save(ckdir, step, {"params": params})
            print(f"[trainer] committed step {step}")

    # -- client thread: queries never stop while indexes swap under them --
    stop = threading.Event()
    responses, drops = [], []

    def client():
        qids = list(ds.queries)
        j = 0
        while not stop.is_set():
            if service.live is None:       # nothing promoted yet
                time.sleep(0.01)
                continue
            qid = qids[j % len(qids)]
            j += 1
            try:
                responses.append(service.submit(qid, ds.queries[qid],
                                                timeout=30))
            except BaseException as e:
                drops.append(repr(e))
                return

    service.start()
    t_train = threading.Thread(target=trainer)
    t_client = threading.Thread(target=client)
    t_train.start()
    t_client.start()

    # -- drive the loop: validate what lands, promote what wins -----------
    deadline = time.monotonic() + 120
    validated = set()
    while time.monotonic() < deadline:
        validator.validate_pending()
        for r in validator.results:
            if r.step not in validated:
                validated.add(r.step)
                print(f"[validator] step {r.step}: "
                      f"MRR@10={r.metrics['MRR@10']:.4f}")
        if promoter.poll_once():
            print(f"[promoter] hot-swap -> step {service.live_step()} "
                  f"(protects {sorted(promoter.protect_set())})")
        if not t_train.is_alive() and len(validated) >= N_CKPTS \
                and service.live_step() == control.selector.best_step:
            break
        time.sleep(0.05)
    time.sleep(0.5)          # let the client serve against the final pick
    stop.set()
    t_train.join()
    t_client.join()
    service.stop()

    # -- GC with the serving tier protected --------------------------------
    removed = ckpt.gc_checkpoints(ckdir, keep_last=1,
                                  protect=validator.protect_set())
    live = service.live_step()
    print(f"[gc] removed {sorted(removed)}; live step {live} survives: "
          f"{live in ckpt.list_steps(ckdir)}")

    # -- the audit: every answer came from a then-promoted checkpoint ------
    swaps = replay_swaps(os.path.join(workdir, "serve.jsonl"))
    promoted = {s["step"] for s in swaps}
    served = {r.step for r in responses}
    print(f"[audit] {len(responses)} responses, {len(drops)} drops, "
          f"swap timeline {[s['step'] for s in swaps]}, "
          f"served steps {sorted(served)}")
    assert not drops, drops
    assert served <= promoted, served - promoted
    assert live in ckpt.list_steps(ckdir), "GC deleted the live checkpoint"
    print("[serve-live] OK — zero-downtime promotion, full attribution")


if __name__ == "__main__":
    main()
