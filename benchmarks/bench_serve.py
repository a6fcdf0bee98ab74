"""Serving-tier SLO bench: tail latency and swap blackout under load.

Threaded clients hammer the QueryService while the promoter hot-swaps
through a sequence of checkpoints.  The zero-downtime claim becomes two
gates: (1) NO query is dropped, rejected, or mis-attributed across >= 3
promotions — every response names exactly one promoted checkpoint and
the admission controller never sheds load; (2) p99 latency stays under a
toy-corpus bound (widened by ``ASYNCVAL_BENCH_TIME_SLACK``) — a swap
that blocked the request path would spike the tail far past it.
"""

from __future__ import annotations

import os
import shutil
import tempfile
import threading
import time

from benchmarks.common import Timer, toy_spec, train_toy_dr
from repro.ckpt import checkpoint as ckpt
from repro.core.suite import ValidationConfig
from repro.data import corpus as corpus_lib
from repro.serve import (AdmissionController, IndexBuilder, Promoter,
                         QueryService, ServeConfig, replay_swaps)

# generous toy-corpus tail bound: a 600-passage index answers in a few
# ms; a swap that held the request path for one index build would push
# the tail past this by an order of magnitude
P99_BOUND_S = 2.0


def run(n_passages: int = 600, n_queries: int = 24, n_clients: int = 4,
        n_promotions: int = 3, settle_s: float = 0.25, seed: int = 0):
    ds = corpus_lib.synthetic_retrieval_dataset(
        seed, n_passages=n_passages, n_queries=n_queries)
    spec = toy_spec(ds.vocab)
    _, snaps = train_toy_dr(ds, spec, steps=20 * n_promotions,
                            snapshot_every=20)
    workdir = tempfile.mkdtemp(prefix="asyncval_serve_")
    try:
        ckdir = os.path.join(workdir, "ckpts")
        for step, params in snaps:
            ckpt.save(ckdir, step, {"params": params})
        steps = [s for s, _ in snaps]

        builder = IndexBuilder(spec, ds.corpus, ServeConfig(
            scoring=ValidationConfig(k=10, batch_size=64)))
        admission = AdmissionController(max_pending=256)
        service = QueryService(spec, k=10, max_batch=8, flush_ms=2.0,
                               admission=admission)
        target = {"step": steps[0]}
        promoter = Promoter(builder, service, ckdir,
                            target_fn=lambda: target["step"],
                            log=os.path.join(workdir, "serve.jsonl"))
        assert promoter.poll_once(), "initial promotion must succeed"
        service.start()

        stop = threading.Event()
        responses, errors = [], []

        def client(i):
            qids = list(ds.queries)
            j = 0
            while not stop.is_set():
                qid = qids[(i + j) % len(qids)]
                j += 1
                try:
                    responses.append(
                        service.submit(qid, ds.queries[qid], timeout=30))
                except BaseException as e:   # any drop IS a blackout
                    errors.append((qid, repr(e)))
                    return

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n_clients)]
        with Timer() as total:
            for t in threads:
                t.start()
            for want in steps[1:]:           # promote under sustained load
                time.sleep(settle_s)
                target["step"] = want
                while not promoter.poll_once():
                    time.sleep(0.01)
            time.sleep(settle_s)
            stop.set()
            for t in threads:
                t.join()
        service.stop()

        lat = sorted(r.latency_s for r in responses)
        p50 = lat[len(lat) // 2]
        p99 = lat[min(len(lat) - 1, int(len(lat) * 0.99))]
        swaps = replay_swaps(os.path.join(workdir, "serve.jsonl"))
        return {
            "n_responses": len(responses), "n_errors": len(errors),
            "errors": errors[:3], "rejected": admission.rejected,
            "p50_ms": p50 * 1e3, "p99_ms": p99 * 1e3,
            "n_swaps": len(swaps),
            "swap_steps": [s["step"] for s in swaps],
            "served_steps": sorted({r.step for r in responses}),
            "promoter_failures": len(promoter.failures),
            "total_s": total.seconds,
        }
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def main():
    slack = float(os.environ.get("ASYNCVAL_BENCH_TIME_SLACK", "1.05"))
    r = run()
    print("name,n_responses,n_swaps,p50_ms,p99_ms,rejected,errors,total_s")
    print(f"serve,{r['n_responses']},{r['n_swaps']},{r['p50_ms']:.2f},"
          f"{r['p99_ms']:.2f},{r['rejected']},{r['n_errors']},"
          f"{r['total_s']:.2f}")

    # gate 1 — zero-downtime across >= 3 promotions: nothing dropped,
    # nothing shed, nothing failed, and every response attributes exactly
    # one promoted checkpoint
    assert r["n_swaps"] >= 3, f"expected >=3 promotions, got {r['n_swaps']}"
    assert r["promoter_failures"] == 0
    assert r["n_errors"] == 0, f"dropped queries: {r['errors']}"
    assert r["rejected"] == 0, f"admission shed {r['rejected']} requests"
    assert r["n_responses"] > 0
    assert set(r["served_steps"]) <= set(r["swap_steps"]), \
        (f"responses attributed non-promoted steps: "
         f"{set(r['served_steps']) - set(r['swap_steps'])}")

    # gate 2 — swap blackout: the tail must not see an index build
    bound = P99_BOUND_S * slack
    assert r["p99_ms"] / 1e3 <= bound, \
        f"p99 {r['p99_ms']:.1f}ms exceeds blackout bound {bound * 1e3:.0f}ms"
    return r


if __name__ == "__main__":
    main()
