"""Paper Figure 2 (right): per-checkpoint validation time vs subset depth.

The paper: full corpus ~2 h, top-1000 ~1 h, top-100 ~10 min on MS MARCO.
Here: wall-clock validation time across subset depths on the synthetic
corpus — the shape of the scaling (linear in encoded passages, dominated by
corpus encoding) is the reproduced artifact.

A traced run of the double-buffered streaming config prints the
lifecycle tracer's (``repro.obs``) per-stage breakdown, inclusive and self
times, and GATES the staging wait share (the per-batch ``staged`` spans of
one engine run over its ``scored`` span: host time spent waiting on
host→device staging) below 10% — the measured form of PR 2's "the device
never waits on copies" claim, on the host's clock.
"""

from __future__ import annotations

import os
import shutil
import tempfile

from benchmarks.common import toy_spec, train_toy_dr
from repro.core.samplers import FullCorpus, RunFileTopK
from repro.core.suite import (ValidationConfig, ValidationSuite,
                              ValidationTask)
from repro.core.validator import ValidationLedger
from repro.data import corpus as corpus_lib
from repro.obs import Telemetry
from repro.obs.export import breakdown_table, load_traces

# shared CI knob: loosen timing-sensitive gates on noisy runners
SLACK = float(os.environ.get("ASYNCVAL_BENCH_TIME_SLACK", "1.0"))
WAIT_GATE = 0.10 * SLACK


def _make_suite(ds, spec, sampler, baseline, *, engine: str,
                telemetry=None) -> ValidationSuite:
    vcfg = ValidationConfig(metrics=("MRR@10",), k=100, batch_size=128,
                            engine=engine, telemetry=telemetry)
    return ValidationSuite(spec, [
        ValidationTask("default", ds.corpus, ds.queries, ds.qrels,
                       sampler=sampler, baseline_run=baseline)], vcfg)


def run(corpus_size: int = 4000, n_queries: int = 60,
        depths=(5, 20, 50, 200), seed: int = 0, repeats: int = 3,
        engine: str = "streaming"):
    ds = corpus_lib.synthetic_retrieval_dataset(
        seed, n_passages=corpus_size, n_queries=n_queries)
    baseline = corpus_lib.lexical_baseline_run(ds, k=max(depths))
    spec = toy_spec(ds.vocab)
    params, _ = train_toy_dr(ds, spec, steps=50, seed=seed)

    rows = []
    samplers = [("full", FullCorpus())] + \
        [(f"top{d}", RunFileTopK(depth=d)) for d in depths]
    for name, sampler in samplers:
        suite = _make_suite(ds, spec, sampler, baseline, engine=engine)
        suite.validate_params(params)           # warm-up (jit compile)
        times, encode_times = [], []
        for r in range(repeats):
            res = suite.validate_params(params, step=r).tasks["default"]
            times.append(res.timings["total_s"])
            encode_times.append(res.timings["encode_corpus_s"])
        rows.append({"engine": engine, "subset": name,
                     "size": res.subset_size,
                     "total_s": min(times),
                     "encode_s": min(encode_times),
                     "mrr": res.metrics["MRR@10"]})
    return rows


def run_breakdown(corpus_size: int = 4000, n_queries: int = 60,
                  seed: int = 0, repeats: int = 3):
    """Trace full-corpus validations of the DOUBLE-BUFFERED streaming
    config (the ValidationConfig default: staging="double_buffered",
    depth 2); returns (trace records, post-warm-up staging wait shares).

    The warm-up run stays in the trace — it is where store_build and the
    compile-heavy first spans live, so the printed table covers every
    stage — but the wait-share GATE reads only the steady-state runs."""
    ds = corpus_lib.synthetic_retrieval_dataset(
        seed, n_passages=corpus_size, n_queries=n_queries)
    spec = toy_spec(ds.vocab)
    params, _ = train_toy_dr(ds, spec, steps=50, seed=seed)

    workdir = tempfile.mkdtemp(prefix="asyncval_obs_bench_")
    trace = os.path.join(workdir, "trace.jsonl")
    tel = Telemetry(trace, process="bench")
    suite = _make_suite(ds, spec, FullCorpus(), None,
                        engine="streaming", telemetry=tel)
    ledger = ValidationLedger(os.path.join(workdir, "ledger.jsonl"),
                              expected_tasks=suite.task_names,
                              telemetry=tel)
    try:
        suite.validate_params(params)           # warm-up (jit compile)
        tel.flush()
        n_warm = len(load_traces([trace]))
        for r in range(1, repeats + 1):
            ledger.record(suite.validate_params(params, step=r))
        tel.flush()
        records = load_traces([trace])
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    steady = records[n_warm:]
    waits = {}
    for rec in steady:
        if rec["name"] == "staged":
            waits[rec["parent"]] = waits.get(rec["parent"], 0.0) + rec["dur"]
    shares = [waits.get(rec["id"], 0.0) / rec["dur"] for rec in steady
              if rec["name"] == "scored" and rec["dur"] > 0]
    return records, shares


def main():
    print("name,engine,subset,passages,total_s,encode_s,mrr")
    by_engine = {}
    for engine in ("streaming", "materialized"):
        rows = by_engine[engine] = run(engine=engine)
        for r in rows:
            print(f"validation_time,{r['engine']},{r['subset']},{r['size']},"
                  f"{r['total_s']:.3f},{r['encode_s']:.3f},{r['mrr']:.4f}")
        full = next(r for r in rows if r["subset"] == "full")
        small = min(rows, key=lambda r: r["size"])
        print(f"validation_time,{engine},speedup_full_vs_smallest,"
              f"{full['total_s']/max(small['total_s'],1e-9):.2f},,,")
        assert small["total_s"] <= full["total_s"], \
            "subset validation must be faster than full-corpus validation"
    # both engines must agree on every subset's metric (same checkpoints;
    # 1e-6: separately-compiled programs may differ by an ulp in scores)
    for rs, rm in zip(by_engine["streaming"], by_engine["materialized"]):
        assert abs(rs["mrr"] - rm["mrr"]) < 1e-6, (rs, rm)

    # per-stage breakdown from the lifecycle tracer + staging wait gate
    records, shares = run_breakdown()
    print("\nper-stage breakdown (traced, incl. warm-up/compile run):")
    print(breakdown_table(records))
    assert shares, "no steady-state scored spans traced"
    mean_wait = sum(shares) / len(shares)
    print(f"validation_time,staging_wait_share,{mean_wait:.4f},"
          f"gate<{WAIT_GATE:.3f},,,")
    # PR 2's double-buffering claim, continuously measured: an engine run
    # must spend <10% of its wall time waiting on host->device staging
    assert mean_wait < WAIT_GATE, \
        f"staging wait share {mean_wait:.3f} >= {WAIT_GATE:.3f} in the " \
        "double-buffered config"
    return by_engine["streaming"] + [
        {"subset": "staging_wait", "mean_wait_share": mean_wait,
         "gate": WAIT_GATE, "n_runs": len(shares)}]


if __name__ == "__main__":
    main()
