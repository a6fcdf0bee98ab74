"""Streaming vs materialized validation engine: the memory/time win —
plus the staging-overlap case (out-of-core mmap TokenStore, double-buffered
vs synchronous host→device staging) and the rerank-at-scale case
(query-blocked vs dense materialized candidate gather; sharded vs
single-device streaming rerank).

The legacy path materializes the full (N, D) corpus embedding matrix on host
(one ``np.asarray`` per batch) and copies it back to device for retrieval.
The streaming engine fuses encode→top-k per chunk so peak embedding memory is
``O(chunk x D + Q x k)`` regardless of N — corpora larger than host RAM
become validatable.  This bench measures, at EQUAL chunk size (streaming
chunk == legacy encode batch):

  * wall-clock per checkpoint — streaming must be no worse (it skips the
    device→host→device round trip and the (N, D) concat), and
    double-buffered staging must be no worse than synchronous staging
    (the device_put of chunk i+1 overlaps chunk i's fused step);
  * the peak embedding AND host-token footprints *implied by each path's
    data flow* (analytic accounting, not a process measurement — the
    structural guarantees are enforced by the encoder-shape spy and
    prefetch-depth tests in tests/test_engine.py and
    tests/test_engine_staging.py).  With an mmap-backed store the host
    only ever holds the staged batches: O(depth x window x chunk x L);
  * metric parity — every path scores identically.
"""

from __future__ import annotations

import os
import shutil
import tempfile
import time

from benchmarks.common import toy_spec, train_toy_dr
from repro.core.pipeline import ValidationConfig, ValidationPipeline
from repro.data import corpus as corpus_lib

TOK_BYTES = 4 + 1                    # int32 token + 1-byte bool mask per slot


def run(corpus_size: int = 8000, n_queries: int = 60, chunk: int = 256,
        k: int = 100, seed: int = 0, repeats: int = 9):
    ds = corpus_lib.synthetic_retrieval_dataset(
        seed, n_passages=corpus_size, n_queries=n_queries)
    spec = toy_spec(ds.vocab)
    params, _ = train_toy_dr(ds, spec, steps=50, seed=seed)
    mmap_dir = tempfile.mkdtemp(prefix="asyncval_tokens_")
    try:
        return _run_variants(ds, spec, params, mmap_dir, chunk=chunk, k=k,
                             repeats=repeats, corpus_size=corpus_size,
                             n_queries=n_queries)
    finally:
        shutil.rmtree(mmap_dir, ignore_errors=True)


def _run_variants(ds, spec, params, mmap_dir, *, chunk, k, repeats,
                  corpus_size, n_queries):
    # staging-overlap case runs window=1 so both staged variants carry the
    # ISSUE's O(2 x chunk x L) host-token bound (and sync is O(1 x ...))
    variants = {
        "materialized": dict(engine="materialized"),
        "streaming": dict(engine="streaming"),
        "stream_mmap_sync": dict(engine="streaming", staging="sync",
                                 token_backing="mmap", mmap_dir=mmap_dir,
                                 scan_window=1),
        "stream_mmap_dbuf": dict(engine="streaming",
                                 staging="double_buffered",
                                 token_backing="mmap", mmap_dir=mmap_dir,
                                 scan_window=1),
    }
    pipes = {}
    for name, kw in variants.items():
        vcfg = ValidationConfig(metrics=("MRR@10",), k=k, batch_size=chunk,
                                chunk_size=chunk, **kw)
        pipes[name] = ValidationPipeline(spec, ds.corpus, ds.queries,
                                         ds.qrels, vcfg)
        pipes[name].validate_params(params)        # warm-up (jit compile)

    # interleave the engines per repeat so machine-load drift hits both
    # equally; min-of-repeats then compares best-case against best-case.
    times = {e: [] for e in variants}
    results = {}
    for r in range(repeats):
        for name in variants:
            res = pipes[name].validate_params(params, step=r)
            times[name].append(res.timings["total_s"])
            results[name] = res

    n, d, q, L = corpus_size, spec.dim, n_queries, spec.p_max_len
    n_chunks = -(-n // chunk)
    rows = []
    for name in variants:
        # analytic footprints from the data-flow shapes (module docstring)
        peak_emb = (n * d * 4 if name == "materialized"
                    else chunk * d * 4 + q * k * 8)  # f32 emb + (f32,i32) carry
        if name == "materialized" or name == "streaming":
            # host-resident TokenStore (or per-batch pads over the full pass)
            peak_tok = n_chunks * chunk * L * TOK_BYTES
        else:
            depth = 2 if name.endswith("dbuf") else 1
            peak_tok = depth * chunk * L * TOK_BYTES
        rows.append({"engine": name, "total_s": min(times[name]),
                     "peak_emb_bytes": peak_emb,
                     "peak_host_tok_bytes": peak_tok,
                     "mrr": results[name].metrics["MRR@10"]})
    return rows, results


def run_rerank(n_queries: int = 2048, cmax: int = 256,
               corpus_size: int = 4096, dim: int = 16, chunk: int = 256,
               mem_shrink: int = 16, seed: int = 0, repeats: int = 5):
    """Rerank at scale: Q=2048 queries x Cmax=256 candidates (the ISSUE's
    acceptance point), four paths over identical integer-valued embeddings
    (exact float32 dot products, so every path must agree bit for bit):

      * ``rerank_dense``   — materialized, one (Q, Cmax, D) gather;
      * ``rerank_blocked`` — materialized, (Q_block, Cmax, D) per gather
        with Q_block = Q/``mem_shrink`` — peak candidate-block memory drops
        ``mem_shrink``-fold while wall time must stay within 10%;
      * ``rerank_stream``  — streaming single-device StreamRerankStage;
      * ``rerank_sharded`` — streaming ShardedStreamRerankStage on a mesh
        over every local device (1 on the CPU CI host; the multi-device
        behaviour is exercised by tests/test_distributed.py).

    Peak candidate-block bytes are analytic (Q_block x Cmax x D x 4), like
    the module's other footprints: the blocked loop provably never holds
    more than one block (the structural guarantee is the loop itself;
    parity across block sizes is enforced by tests/test_rerank_parity.py).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import engine as E
    from repro.core import retrieval as R

    rng = np.random.default_rng(seed)
    vocab = 64
    table = rng.integers(-4, 5, size=(vocab, dim)).astype(np.float32)
    doc_texts = [[int(i % vocab)] for i in range(corpus_size)]
    c = table[[t[0] for t in doc_texts]]
    q = rng.integers(-4, 5, size=(n_queries, dim)).astype(np.float32)
    qids = [f"q{i}" for i in range(n_queries)]
    dids = [f"d{i}" for i in range(corpus_size)]
    # cmax distinct candidates per query, vectorized draw
    picks = rng.permuted(np.tile(np.arange(corpus_size), (n_queries, 1)),
                         axis=1)[:, :cmax]
    per_query = {qid: [f"d{j}" for j in row]
                 for qid, row in zip(qids, picks)}

    q_block = max(1, n_queries // mem_shrink)
    k = 100

    def dense():
        return R.rerank_run(qids, q, dids, c, per_query, k=k,
                            q_block=n_queries)

    def blocked():
        return R.rerank_run(qids, q, dids, c, per_query, k=k,
                            q_block=q_block)

    params = {"table": jnp.asarray(table)}
    q_dev = jnp.asarray(q)

    def enc(params, tokens, mask):
        return jnp.take(params["table"], tokens[:, 0], axis=0)

    store = E.TokenStore.build(doc_texts, max_len=2, chunk=chunk)
    stages = {
        "rerank_stream": E.StreamRerankStage(
            enc, k=k, query_ids=qids, doc_ids=dids, per_query=per_query,
            store=store),
        "rerank_sharded": E.ShardedStreamRerankStage(
            enc, jax.make_mesh((jax.device_count(),), ("data",),
                               axis_types=(jax.sharding.AxisType.Auto,)),
            k=k,
            query_ids=qids, doc_ids=dids, per_query=per_query, store=store),
    }

    def stream(stage):
        def go():
            # honor the compacting rerank stage's packed pseudo-chunk store,
            # exactly like StreamingEngine.run
            st = getattr(stage, "store_override", None) or store
            carry = stage.init(q_dev)
            for toks, mask, base, n_valid in st.chunks():
                if not stage.wants_chunk(base // st.chunk):
                    continue
                carry = stage.step(params, q_dev, carry, toks, mask, base,
                                   n_valid)
            jax.block_until_ready(carry)
            return stage.finalize(carry)
        return go

    fns = {"rerank_dense": dense, "rerank_blocked": blocked,
           **{name: stream(stg) for name, stg in stages.items()}}
    outs = {name: fn() for name, fn in fns.items()}      # warm-up + parity
    times = {name: [] for name in fns}
    for _ in range(repeats):
        for name, fn in fns.items():                     # interleaved
            t0 = time.time()
            fn()
            times[name].append(time.time() - t0)

    cand_bytes = {"rerank_dense": n_queries * cmax * dim * 4,
                  "rerank_blocked": q_block * cmax * dim * 4,
                  # streaming never gathers candidate embeddings at all —
                  # its footprint is the (Q, Cmax) f32 score carry
                  "rerank_stream": n_queries * cmax * 4,
                  "rerank_sharded": n_queries * cmax * 4}
    rows = [{"engine": name, "total_s": min(times[name]),
             "peak_cand_bytes": cand_bytes[name]} for name in fns]
    return rows, outs


def run_rerank_sparse(n_queries: int = 256, cands_per_q: int = 4,
                      corpus_size: int = 8192, dim: int = 16,
                      chunk: int = 64, seed: int = 0, repeats: int = 5):
    """Sparse-rerank gather compaction: at very sparse candidate depths
    (here ~4 candidates/query over a 8192-doc corpus, chunk=64) nearly every
    chunk survives chunk-skipping with only a handful of candidate rows in
    it.  The compacting stage packs those rows into dense pseudo-chunks, so
    encoded rows collapse from ``surviving_chunks x chunk`` to roughly the
    unique-candidate count — bit-for-bit identical output (integer-valued
    embeddings, row-independent encoder).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import engine as E

    rng = np.random.default_rng(seed)
    vocab = 64
    table = rng.integers(-4, 5, size=(vocab, dim)).astype(np.float32)
    doc_texts = [[int(i % vocab)] for i in range(corpus_size)]
    q = rng.integers(-4, 5, size=(n_queries, dim)).astype(np.float32)
    qids = [f"q{i}" for i in range(n_queries)]
    dids = [f"d{i}" for i in range(corpus_size)]
    # spread candidates so nearly every chunk holds at least one: the
    # worst case for chunk-skipping, the best case for compaction
    picks = rng.permuted(np.tile(np.arange(corpus_size), (n_queries, 1)),
                         axis=1)[:, :cands_per_q]
    per_query = {qid: [f"d{j}" for j in row]
                 for qid, row in zip(qids, picks)}
    params = {"table": jnp.asarray(table)}
    q_dev = jnp.asarray(q)

    def enc(params, tokens, mask):
        return jnp.take(params["table"], tokens[:, 0], axis=0)

    store = E.TokenStore.build(doc_texts, max_len=2, chunk=chunk)
    kw = dict(k=10, query_ids=qids, doc_ids=dids, per_query=per_query,
              store=store)
    stages = {"rerank_plain": E.StreamRerankStage(enc, compact=False, **kw),
              "rerank_compact": E.StreamRerankStage(enc, compact=True, **kw)}
    assert stages["rerank_compact"].store_override is not None, \
        "sparse candidates must trigger gather compaction"

    def stream(stage):
        def go():
            st = getattr(stage, "store_override", None) or store
            carry = stage.init(q_dev)
            for toks, mask, base, n_valid in st.chunks():
                if not stage.wants_chunk(base // st.chunk):
                    continue
                carry = stage.step(params, q_dev, carry, toks, mask, base,
                                   n_valid)
            jax.block_until_ready(carry)
            return stage.finalize(carry)
        return go

    fns = {name: stream(stg) for name, stg in stages.items()}
    outs = {name: fn() for name, fn in fns.items()}
    times = {name: [] for name in fns}
    for _ in range(repeats):
        for name, fn in fns.items():
            t0 = time.time()
            fn()
            times[name].append(time.time() - t0)

    surviving = sum(stages["rerank_plain"].wants_chunk(ci)
                    for ci in range(store.n_chunks))
    packed = stages["rerank_compact"].store_override.n_chunks
    rows = [{"engine": "rerank_plain", "total_s": min(times["rerank_plain"]),
             "chunks_encoded": surviving},
            {"engine": "rerank_compact",
             "total_s": min(times["rerank_compact"]),
             "chunks_encoded": packed}]
    return rows, outs


def run_precision(corpus_size: int = 4000, n_queries: int = 48,
                  chunk: int = 256, k: int = 100, seed: int = 0,
                  repeats: int = 5):
    """score_dtype sweep through the full streaming validation pipeline:
    wall time, the analytic per-chunk embedding bytes the fused step moves,
    and metric proximity to the f32 run."""
    from repro.core.precision import itemsize

    ds = corpus_lib.synthetic_retrieval_dataset(
        seed, n_passages=corpus_size, n_queries=n_queries)
    spec = toy_spec(ds.vocab)
    params, _ = train_toy_dr(ds, spec, steps=50, seed=seed)
    rows, results = [], {}
    for dt in ("f32", "bf16", "int8"):
        vcfg = ValidationConfig(metrics=("MRR@10",), k=k, batch_size=chunk,
                                chunk_size=chunk, engine="streaming",
                                score_dtype=dt)
        pipe = ValidationPipeline(spec, ds.corpus, ds.queries, ds.qrels,
                                  vcfg)
        pipe.validate_params(params)                    # warm-up
        times = [pipe.validate_params(params, step=r).timings["total_s"]
                 for r in range(repeats)]
        results[dt] = pipe.validate_params(params, step=repeats)
        rows.append({"score_dtype": dt, "total_s": min(times),
                     "chunk_emb_bytes": chunk * spec.dim * itemsize(dt),
                     "mrr": results[dt].metrics["MRR@10"]})
    return rows, results


def main():
    rows, results = run()
    print("name,engine,total_s,peak_emb_bytes,peak_host_tok_bytes,mrr")
    for r in rows:
        print(f"streaming_engine,{r['engine']},{r['total_s']:.3f},"
              f"{r['peak_emb_bytes']},{r['peak_host_tok_bytes']},"
              f"{r['mrr']:.4f}")
    by = {r["engine"]: r for r in rows}
    legacy, stream = by["materialized"], by["streaming"]
    ratio = stream["total_s"] / max(legacy["total_s"], 1e-9)
    shrink = legacy["peak_emb_bytes"] / stream["peak_emb_bytes"]
    stage_ratio = (by["stream_mmap_dbuf"]["total_s"]
                   / max(by["stream_mmap_sync"]["total_s"], 1e-9))
    tok_shrink = (stream["peak_host_tok_bytes"]
                  / by["stream_mmap_dbuf"]["peak_host_tok_bytes"])
    print(f"streaming_engine,time_ratio_stream_over_legacy,{ratio:.3f},,,")
    print(f"streaming_engine,peak_memory_shrink_x,{shrink:.1f},,,")
    print(f"streaming_engine,time_ratio_dbuf_over_sync,{stage_ratio:.3f},,,")
    print(f"streaming_engine,host_token_shrink_x,{tok_shrink:.1f},,,")
    # metric parity with a 1e-6 epsilon: the paths are separately compiled
    # XLA programs, so a compiler upgrade may legally shift scores by an ulp
    # and flip a near-tie rank (exact equality lives in tests/test_engine.py
    # and tests/test_engine_staging.py where sides share program structure).
    for name, v in results["streaming"].metrics.items():
        for other in ("materialized", "stream_mmap_sync", "stream_mmap_dbuf"):
            assert abs(v - results[other].metrics[name]) < 1e-6, \
                (name, other, v, results[other].metrics[name])
    assert stream["peak_emb_bytes"] < legacy["peak_emb_bytes"], \
        "streaming peak embedding memory must be below the (N, D) matrix"
    # out-of-core: host tokens bounded by the double buffer, O(2 x chunk x L)
    assert by["stream_mmap_dbuf"]["peak_host_tok_bytes"] \
        < stream["peak_host_tok_bytes"], \
        "mmap + staged tokens must undercut the host-resident TokenStore"
    # wall-clock gates: 1.05 by default; CI runners are noisy shared
    # tenants, so the workflow widens the slack rather than flaking
    # unrelated PRs.
    slack = float(os.environ.get("ASYNCVAL_BENCH_TIME_SLACK", "1.05"))
    assert ratio <= slack, \
        f"streaming wall-time must be no worse than legacy " \
        f"(ratio={ratio:.3f} > slack={slack})"
    assert stage_ratio <= slack, \
        f"double-buffered staging must be no worse than synchronous " \
        f"(ratio={stage_ratio:.3f} > slack={slack})"

    # -- rerank at scale: Q=2048, Cmax=256 ---------------------------------
    rrows, routs = run_rerank()
    print("name,engine,total_s,peak_cand_bytes,,")
    for r in rrows:
        print(f"rerank_scale,{r['engine']},{r['total_s']:.3f},"
              f"{r['peak_cand_bytes']},,")
    rby = {r["engine"]: r for r in rrows}
    mem_ratio = (rby["rerank_dense"]["peak_cand_bytes"]
                 / rby["rerank_blocked"]["peak_cand_bytes"])
    rr_time = (rby["rerank_blocked"]["total_s"]
               / max(rby["rerank_dense"]["total_s"], 1e-9))
    sh_time = (rby["rerank_sharded"]["total_s"]
               / max(rby["rerank_stream"]["total_s"], 1e-9))
    print(f"rerank_scale,cand_block_shrink_x,{mem_ratio:.1f},,,")
    print(f"rerank_scale,time_ratio_blocked_over_dense,{rr_time:.3f},,,")
    print(f"rerank_scale,time_ratio_sharded_over_single,{sh_time:.3f},,,")
    # integer-valued embeddings: every rerank path must agree bit for bit
    # (runs AND scores), not just to a metric epsilon.
    for name, got in routs.items():
        assert got == routs["rerank_dense"], \
            f"rerank path {name} diverged from the dense gather"
    assert mem_ratio >= 8, \
        f"blocked gather must cut peak candidate-block memory >= 8x " \
        f"(got {mem_ratio:.1f}x)"
    # acceptance bar: blocked within 10% of the dense gather's wall time
    # (same CI noise widening as the other wall-clock gates)
    rr_slack = 1.10 * slack / 1.05
    assert rr_time <= rr_slack, \
        f"blocked rerank gather must stay within 10% of dense wall time " \
        f"(ratio={rr_time:.3f} > {rr_slack:.3f})"

    # -- sparse-rerank gather compaction (PR-6) ----------------------------
    srows, souts = run_rerank_sparse()
    print("name,engine,total_s,chunks_encoded,,")
    for r in srows:
        print(f"rerank_sparse,{r['engine']},{r['total_s']:.3f},"
              f"{r['chunks_encoded']},,")
    sby = {r["engine"]: r for r in srows}
    chunk_shrink = (sby["rerank_plain"]["chunks_encoded"]
                    / max(sby["rerank_compact"]["chunks_encoded"], 1))
    print(f"rerank_sparse,chunks_encoded_shrink_x,{chunk_shrink:.1f},,,")
    assert souts["rerank_compact"] == souts["rerank_plain"], \
        "compacted sparse rerank diverged from the plain stream"
    assert chunk_shrink >= 2, \
        f"gather compaction must at least halve encoded chunks at sparse " \
        f"depths (got {chunk_shrink:.1f}x)"

    # -- score_dtype sweep through the streaming pipeline (PR-6) -----------
    prows, presults = run_precision()
    print("name,score_dtype,total_s,chunk_emb_bytes,mrr,")
    for r in prows:
        print(f"stream_precision,{r['score_dtype']},{r['total_s']:.3f},"
              f"{r['chunk_emb_bytes']},{r['mrr']:.4f},")
    pby = {r["score_dtype"]: r for r in prows}
    emb_shrink = (pby["f32"]["chunk_emb_bytes"]
                  / pby["bf16"]["chunk_emb_bytes"])
    print(f"stream_precision,bf16_chunk_emb_shrink_x,{emb_shrink:.1f},,,")
    assert emb_shrink >= 2.0, \
        "bf16 must halve the per-chunk embedding bytes the step moves"
    for dt in ("bf16", "int8"):
        delta = abs(pby[dt]["mrr"] - pby["f32"]["mrr"])
        assert delta <= 0.05, \
            f"{dt} validation must stay near the f32 metric " \
            f"(|delta MRR@10|={delta:.4f})"
    return rows


if __name__ == "__main__":
    main()
