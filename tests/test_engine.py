"""Streaming ValidationEngine: TokenStore chunking, fused encode→top-k parity
with the materialized path (bit-for-bit against ``topk_exact``), rerank
streaming, pallas chunk-carry, sharded streaming, and engine injection."""

import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine as E
from repro.core import retrieval as R
from repro.core.encoder import encode_texts, jitted_encoder
from repro.core.pipeline import ValidationConfig, ValidationPipeline
from repro.core.samplers import QrelPool, RerankTopK, RunFileTopK
from repro.data import corpus as corpus_lib
from repro.models.biencoder import EncoderSpec

DIM = 16
VOCAB = 64


def _gather_encode(params, tokens, mask):
    """Pure-gather encoder: emb row = table[tokens[:, 0]] — no arithmetic, so
    streamed and materialized embeddings are bitwise identical by
    construction and any parity failure is the engine's fault."""
    del mask
    return jnp.take(params["table"], tokens[:, 0], axis=0)


def _gather_setup(N, Q, seed=0):
    rng = np.random.default_rng(seed)
    params = {"table": jnp.asarray(rng.normal(size=(VOCAB, DIM)), jnp.float32)}
    doc_texts = [[int(i % VOCAB)] for i in range(N)]
    c_emb = jnp.take(params["table"],
                     jnp.asarray([t[0] for t in doc_texts]), axis=0)
    q_emb = jnp.asarray(rng.normal(size=(Q, DIM)), jnp.float32)
    return params, doc_texts, c_emb, q_emb


def _stream_topk(stage_cls, params, q_emb, doc_texts, *, chunk, **kw):
    store = E.TokenStore.build(doc_texts, max_len=2, chunk=chunk)
    stage = stage_cls(_gather_encode,
                      query_ids=[f"q{i}" for i in range(q_emb.shape[0])],
                      doc_ids=[f"d{i}" for i in range(len(doc_texts))], **kw)
    carry = stage.init(q_emb)
    for toks, mask, base, n_valid in store.chunks():
        carry = stage.step(params, q_emb, carry, toks, mask, base, n_valid)
    return carry


# ---------------------------------------------------------------------------
# TokenStore
# ---------------------------------------------------------------------------


def test_token_store_fixed_shapes_and_ragged_tail():
    texts = [[i, i + 1] for i in range(10)]
    store = E.TokenStore.build(texts, max_len=4, chunk=4)
    assert store.n_chunks == 3
    assert store.tokens.shape == (3, 4, 4)        # every chunk one shape
    assert store.rows_valid(0) == 4 and store.rows_valid(2) == 2
    seen = []
    for toks, mask, base, n_valid in store.chunks():
        assert toks.shape == (4, 4) and mask.shape == (4, 4)
        for r in range(n_valid):
            seen.append(list(np.asarray(toks[r, :2])))
        assert not np.asarray(mask[n_valid:]).any()   # padding rows masked out
    assert seen == texts


def test_token_store_empty_and_oversized_chunk():
    assert E.TokenStore.build([], max_len=3, chunk=8).n_chunks == 0
    store = E.TokenStore.build([[1], [2]], max_len=3, chunk=100)
    assert store.n_chunks == 1 and store.rows_valid(0) == 2


# ---------------------------------------------------------------------------
# Fused streaming top-k == materialized topk_exact, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("N,chunk,k", [
    (60, 16, 10),     # ragged final chunk (60 = 3*16 + 12)
    (64, 16, 10),     # exact chunking
    (23, 7, 40),      # k > N and k > chunk
    (50, 8, 13),      # k > chunk
    (40, 40, 5),      # single chunk
    (40, 64, 5),      # chunk > N
])
def test_stream_topk_bitwise_vs_topk_exact(N, chunk, k):
    params, doc_texts, c_emb, q_emb = _gather_setup(N, Q=6)
    run_s, run_i = _stream_topk(E.StreamTopKStage, params, q_emb, doc_texts,
                                chunk=chunk, k=k)
    es, ei = R.topk_exact(q_emb, c_emb, k=k, block=chunk)
    # same chunk decomposition + same merge sequence -> identical programs:
    # scores AND indices must agree exactly, not just within tolerance.
    np.testing.assert_array_equal(np.asarray(run_s), np.asarray(es))
    np.testing.assert_array_equal(np.asarray(run_i), np.asarray(ei))


@pytest.mark.parametrize("N,chunk,k,window", [
    (60, 4, 10, 8),    # 15 chunks: windows of 8, 4 and 2 merged in pairs
    (64, 4, 10, 8),    # 16 chunks: 2 full windows exactly, g 2 of w 8
    (50, 3, 40, 4),    # ragged final chunk + k > chunk, g = w
    (150, 4, 37, 8),   # k not a multiple of chunk, ragged, tail windows,
                       # passages repeat (ties broken by id)
    (18, 4, 40, 8),    # corpus smaller than k: padding entries in the carry
    (40, 4, 12, 8),    # g 2: three chunks fit in k but do not divide w
])
def test_stream_topk_window_bitwise_vs_topk_exact(N, chunk, k, window):
    """The scan window merges every ``merge_group`` chunks, and its carry
    after each dispatch is bit for bit the per-chunk fold's at the same
    chunk, and in the end ``topk_exact``'s (scores and ids)."""
    params, doc_texts, c_emb, q_emb = _gather_setup(N, Q=5)
    store = E.TokenStore.build(doc_texts, max_len=2, chunk=chunk)
    ids = dict(query_ids=[f"q{i}" for i in range(5)],
               doc_ids=[f"d{i}" for i in range(N)])
    stage = E.StreamTopKStage(_gather_encode, k=k, window=window, **ids)
    per_chunk = E.StreamTopKStage(_gather_encode, k=k, window=1, **ids)
    carry, ref = stage.init(q_emb), per_chunk.init(q_emb)
    groups = []
    for ci, w in E.plan_schedule(store.n_chunks, window):   # as the engine
        if w > 1:
            bases = store.chunk * np.arange(ci, ci + w, dtype=np.int32)
            nvs = np.asarray([store.rows_valid(j)
                              for j in range(ci, ci + w)], np.int32)
            carry = stage.step_window(
                params, q_emb, carry, jnp.asarray(store.tokens[ci:ci + w]),
                jnp.asarray(store.mask[ci:ci + w]), bases, nvs)
            groups.append(E.merge_group(stage.k, chunk, w))
        else:
            carry = stage.step(params, q_emb, carry,
                               jnp.asarray(store.tokens[ci]),
                               jnp.asarray(store.mask[ci]),
                               store.chunk * ci, store.rows_valid(ci))
        for j in range(ci, ci + w):
            ref = per_chunk.step(params, q_emb, ref,
                                 jnp.asarray(store.tokens[j]),
                                 jnp.asarray(store.mask[j]),
                                 store.chunk * j, store.rows_valid(j))
        np.testing.assert_array_equal(np.asarray(carry[0]),
                                      np.asarray(ref[0]))
        np.testing.assert_array_equal(np.asarray(carry[1]),
                                      np.asarray(ref[1]))
    assert max(groups) > 1                       # the grouped merge ran
    es, ei = R.topk_exact(q_emb, c_emb, k=k, block=chunk)
    np.testing.assert_array_equal(np.asarray(carry[0]), np.asarray(es))
    np.testing.assert_array_equal(np.asarray(carry[1]), np.asarray(ei))


@pytest.mark.parametrize("k,chunk,w,g", [
    (1000, 64, 8, 8), (10, 64, 8, 1), (10, 64, 1, 1), (40, 4, 8, 8),
    (12, 4, 8, 2), (3, 4, 8, 1), (24, 4, 6, 6), (20, 4, 6, 3), (8, 4, 7, 1),
])
def test_merge_group_is_the_largest_divisor_within_k(k, chunk, w, g):
    assert E.merge_group(k, chunk, w) == g


@pytest.mark.parametrize("k,chunk,window,width", [
    (40, 4, 8, 40 + 8 * 4),     # k 1,000-like: one sort over the window
    (10, 4, 8, 10 + 2 * 4),     # 1 < g < w
    (3, 4, 8, 3 + 4),           # k 10-like: the per-chunk sort
])
def test_fused_window_lowers_one_sort_of_the_group_width(k, chunk, window,
                                                         width):
    """The window's program holds one sort, over (Q, k + g x chunk)."""
    import re
    Q = 5
    stage = E.StreamTopKStage(_gather_encode, k=k, window=window,
                              query_ids=[f"q{i}" for i in range(Q)],
                              doc_ids=[f"d{i}" for i in range(64)])
    q = jnp.ones((Q, DIM), jnp.float32)
    run_s, run_i = stage.init(q)
    text = stage._fused_window.lower(
        {"table": jnp.ones((VOCAB, DIM), jnp.float32)}, q, run_s, run_i,
        jnp.ones((window, chunk, 2), jnp.int32),
        jnp.ones((window, chunk, 2), bool), jnp.zeros(window, jnp.int32),
        jnp.full(window, chunk, jnp.int32)).compiler_ir("hlo").as_hlo_text()
    sorts = re.findall(r"= \(f32\[(\d+),(\d+)\][^)]*\) sort\(", text)
    assert sorts == [(str(Q), str(width))]


def test_stream_topk_finalize_reads_the_carry_as_the_old_lists(tmp_path):
    """finalize hands back views of the (Q, k) carry: equal, as mappings,
    to the run and score dicts it once built query by query, in the same
    query order, and the TREC file written from either is the same."""
    from repro.core import metrics as M
    params, doc_texts, _, q_emb = _gather_setup(60, Q=6)
    store = E.TokenStore.build(doc_texts, max_len=2, chunk=16)
    stage = E.StreamTopKStage(_gather_encode, k=10,
                              query_ids=[f"q{i}" for i in range(6)],
                              doc_ids=[f"d{i}" for i in range(60)])
    carry = stage.init(q_emb)
    for toks, mask, base, n_valid in store.chunks():
        carry = stage.step(params, q_emb, carry, toks, mask, base, n_valid)
    carry = jax.device_get(carry)
    run, scores = stage.finalize(carry)
    assert isinstance(run, M.RankedRun) and isinstance(scores,
                                                       M.RankedScores)
    old_run, old_scores = {}, {}
    for qi, qid in enumerate(stage.query_ids):
        old_run[qid] = [stage.doc_ids[j] for j in carry[1][qi]]
        old_scores[qid] = [float(s) for s in carry[0][qi]]
    assert run == old_run and scores == old_scores
    assert list(run.items()) == list(old_run.items())
    assert list(scores.items()) == list(old_scores.items())
    view, plain = tmp_path / "view.trec", tmp_path / "plain.trec"
    M.write_trec_run(str(view), run, scores, tag="t")
    M.write_trec_run(str(plain), dict(run), dict(scores), tag="t")
    assert view.read_bytes() == plain.read_bytes()
    assert len(view.read_bytes().splitlines()) == 60


@pytest.mark.parametrize("engine,path", [("streaming", "array_path"),
                                         ("materialized", "list_path")])
def test_suite_counts_the_metrics_path_once_per_verdict(ds, engine, path):
    """The streaming engine's verdicts are scored from the carry's rows, a
    plain run dict from its lists: one count a verdict, on one path."""
    from repro.core.suite import ValidationSuite, ValidationTask
    from repro.obs import Telemetry
    tel = Telemetry(None)
    spec = _toy_spec()
    vcfg = ValidationConfig(metrics=("MRR@10", "Recall@100"), k=100,
                            batch_size=64, chunk_size=64, engine=engine,
                            telemetry=tel)
    suite = ValidationSuite(spec, [ValidationTask(
        "default", ds.corpus, ds.queries, ds.qrels)], vcfg)
    params = spec.init(jax.random.PRNGKey(1))
    for step in (1, 2):
        suite.validate_params(params, step=step)
    other = {"array_path": "list_path", "list_path": "array_path"}[path]
    assert tel.metrics.get(f"metrics.{path}").value == 2
    assert tel.metrics.get(f"metrics.{other}") is None


def test_stream_pallas_matches_xla_stream():
    params, doc_texts, c_emb, q_emb = _gather_setup(45, Q=4)
    xs, xi = _stream_topk(E.StreamTopKStage, params, q_emb, doc_texts,
                          chunk=16, k=12)
    ps, pi = _stream_topk(E.PallasStreamTopKStage, params, q_emb, doc_texts,
                          chunk=16, k=12)
    np.testing.assert_allclose(np.asarray(ps), np.asarray(xs), rtol=1e-6)
    assert (np.asarray(pi) == np.asarray(xi)).mean() > 0.99


def test_stream_never_materializes_corpus_embeddings():
    """Every embedding block the encoder ever produces is chunk-sized; the
    final carry is (Q, k) — peak embedding memory O(chunk x D + Q x k)."""
    N, chunk, k, Q = 100, 16, 7, 5
    shapes = []

    def spy_encode(params, tokens, mask):
        shapes.append(tuple(tokens.shape))
        return _gather_encode(params, tokens, mask)

    params, doc_texts, _, q_emb = _gather_setup(N, Q=Q)
    store = E.TokenStore.build(doc_texts, max_len=2, chunk=chunk)
    stage = E.StreamTopKStage(spy_encode, k=k,
                              query_ids=[f"q{i}" for i in range(Q)],
                              doc_ids=[f"d{i}" for i in range(N)])
    carry = stage.init(q_emb)
    for toks, mask, base, n_valid in store.chunks():
        carry = stage.step(params, q_emb, carry, toks, mask, base, n_valid)
    assert all(s == (chunk, 2) for s in shapes)     # never (N, L)
    assert carry[0].shape == (Q, k) and carry[1].shape == (Q, k)


# ---------------------------------------------------------------------------
# Rerank streaming == vectorized rerank_run
# ---------------------------------------------------------------------------


def test_stream_rerank_matches_rerank_run():
    N, Q, k = 50, 6, 5
    params, doc_texts, c_emb, q_emb = _gather_setup(N, Q=Q)
    qids = [f"q{i}" for i in range(Q)]
    dids = [f"d{i}" for i in range(N)]
    rng = np.random.default_rng(3)
    per_query = {qid: [f"d{j}" for j in rng.choice(N, size=12, replace=False)]
                 for qid in qids}
    per_query[qids[-1]] = []                       # empty candidate list
    ref_run, ref_scores = R.rerank_run(qids, q_emb, dids, c_emb, per_query,
                                       k=k)
    store = E.TokenStore.build(doc_texts, max_len=2, chunk=16)
    stage = E.StreamRerankStage(_gather_encode, k=k, query_ids=qids,
                                doc_ids=dids, per_query=per_query)
    carry = stage.init(q_emb)
    for toks, mask, base, n_valid in store.chunks():
        carry = stage.step(params, q_emb, carry, toks, mask, base, n_valid)
    run, scores = stage.finalize(carry)
    assert run == ref_run
    for qid in qids:
        np.testing.assert_allclose(scores[qid], ref_scores[qid], rtol=1e-6)


def test_rerank_run_vectorized_matches_manual_loop():
    """The padded batched-matmul rerank matches a straightforward per-query
    reference (the old implementation's semantics)."""
    rng = np.random.default_rng(0)
    Q, N, D, k = 5, 40, 8, 6
    q = rng.normal(size=(Q, D)).astype(np.float32)
    c = rng.normal(size=(N, D)).astype(np.float32)
    qids = [f"q{i}" for i in range(Q)]
    dids = [f"d{i}" for i in range(N)]
    per_query = {qid: [f"d{j}" for j in rng.choice(N, size=9, replace=False)]
                 for qid in qids}
    per_query[qids[0]] = ["d3"]                      # single candidate
    per_query[qids[1]] = []                          # none
    per_query[qids[2]].append("unknown_doc")         # filtered out
    run, scores = R.rerank_run(qids, q, dids, c, per_query, k=k)
    doc_pos = {d: i for i, d in enumerate(dids)}
    for qi, qid in enumerate(qids):
        cands = [d for d in per_query[qid] if d in doc_pos]
        s = np.asarray([c[doc_pos[d]] @ q[qi] for d in cands])
        order = np.argsort(-s)[:k]
        assert run[qid] == [cands[j] for j in order]
        np.testing.assert_allclose(scores[qid], s[order], rtol=1e-6)


# ---------------------------------------------------------------------------
# Whole-pipeline parity: streaming engine vs legacy materialized engine
# ---------------------------------------------------------------------------


def _toy_spec():
    def enc(params, tokens, mask):
        emb = jnp.take(params["t"], tokens, axis=0)
        m = mask.astype(emb.dtype)[..., None]
        v = (emb * m).sum(1) / jnp.clip(m.sum(1), 1e-6)
        return v / jnp.clip(jnp.linalg.norm(v, axis=-1, keepdims=True), 1e-6)

    return EncoderSpec(
        name="toy", dim=DIM, encode_query=enc, encode_passage=enc,
        init=lambda rng: {"t": 0.1 * jax.random.normal(rng, (503, DIM))},
        q_max_len=8, p_max_len=20)


@pytest.fixture(scope="module")
def ds():
    return corpus_lib.synthetic_retrieval_dataset(0, n_passages=300,
                                                  n_queries=30)


@pytest.fixture(scope="module")
def baseline_run(ds):
    return corpus_lib.lexical_baseline_run(ds, k=50)


@pytest.mark.parametrize("mode,sampler_fn,impl", [
    ("retrieval", lambda: None, "xla"),
    ("retrieval", lambda: RunFileTopK(depth=10), "xla"),
    ("retrieval", lambda: None, "pallas"),
    ("rerank", lambda: RerankTopK(depth=10), "xla"),
    ("average_rank", lambda: QrelPool(pool=10), "xla"),
])
def test_pipeline_streaming_matches_materialized(ds, baseline_run, mode,
                                                 sampler_fn, impl):
    spec = _toy_spec()
    params = spec.init(jax.random.PRNGKey(1))
    kw = dict(metrics=("MRR@10", "Recall@100"), mode=mode, k=100,
              batch_size=64, impl=impl)
    for chunk in (64, 96):                         # 96 -> ragged final chunk
        ps = ValidationPipeline(
            spec, ds.corpus, ds.queries, ds.qrels,
            ValidationConfig(engine="streaming", chunk_size=chunk, **kw),
            sampler=sampler_fn(), baseline_run=baseline_run)
        pm = ValidationPipeline(
            spec, ds.corpus, ds.queries, ds.qrels,
            ValidationConfig(engine="materialized", **kw),
            sampler=sampler_fn(), baseline_run=baseline_run)
        rs = ps.validate_params(params)
        rm = pm.validate_params(params)
        assert rs.metrics == rm.metrics
        assert set(rs.timings) == set(rm.timings)  # stable ledger/CSV schema


# ---------------------------------------------------------------------------
# Encoder jit cache (the per-checkpoint retrace bug)
# ---------------------------------------------------------------------------


def test_jitted_encoder_cached_across_calls():
    traces = []

    def enc(params, tokens, mask):
        traces.append(tuple(tokens.shape))
        return jnp.take(params["t"], tokens[:, 0], axis=0)

    params = {"t": jnp.ones((8, 4), jnp.float32)}
    texts = [[1], [2], [3]]
    encode_texts(enc, params, texts, max_len=2, batch_size=2)
    n_first = len(traces)
    assert n_first >= 1
    # second checkpoint: same shapes must NOT retrace (old code re-jitted)
    encode_texts(enc, {"t": 2.0 * params["t"]}, texts, max_len=2,
                 batch_size=2)
    assert len(traces) == n_first
    assert jitted_encoder(enc) is jitted_encoder(enc)


# ---------------------------------------------------------------------------
# Sharded streaming (forced multi-device, subprocess)
# ---------------------------------------------------------------------------


def test_stream_sharded_multidevice_subprocess():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import engine as E
        from repro.core import retrieval as R

        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        rng = np.random.default_rng(0)
        N, Q, D, k, chunk = 100, 5, 16, 17, 24
        params = {"table": jnp.asarray(rng.normal(size=(64, D)), jnp.float32)}
        doc_texts = [[int(i % 64)] for i in range(N)]
        c_emb = jnp.take(params["table"],
                         jnp.asarray([t[0] for t in doc_texts]), axis=0)
        q_emb = jnp.asarray(rng.normal(size=(Q, D)), jnp.float32)

        def enc(params, tokens, mask):
            return jnp.take(params["table"], tokens[:, 0], axis=0)

        store = E.TokenStore.build(doc_texts, max_len=2, chunk=chunk)
        stage = E.ShardedStreamTopKStage(
            enc, mesh, k=k, query_ids=[f"q{i}" for i in range(Q)],
            doc_ids=[f"d{i}" for i in range(N)])
        carry = stage.init(q_emb)
        for toks, mask, base, n_valid in store.chunks():
            carry = stage.step(params, q_emb, carry, toks, mask, base,
                               n_valid)
        es, ei = R.topk_exact(q_emb, c_emb, k=k)
        np.testing.assert_allclose(np.asarray(carry[0]), np.asarray(es),
                                   rtol=1e-5)
        assert (np.asarray(carry[1]) == np.asarray(ei)).mean() > 0.99
        print("STREAM_SHARDED_OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300)
    assert "STREAM_SHARDED_OK" in out.stdout, out.stdout + out.stderr


# ---------------------------------------------------------------------------
# Engine injection into the validator
# ---------------------------------------------------------------------------


def test_validator_engine_injection(tmp_path, ds, baseline_run):
    from repro.ckpt import checkpoint as ckpt
    from repro.core.validator import AsyncValidator

    spec = _toy_spec()
    root = str(tmp_path / "ck")
    params = spec.init(jax.random.PRNGKey(0))
    ckpt.save(root, 1, {"params": params})

    pipe = ValidationPipeline(spec, ds.corpus, ds.queries, ds.qrels,
                              ValidationConfig(batch_size=64),
                              sampler=RunFileTopK(depth=5),
                              baseline_run=baseline_run)
    assert pipe.engine.name == "streaming"
    legacy = E.MaterializedEngine(
        spec, pipe.doc_texts, pipe.query_texts, mode="retrieval", k=100,
        impl="xla", batch_size=64, query_ids=pipe.query_ids,
        doc_ids=pipe.doc_ids)

    class SpyEngine:                               # proves injection is used
        name = "spy"
        runs = 0

        def run(self, params):
            SpyEngine.runs += 1
            return legacy.run(params)

    v = AsyncValidator(root, pipe, engine=SpyEngine())
    assert v.validate_pending() == 1
    assert SpyEngine.runs == 1                     # injected engine ran
    assert pipe.engine.name == "streaming"         # pipeline NOT mutated
    stream_res = ValidationPipeline(
        spec, ds.corpus, ds.queries, ds.qrels, ValidationConfig(batch_size=64),
        sampler=RunFileTopK(depth=5),
        baseline_run=baseline_run).validate_params(params, step=1)
    assert v.results[0].metrics == stream_res.metrics


@pytest.mark.parametrize("window", [1, 4])
def test_engine_places_host_params_once(window):
    """A restored checkpoint arrives as host arrays.  The engine puts it
    on the device once per run, so every jitted dispatch gets device
    arrays; a host array would be copied to the device again on each
    dispatch, and the queued dispatches would hold every copy at once."""
    from repro.serve import IndexBuilder, ServeConfig
    rng = np.random.default_rng(0)
    host = {"table": rng.normal(size=(VOCAB, DIM)).astype(np.float32)}
    spec = EncoderSpec(name="gather", dim=DIM, encode_query=_gather_encode,
                       encode_passage=_gather_encode, init=None,
                       q_max_len=2, p_max_len=2)
    docs = [[i % VOCAB] for i in range(40)]
    queries = [[i] for i in range(5)]
    stage = E.StreamTopKStage(_gather_encode, k=5,
                              query_ids=[f"q{i}" for i in range(5)],
                              doc_ids=[f"d{i}" for i in range(40)],
                              window=window)
    seen = []

    def spy(fn):
        def call(params, *args):
            seen.append(all(isinstance(x, jax.Array)
                            for x in jax.tree_util.tree_leaves(params)))
            return fn(params, *args)
        return call

    stage.step = spy(stage.step)
    stage.step_window = spy(stage.step_window)
    eng = E.StreamingEngine(
        spec, E.TokenStore.build(docs, max_len=2, chunk=8),
        E.TokenStore.build(queries, max_len=2, chunk=8), stage)
    eng.run(host)
    assert seen and all(seen)
    scfg = ServeConfig(scoring=ValidationConfig(k=5, batch_size=8))
    index = IndexBuilder(spec, {f"d{i}": t for i, t in enumerate(docs)},
                         scfg).build(host, 1)
    assert isinstance(index.params["table"], jax.Array)


# ---------------------------------------------------------------------------
# Telemetry at the engine's boundaries
# ---------------------------------------------------------------------------


def _observed_engine(telemetry, window=4, n_docs=40, k=5):
    spec = EncoderSpec(name="gather", dim=DIM, encode_query=_gather_encode,
                       encode_passage=_gather_encode, init=None,
                       q_max_len=2, p_max_len=2)
    stage = E.StreamTopKStage(_gather_encode, k=k,
                              query_ids=[f"q{i}" for i in range(5)],
                              doc_ids=[f"d{i}" for i in range(n_docs)],
                              window=window)
    docs = [[i % VOCAB] for i in range(n_docs)]
    eng = E.StreamingEngine(
        spec, E.TokenStore.build(docs, max_len=2, chunk=4),
        E.TokenStore.build([[i] for i in range(5)], max_len=2, chunk=8),
        stage, telemetry=telemetry)
    params = {"table": jnp.asarray(np.random.default_rng(0).normal(
        size=(VOCAB, DIM)), jnp.float32)}
    return eng, params


@pytest.mark.parametrize("k,window,merges", [
    (32, 8, 16 // 8),     # g 8: one merge a window
    (12, 8, 16 // 2),     # g 2
    (5, 8, 16),           # g 1: one merge a chunk
    (32, 1, 16),          # no window: one merge a step
])
def test_engine_counts_the_merges_it_dispatches(k, window, merges):
    """``merge.sorts`` adds ``w // g`` a window and 1 a step: 16 chunks
    of 4 passages, full windows of 8."""
    from repro.obs import MERGE_SORTS_METRIC, Telemetry
    tel = Telemetry(None)
    eng, params = _observed_engine(tel, window=window, n_docs=64, k=k)
    eng.run(params)
    assert tel.metrics.get(MERGE_SORTS_METRIC).value == merges


def test_engine_without_telemetry_opens_no_span_or_hook(monkeypatch):
    import gc

    import repro.obs as obs
    calls = []
    monkeypatch.setattr(obs._Span, "__enter__",
                        lambda self: calls.append(self.name) or self)
    monkeypatch.setattr(obs.Telemetry, "register_program",
                        lambda *a: calls.append("register"))
    hooks = list(gc.callbacks)
    eng, params = _observed_engine(None)
    run, _, timings = eng.run(params)
    assert len(run) == 5 and timings["retrieve_s"] >= 0
    assert calls == []
    assert gc.callbacks == hooks
    assert eng.stage.telemetry is None


def _records(path):
    from repro.obs import read_trace
    return read_trace(str(path))


def test_engine_spans_staged_per_batch(tmp_path):
    from repro.obs import Telemetry
    tel = Telemetry(str(tmp_path / "trace.jsonl"))
    eng, params = _observed_engine(tel)
    eng.run(params)
    tel.flush()
    recs = _records(tmp_path / "trace.jsonl")
    schedule = E.plan_schedule(eng.doc_store.n_chunks, 4)
    staged = [r for r in recs if r["name"] == "staged"]
    dispatch = [r for r in recs if r["name"] == "dispatch"]
    assert len(staged) == len(dispatch) == len(schedule) == 3
    wait = tel.metrics.get("engine.staging_wait_s")
    assert wait.count == 1
    assert sum(r["dur"] for r in staged) == pytest.approx(wait.total,
                                                          rel=1e-12)
    names = [r["name"] for r in recs]
    assert names.index("placed") < names.index("encoded") \
        < names.index("staged") < names.index("drain")
    assert tel.metrics.get("engine.chunk_step_s") is None
    assert tel.metrics.get("engine.staging_idle_ratio") is None


def test_engine_finalize_spans_nest_and_retrieve_s_covers_them(tmp_path):
    from repro.obs import Telemetry
    tel = Telemetry(str(tmp_path / "trace.jsonl"))
    eng, params = _observed_engine(tel)
    _, _, timings = eng.run(params)
    tel.flush()
    by = {r["name"]: r for r in _records(tmp_path / "trace.jsonl")}
    fin, fetch, build = (by["finalize"], by["finalize.fetch"],
                         by["finalize.build"])
    assert fetch["parent"] == fin["id"] and build["parent"] == fin["id"]
    assert fetch["t0"] + fetch["dur"] <= build["t0"]
    assert timings["retrieve_s"] >= fetch["dur"] + build["dur"]
    # the in-memory copy of every span agrees with the trace file
    recent = {n: (t0, t1) for n, t0, t1 in tel.recent_spans}
    assert recent["finalize.build"] == pytest.approx(
        (build["t0"], build["t0"] + build["dur"]))


def test_engine_registers_each_program_once(tmp_path):
    from repro.obs import SCOPES, Telemetry
    tel = Telemetry(None)
    eng, params = _observed_engine(tel, window=4, n_docs=40)
    eng.run(params)
    n = len(tel.device_scopes)
    # 10 chunks at window 4: windows of 4 and 2 — two programs
    assert len(tel._programs) == 2 and n > 0
    assert set(tel.device_scopes.values()) <= set(SCOPES)
    assert tel.device_programs == {"jit_fused_window"}
    eng.run(params)
    assert len(tel._programs) == 2 and len(tel.device_scopes) == n


def test_hlo_scopes_cover_the_fused_step():
    """Every non-parameter instruction of the CPU-compiled fused step
    (encode -> score -> top-k merge of one chunk), outside the scalar
    reducers and comparators, lies in exactly one of the scopes, and each
    of the three a trunk without experts has (encode, score, merge) holds
    some."""
    import re

    from repro.models import nn
    from repro.models.biencoder import biencoder_spec
    from repro.models.transformer import TransformerConfig
    from repro.obs import SCOPES, hlo_scopes
    from repro.obs.scopes import _parse, _profile_text
    cfg = TransformerConfig(name="tiny", n_layers=1, d_model=32, n_heads=2,
                            n_kv_heads=2, head_dim=16, d_ff=64,
                            vocab_size=97, use_rope=False, causal=False,
                            max_position_embeddings=16, norm_style="post",
                            act="gelu", qkv_bias=True)
    spec = biencoder_spec(cfg, pooling="mean", q_max_len=8, p_max_len=16)
    params = nn.materialize(spec.init(jax.random.PRNGKey(0)))
    stage = E.StreamTopKStage(spec.encode_passage, k=3,
                              query_ids=["q0", "q1"],
                              doc_ids=[f"d{i}" for i in range(8)], window=1)
    q = jnp.ones((2, spec.dim), jnp.float32)
    run_s, run_i = stage.init(q)
    toks = jnp.ones((8, 16), jnp.int32)
    mask = jnp.ones((8, 16), bool)
    compiled = stage._fused.lower(params, q, run_s, run_i, toks, mask,
                                  jnp.int32(0), jnp.int32(8)).compile()
    text = compiled.as_text()
    scopes = hlo_scopes(compiled)
    # scalar reducers and sort comparators run inside another instruction
    applied = set(re.findall(
        r"(?:to_apply=|comparator=|called_computations=\{)(%[\w.\-]+)",
        text))
    instrs, comp = [], None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?(%[\w.\-]+) .*\{$", line)
        if head:
            comp = head.group(1)
            continue
        ins = _parse(line)
        if ins is not None and ins.opcode != "parameter" \
                and comp not in applied:
            instrs.append(ins)
    types = {i.name: i.type for i in map(_parse, text.splitlines()) if i}
    assert instrs
    assert all(scopes.get(_profile_text(i, types)) in SCOPES
               for i in instrs)
    assert set(scopes.values()) == {"encode", "score", "merge"}


def _tiny_bert_fused_window_hlo(spec, encode_fn) -> str:
    """The fused window (two chunks) of ``spec``'s trunk encoding passages
    with ``encode_fn``, as HLO before optimisation, ``metadata`` stripped."""
    import re

    from repro.models import nn
    params = nn.materialize(spec.init(jax.random.PRNGKey(0)))
    stage = E.StreamTopKStage(encode_fn, k=3, query_ids=["q0", "q1"],
                              doc_ids=[f"d{i}" for i in range(16)], window=2)
    q = jnp.ones((2, 32))
    run_s, run_i = stage.init(q)
    lowered = stage._fused_window.lower(
        params, q, run_s, run_i, jnp.ones((2, 8, 16), jnp.int32),
        jnp.ones((2, 8, 16), bool), jnp.zeros(2, jnp.int32),
        jnp.full(2, 8, jnp.int32))
    return re.sub(r",? metadata=\{[^}]*\}", "",
                  lowered.compiler_ir("hlo").as_hlo_text())


@pytest.mark.parametrize("pooling", ["cls", "mean"])
def test_fused_step_of_a_trunk_without_experts_is_unchanged(pooling):
    """A trunk without expert layers offers no counted encoder, so its
    stage keeps the two-array carry, and its fused window holds the same
    instructions as one whose encoder calls the trunk as it was called
    before the encode path had a route mask (``forward`` with the key mask
    alone, pooled and normalised the same way)."""
    from repro.models import transformer as tfm
    from repro.models.biencoder import biencoder_spec
    cfg = tfm.TransformerConfig(name="tiny", n_layers=1, d_model=32,
                                n_heads=2, n_kv_heads=2, head_dim=16,
                                d_ff=64, vocab_size=97, use_rope=False,
                                causal=False, max_position_embeddings=16,
                                norm_style="post", act="gelu", qkv_bias=True)
    spec = biencoder_spec(cfg, pooling=pooling, q_max_len=8, p_max_len=16)
    assert spec.encode_passage_counted is None

    def unrouted(params, tokens, mask):
        hidden, _, _ = tfm.forward(params, cfg, tokens, kv_mask=mask)
        if pooling == "cls":
            emb = hidden[:, 0]
        else:
            m = mask.astype(hidden.dtype)[..., None]
            emb = (hidden * m).sum(1) / jnp.clip(m.sum(1), 1e-6)
        emb = emb.astype(jnp.float32)
        return emb / jnp.clip(jnp.linalg.norm(emb, axis=-1, keepdims=True),
                              1e-6)

    got = _tiny_bert_fused_window_hlo(spec, spec.encode_passage)
    assert got == _tiny_bert_fused_window_hlo(spec, unrouted)
