"""Cross-mode rerank parity suite — the lockdown for the mesh-complete
rerank path.

Three implementations must produce **bit-for-bit identical** runs and
scores:

  * materialized ``rerank_run`` with the query-blocked ``(Q_block, Cmax, D)``
    gather — at every block size, including the Q_block = 1, Q, and Q+1
    boundaries;
  * the streaming single-device :class:`StreamRerankStage`;
  * the streaming :class:`ShardedStreamRerankStage` on the validator mesh.

Exactness (not allclose) is achievable because every test uses
integer-valued embeddings: a pure-gather encoder over a small-integer table
and small-integer query vectors make every dot product an exactly
representable float32 regardless of reduction order, so XLA-vs-numpy and
sharded-vs-dense differences cannot introduce ulp jitter — any inequality is
a real semantic divergence.  Tie order (duplicate doc ids score exactly
equal) is pinned by the shared stable selection in
``retrieval.rank_candidates``.

The adversarial surface: ragged candidate lists, duplicate doc ids, unknown
doc ids (filtered), empty candidate sets (one query and all queries),
``k > Cmax``, chunk sizes that leave ragged tails, and candidate sets that
leave whole chunks empty (exercising the engine's chunk skipping).
Property-based exploration runs when hypothesis is installed (via the
``hypothesis_compat`` guard); a seeded fuzz loop keeps randomized coverage
in environments without it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine as E
from repro.core import retrieval as R
from repro.core.pipeline import ValidationConfig, ValidationPipeline
from repro.core.samplers import SubsetResult
from repro.models.biencoder import EncoderSpec
from tests.hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st

DIM = 8
VOCAB = 64


def _gather_encode(params, tokens, mask):
    del mask
    return jnp.take(params["table"], tokens[:, 0], axis=0)


def _int_setup(n_docs, n_queries, seed):
    """Integer-valued table/queries: exact float32 scores on every path."""
    rng = np.random.default_rng(seed)
    params = {"table": jnp.asarray(rng.integers(-4, 5, size=(VOCAB, DIM)),
                                   jnp.float32)}
    doc_texts = [[int(i % VOCAB)] for i in range(n_docs)]
    c_emb = jnp.take(params["table"],
                     jnp.asarray([t[0] for t in doc_texts]), axis=0)
    q_emb = jnp.asarray(rng.integers(-4, 5, size=(n_queries, DIM)),
                        jnp.float32)
    return params, doc_texts, c_emb, q_emb


@pytest.fixture(scope="module")
def mesh1():
    """Single-device mesh: routes through the full shard_map machinery
    (sharded specs, axis_index, hierarchical slot merge) deterministically;
    true multi-device behaviour is covered by the subprocess test in
    tests/test_distributed.py."""
    return jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))


def _drive_stage(stage, store, params, q_emb):
    """Mirror StreamingEngine's loop, including candidate chunk skipping."""
    carry = stage.init(q_emb)
    for toks, mask, base, n_valid in store.chunks():
        if not stage.wants_chunk(base // store.chunk):
            continue
        carry = stage.step(params, q_emb, carry, toks, mask, base, n_valid)
    return stage.finalize(carry)


def _check_parity(mesh, n_docs, cand_lists, *, k, chunk, seed=0):
    """Assert all rerank modes agree bit-for-bit for one scenario.

    ``cand_lists`` is one candidate-id list per query; ids may repeat, be
    unknown, or be empty lists.
    """
    Q = len(cand_lists)
    params, doc_texts, c_emb, q_emb = _int_setup(n_docs, Q, seed)
    qids = [f"q{i}" for i in range(Q)]
    dids = [f"d{i}" for i in range(n_docs)]
    per_query = {qid: list(c) for qid, c in zip(qids, cand_lists)}

    ref = R.rerank_run(qids, q_emb, dids, c_emb, per_query, k=k,
                       q_block=max(Q, 1))                  # dense gather
    # blocked materialized gather at the boundary block sizes
    for qb in (1, Q, Q + 1, None):
        got = R.rerank_run(qids, q_emb, dids, c_emb, per_query, k=k,
                           q_block=qb)
        assert got == ref, f"blocked rerank_run (q_block={qb}) diverged"

    store = E.TokenStore.build(doc_texts, max_len=2, chunk=chunk)
    single = E.StreamRerankStage(_gather_encode, k=k, query_ids=qids,
                                 doc_ids=dids, per_query=per_query,
                                 store=store)
    assert _drive_stage(single, store, params, q_emb) == ref, \
        "single-device streaming rerank diverged"

    sharded = E.ShardedStreamRerankStage(_gather_encode, mesh, k=k,
                                         query_ids=qids, doc_ids=dids,
                                         per_query=per_query, store=store)
    assert _drive_stage(sharded, store, params, q_emb) == ref, \
        "sharded streaming rerank diverged"
    return ref


# ---------------------------------------------------------------------------
# Deterministic adversarial scenarios
# ---------------------------------------------------------------------------


def test_parity_ragged_duplicate_unknown_empty(mesh1):
    """The kitchen sink: ragged lists, duplicate ids, an unknown id, an
    empty candidate list, and k far above Cmax."""
    run, scores = _check_parity(mesh1, 37, [
        ["d3", "d3", "d10", "d36"],                       # duplicates
        [],                                               # empty
        [f"d{j}" for j in range(20)] + ["nope"],          # ragged + unknown
        ["d36"],                                          # last ragged chunk
        ["d0", "d5", "d5", "d7"],
    ], k=50, chunk=8)
    assert run["q1"] == [] and scores["q1"] == []
    assert len(run["q0"]) == 4                            # dups kept, k > Cmax
    assert len(run["q2"]) == 20                           # unknown filtered


def test_parity_all_queries_empty(mesh1):
    run, scores = _check_parity(mesh1, 12, [[], [], []], k=5, chunk=4)
    assert all(v == [] for v in run.values())
    assert all(v == [] for v in scores.values())


def test_parity_duplicate_tie_order_is_slot_stable(mesh1):
    """Duplicate doc ids score exactly equal; the shared stable selection
    must order them by candidate slot on every path."""
    run, _ = _check_parity(mesh1, 10, [["d2", "d2", "d2"]], k=3, chunk=4)
    assert run["q0"] == ["d2", "d2", "d2"]


@pytest.mark.parametrize("n_docs,chunk,k", [
    (1, 1, 1),        # minimal everything
    (9, 1, 3),        # chunk=1: one row per chunk, heavy skipping
    (16, 16, 100),    # single chunk, k >> candidates
    (23, 7, 2),       # ragged tail, k < Cmax
])
def test_parity_shape_extremes(mesh1, n_docs, chunk, k):
    rng = np.random.default_rng(n_docs)
    cand_lists = [[f"d{j}" for j in rng.integers(0, n_docs, size=m)]
                  for m in (1, 0, min(5, n_docs))]
    _check_parity(mesh1, n_docs, cand_lists, k=k, chunk=chunk, seed=n_docs)


def test_parity_candidates_confined_to_one_chunk(mesh1):
    """Every other chunk is candidate-free: chunk skipping engaged on both
    streaming paths, results still identical to the full materialized run."""
    cand_lists = [["d8", "d9", "d10"], ["d11", "d8"]]
    _check_parity(mesh1, 40, cand_lists, k=10, chunk=8)


def test_rank_candidates_pads_never_surface():
    """k larger than the candidate list must stop at the list, even though
    the score matrix has -inf pad slots."""
    s = np.asarray([[3.0, -np.inf], [1.0, 2.0]], np.float32)
    run, scores = R.rank_candidates(["a", "b"], s, [["x"], ["y", "z"]], k=9)
    assert run == {"a": ["x"], "b": ["z", "y"]}
    assert scores == {"a": [3.0], "b": [2.0, 1.0]}


# ---------------------------------------------------------------------------
# Seeded fuzz (runs everywhere) + hypothesis property (when installed)
# ---------------------------------------------------------------------------


def _random_scenario(rng):
    n_docs = int(rng.integers(1, 41))
    chunk = int(rng.choice([1, 3, 8, 13]))
    Q = int(rng.integers(1, 5))
    cand_lists = []
    for _ in range(Q):
        m = int(rng.integers(0, 9))
        # j can exceed n_docs-1 -> unknown ids; repeats -> duplicates
        cand_lists.append([f"d{int(j)}"
                           for j in rng.integers(0, n_docs + 3, size=m)])
    k = int(rng.integers(1, 61))
    return n_docs, cand_lists, k, chunk


def test_parity_seeded_fuzz(mesh1):
    """Randomized cross-mode sweep that does not need hypothesis — the same
    checker the property test drives, over a fixed seed set."""
    rng = np.random.default_rng(7)
    for i in range(12):
        n_docs, cand_lists, k, chunk = _random_scenario(rng)
        _check_parity(mesh1, n_docs, cand_lists, k=k, chunk=chunk, seed=i)


@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_parity_property(seed):
    """Hypothesis-driven exploration of the same invariant (skipped when
    hypothesis is absent, see tests/hypothesis_compat.py)."""
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    rng = np.random.default_rng(seed)
    n_docs, cand_lists, k, chunk = _random_scenario(rng)
    _check_parity(mesh, n_docs, cand_lists, k=k, chunk=chunk,
                  seed=seed % 1000)


# ---------------------------------------------------------------------------
# Whole-pipeline parity: streaming (sharded + single) vs blocked materialized
# ---------------------------------------------------------------------------


class _FixedSampler:
    """Pin per-query candidates so all pipelines score the same subset."""

    name = "fixed"

    def __init__(self, per_query):
        self.per_query = per_query

    def sample(self, corpus_ids, run, qrels):
        union = sorted({d for c in self.per_query.values() for d in c
                        if d in set(corpus_ids)})
        return SubsetResult(doc_ids=union, per_query=self.per_query)


def test_pipeline_rerank_all_paths_identical(mesh1):
    """End to end through ValidationPipeline: streaming sharded, streaming
    single-device, and blocked materialized (rerank_block=1 — the worst
    case) produce identical runs, scores, and metrics."""
    n_docs, n_queries = 30, 4
    rng = np.random.default_rng(5)
    params, doc_texts, _, _ = _int_setup(n_docs, n_queries, seed=5)
    corpus = {f"d{i}": doc_texts[i] for i in range(n_docs)}
    queries = {f"q{i}": [int(rng.integers(0, VOCAB))]
               for i in range(n_queries)}
    qrels = {f"q{i}": {f"d{i}": 1} for i in range(n_queries)}
    per_query = {
        "q0": ["d1", "d1", "d4", "d29"],
        "q1": [],
        "q2": [f"d{j}" for j in range(12)],
        "q3": ["d29", "d0"],
    }
    spec = EncoderSpec(
        name="gather", dim=DIM, encode_query=_gather_encode,
        encode_passage=_gather_encode, init=lambda rng: params,
        q_max_len=2, p_max_len=2)

    def pipe(**kw):
        return ValidationPipeline(
            spec, corpus, queries, qrels,
            ValidationConfig(metrics=("MRR@10",), mode="rerank", k=10,
                             batch_size=8, chunk_size=6, **kw),
            sampler=_FixedSampler(per_query))

    outs = {}
    for name, kw in {
        "stream_sharded": dict(mesh=mesh1),
        "stream_single": dict(),
        "mat_blocked": dict(engine="materialized", rerank_block=1),
        "mat_dense": dict(engine="materialized"),
    }.items():
        p = pipe(**kw)
        run, scores, _ = p.engine.run(params)
        outs[name] = (run, scores, p.validate_params(params).metrics)
    ref = outs["mat_dense"]
    for name, got in outs.items():
        assert got == ref, f"{name} diverged from dense materialized"
