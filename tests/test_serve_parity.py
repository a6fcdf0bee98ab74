"""Serve<->validate bit-parity: serving numbers ARE validation numbers.

Kim et al. 2022's training-inference gap, as an executable claim: for a
fixed checkpoint, the QueryService's answers (doc ids + scores + tie-break
order) must be bit-identical to what ``ValidationSuite.validate_params``
scored — across every ``score_dtype`` (f32/bf16/int8), sharded and
single-device, through the real micro-batching request path with its
fixed-shape padding and arbitrary batch boundaries.
"""

import concurrent.futures

import jax
import numpy as np
import pytest

from tests.hypothesis_compat import given, settings, st

from benchmarks.common import toy_spec, train_toy_dr
from repro.core import metrics as metrics_lib
from repro.core.suite import (ValidationConfig, ValidationSuite,
                              ValidationTask)
from repro.data import corpus as corpus_lib
from repro.serve import IndexBuilder, QueryService, ServeConfig

K = 10


@pytest.fixture(scope="module")
def setup():
    ds = corpus_lib.synthetic_retrieval_dataset(0, n_passages=240,
                                                n_queries=12)
    spec = toy_spec(ds.vocab)
    _, snaps = train_toy_dr(ds, spec, steps=20, snapshot_every=20)
    return ds, spec, snaps[-1][1]


def _vcfg(*, score_dtype="f32", mesh=None, impl="xla", chunk_size=None):
    return ValidationConfig(metrics=("MRR@10",), k=K, batch_size=32,
                            score_dtype=score_dtype, mesh=mesh, impl=impl,
                            chunk_size=chunk_size)


def _suite(ds, spec, **kw):
    return ValidationSuite(spec, [
        ValidationTask("default", ds.corpus, ds.queries, ds.qrels)],
        _vcfg(**kw))


def _serve_run(ds, spec, params, *, max_batch=5, threaded=True, step=7,
               **kw):
    """Answer every query through the REAL request path: a started
    micro-batcher with concurrent submits (arbitrary batch packing), or
    the synchronous ``answer`` path when ``threaded`` is False.  The index
    scores with the suite's own config (``_vcfg(**kw)``)."""
    cfg = ServeConfig(max_batch=max_batch, flush_ms=2.0, scoring=_vcfg(**kw))
    builder = IndexBuilder(spec, ds.corpus, cfg)
    service = QueryService(spec, k=K, max_batch=max_batch, flush_ms=2.0)
    service.install(builder.build(params, step))
    items = [(q, ds.queries[q]) for q in ds.queries]
    if not threaded:
        resp = service.answer(items)
    else:
        service.start()
        try:
            with concurrent.futures.ThreadPoolExecutor(4) as pool:
                resp = list(pool.map(
                    lambda it: service.submit(it[0], it[1], timeout=30),
                    items))
        finally:
            service.stop()
    assert all(r.step == step for r in resp), \
        "every response must attribute the installed checkpoint"
    return ({r.qid: r.doc_ids for r in resp},
            {r.qid: r.scores for r in resp})


@pytest.mark.parametrize("sharded", [False, True],
                         ids=["single_device", "sharded"])
@pytest.mark.parametrize("score_dtype", ["f32", "bf16", "int8"])
def test_serve_matches_validator_bitwise(setup, score_dtype, sharded):
    """The acceptance matrix: ids + scores + tie order, bit-identical,
    for score_dtype x sharded/single-device.  The sharded leg uses a
    1-device mesh — the full shard_map/hierarchical-merge machinery runs
    deterministically (multi-device is the slow-tier subprocess test)."""
    ds, spec, params = setup
    mesh = None
    if sharded:
        mesh = jax.make_mesh((1,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
    suite = _suite(ds, spec, score_dtype=score_dtype, mesh=mesh)
    val_run, val_scores, _ = suite.engine("default").run(params)
    srv_run, srv_scores = _serve_run(ds, spec, params,
                                     score_dtype=score_dtype, mesh=mesh)
    assert srv_run == val_run          # ids, in rank (tie-broken) order
    assert srv_scores == val_scores    # float-exact scores

    # close the loop through validate_params: metrics computed from the
    # served run equal the suite's ledger-bound metrics exactly
    suite_metrics = suite.validate_params(params, step=7,
                                          write_runs=False).metrics
    served_metrics = metrics_lib.compute_metrics(srv_run, ds.qrels,
                                                 ["MRR@10"])
    assert served_metrics["MRR@10"] == suite_metrics["MRR@10"]


def test_serve_matches_validator_pallas(setup):
    """The pallas kernel path: serve's topk_mips dispatch against the
    validator's pallas streaming engine, bit-identical at f32."""
    ds, spec, params = setup
    suite = _suite(ds, spec, impl="pallas")
    val_run, val_scores, _ = suite.engine("default").run(params)
    srv_run, srv_scores = _serve_run(ds, spec, params, impl="pallas")
    assert srv_run == val_run
    assert srv_scores == val_scores


def test_tie_break_parity_duplicate_docs(setup):
    """Exact score ties (duplicated passages) must resolve identically on
    both paths — the rank_candidates stable-tie-break discipline extended
    to serving: identical score sets imply identical runs, not just
    identical up to tie order."""
    ds, spec, params = setup
    dup = dict(ds.corpus)
    base = list(ds.corpus.items())[:20]
    for did, toks in base:
        dup[f"{did}__dup"] = list(toks)   # bitwise-equal duplicate rows
    import dataclasses
    ds_dup = dataclasses.replace(ds, corpus=dup)
    suite = _suite(ds_dup, spec)
    val_run, val_scores, _ = suite.engine("default").run(params)
    srv_run, srv_scores = _serve_run(ds_dup, spec, params)
    assert srv_run == val_run
    assert srv_scores == val_scores
    # the ties actually engaged: some query surfaced a duplicated doc
    assert any(d.endswith("__dup") or f"{d}__dup" in dup
               for r in val_run.values() for d in r)


def test_micro_batch_packing_invariance(setup):
    """A query's answer must not depend on where it lands in a micro-batch
    (row-independent encoders + fixed-shape padding): alone, in a full
    batch, and through the threaded batcher all agree bitwise."""
    ds, spec, params = setup
    runs = []
    for max_batch, threaded in ((1, False), (len(ds.queries), False),
                                (3, True)):
        runs.append(_serve_run(ds, spec, params, max_batch=max_batch,
                               threaded=threaded))
    assert runs[0] == runs[1] == runs[2]


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(min_value=1, max_value=10_000))
def test_serve_parity_property(seed):
    """Property form of the acceptance claim: any synthetic corpus, any
    checkpoint — serve == validate, bitwise (f32; the dtype matrix is the
    parametrized test above)."""
    ds = corpus_lib.synthetic_retrieval_dataset(seed, n_passages=120,
                                                n_queries=6)
    spec = toy_spec(ds.vocab)
    _, snaps = train_toy_dr(ds, spec, steps=10, snapshot_every=10)
    params = snaps[-1][1]
    suite = _suite(ds, spec)
    val_run, val_scores, _ = suite.engine("default").run(params)
    srv_run, srv_scores = _serve_run(ds, spec, params, threaded=False)
    assert srv_run == val_run
    assert srv_scores == val_scores


@pytest.mark.parametrize("seed,chunk_size",
                         [(11, None), (29, None), (47, None), (11, 28),
                          (11, 4)],
                         ids=["11", "29", "47", "11-chunk28", "11-chunk4"])
def test_serve_parity_seeded(seed, chunk_size):
    """Seeded fallback for environments without hypothesis: the same
    property, pinned; ``11-chunk28`` sets a chunk apart from the encode
    batch, with a ragged last chunk (120 = 4 x 28 + 8), and ``11-chunk4``
    a chunk narrow enough against k 10 that both sides merge two chunks
    at a time, over windows of their own widths (8, 4, 2 against 30)."""
    ds = corpus_lib.synthetic_retrieval_dataset(seed, n_passages=120,
                                                n_queries=6)
    spec = toy_spec(ds.vocab)
    _, snaps = train_toy_dr(ds, spec, steps=10, snapshot_every=10)
    params = snaps[-1][1]
    suite = _suite(ds, spec, chunk_size=chunk_size)
    val_run, val_scores, _ = suite.engine("default").run(params)
    srv_run, srv_scores = _serve_run(ds, spec, params, threaded=False,
                                     chunk_size=chunk_size)
    assert srv_run == val_run
    assert srv_scores == val_scores


@pytest.mark.slow
def test_serve_parity_multidevice_padded():
    """8-device sharded serving with a corpus NOT divisible by the mesh:
    the ragged last chunk, masked inside the sharded stage, must still
    match the single-device answer exactly (tie-free corpus).  Runs in a
    subprocess with XLA-forced devices, like tests/test_distributed.py."""
    import os
    import subprocess
    import sys
    import textwrap
    code = textwrap.dedent("""
        import numpy as np
        from benchmarks.common import toy_spec, train_toy_dr
        from repro.data import corpus as corpus_lib
        from repro.core.suite import ValidationConfig
        from repro.serve import IndexBuilder, ServeConfig
        from repro.core.encoder import jitted_encoder
        from repro.data.corpus import pad_batch
        import jax
        import jax.numpy as jnp

        ds = corpus_lib.synthetic_retrieval_dataset(3, n_passages=205,
                                                    n_queries=8)
        spec = toy_spec(ds.vocab)
        _, snaps = train_toy_dr(ds, spec, steps=10, snapshot_every=10)
        params = snaps[-1][1]
        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        assert 205 % 8 != 0
        qids = list(ds.queries)
        toks, mask = pad_batch([ds.queries[q] for q in qids],
                               spec.q_max_len)
        q_emb = jitted_encoder(spec.encode_query)(
            params, jnp.asarray(toks), jnp.asarray(mask))
        runs = []
        for m in (None, mesh):
            builder = IndexBuilder(spec, ds.corpus, ServeConfig(
                scoring=ValidationConfig(k=10, mesh=m, batch_size=32)))
            store = builder.store          # 205 rows: a ragged last chunk
            assert 0 < store.rows_valid(store.n_chunks - 1) < store.chunk
            idx = builder.build(params, 1)
            runs.append(idx.search_run(qids, q_emb, k=10))
        assert runs[0] == runs[1], "padded sharded run diverged"
        print("OK")
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=8")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr
    assert "OK" in out.stdout
