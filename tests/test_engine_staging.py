"""Out-of-core TokenStore + double-buffered staging: mmap parity (bit for
bit vs the in-memory path), staging-schedule/prefetch-depth invariants,
double-buffered vs synchronous parity, ragged final chunk on disk, and the
sharded query-encoding path."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine as E
from repro.core.pipeline import ValidationConfig, ValidationPipeline
from repro.data import corpus as corpus_lib
from repro.models.biencoder import EncoderSpec

DIM = 16


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


# ---------------------------------------------------------------------------
# TokenStore mmap backing
# ---------------------------------------------------------------------------


def test_token_store_mmap_bitwise_parity_and_ragged_tail(tmp_path):
    texts = [[i % 50, i + 1, i + 2] for i in range(43)]    # 43 = 2*16 + 11
    mem = E.TokenStore.build(texts, max_len=5, chunk=16)
    mm = E.TokenStore.build(texts, max_len=5, chunk=16, backing="mmap",
                            cache_dir=str(tmp_path / "cache"))
    assert isinstance(mm.tokens, np.memmap) and isinstance(mm.mask, np.memmap)
    assert mm.n_chunks == mem.n_chunks == 3
    assert mm.rows_valid(2) == 11                          # ragged final chunk
    np.testing.assert_array_equal(np.asarray(mm.tokens), mem.tokens)
    np.testing.assert_array_equal(np.asarray(mm.mask), mem.mask)
    # per-chunk iteration parity too (what the engine actually consumes)
    for (ta, ma, ba, va), (tb, mb, bb, vb) in zip(mem.chunks(), mm.chunks()):
        np.testing.assert_array_equal(np.asarray(ta), np.asarray(tb))
        np.testing.assert_array_equal(np.asarray(ma), np.asarray(mb))
        assert (ba, va) == (bb, vb)


def test_token_store_mmap_cache_reused_across_builds(tmp_path):
    texts = [[i, i + 1] for i in range(10)]
    cache = str(tmp_path / "cache")
    first = E.TokenStore.build(texts, max_len=4, chunk=4, backing="mmap",
                               cache_dir=cache)
    assert not first.reused
    meta = json.load(open(os.path.join(cache, "store_meta.json")))
    assert meta["n_texts"] == 10 and meta["n_chunks"] == 3
    # second build (next checkpoint / restarted process): files are reused
    second = E.TokenStore.build(texts, max_len=4, chunk=4, backing="mmap",
                                cache_dir=cache)
    assert second.reused
    np.testing.assert_array_equal(np.asarray(second.tokens),
                                  np.asarray(first.tokens))
    # different content with same geometry must NOT reuse
    other = [[i + 7, i] for i in range(10)]
    third = E.TokenStore.build(other, max_len=4, chunk=4, backing="mmap",
                               cache_dir=cache)
    assert not third.reused
    assert np.asarray(third.tokens)[0, 0, 0] == 7


def test_token_store_mmap_survives_torn_meta(tmp_path):
    """A crash mid-build (torn/truncated store_meta.json) must trigger a
    rebuild on the next build, not a permanent JSONDecodeError."""
    texts = [[i, i + 1] for i in range(10)]
    cache = str(tmp_path / "cache")
    E.TokenStore.build(texts, max_len=4, chunk=4, backing="mmap",
                       cache_dir=cache)
    with open(os.path.join(cache, "store_meta.json"), "w") as f:
        f.write('{"version": 1, "n_te')                    # torn write
    store = E.TokenStore.build(texts, max_len=4, chunk=4, backing="mmap",
                               cache_dir=cache)
    assert not store.reused                                # rebuilt
    mem = E.TokenStore.build(texts, max_len=4, chunk=4)
    np.testing.assert_array_equal(np.asarray(store.tokens), mem.tokens)
    # and the rebuild re-committed a valid marker
    assert E.TokenStore.build(texts, max_len=4, chunk=4, backing="mmap",
                              cache_dir=cache).reused
    # a valid marker with missing/truncated bins must also rebuild (a
    # partially copied cache_dir), not crash on the memmap open
    os.remove(os.path.join(cache, "tokens.int32.bin"))
    store = E.TokenStore.build(texts, max_len=4, chunk=4, backing="mmap",
                               cache_dir=cache)
    assert not store.reused
    np.testing.assert_array_equal(np.asarray(store.tokens), mem.tokens)


def test_token_store_full_fingerprint_catches_middle_mutation(tmp_path):
    """The mmap-cache middle-mutation hazard (ROADMAP): the O(1) "fast"
    fingerprint only sees geometry + edge texts, so an in-place mutation of
    a middle document is a DOCUMENTED stale hit; the opt-in "full" content
    hash must rebuild instead."""
    texts = [[i, i + 1] for i in range(40)]
    mutated = [list(t) for t in texts]
    mutated[20] = [999, 998]                       # middle doc, edges intact

    # fast (default): stale reuse — the documented hazard, asserted so the
    # contract is pinned, not accidental.
    fast = str(tmp_path / "fast")
    E.TokenStore.build(texts, max_len=4, chunk=8, backing="mmap",
                       cache_dir=fast)
    stale = E.TokenStore.build(mutated, max_len=4, chunk=8, backing="mmap",
                               cache_dir=fast)
    assert stale.reused                            # cache NOT invalidated
    assert np.asarray(stale.tokens)[2, 4, 0] == 20  # still the old content

    # full: the same mutation rebuilds the cache
    full = str(tmp_path / "full")
    first = E.TokenStore.build(texts, max_len=4, chunk=8, backing="mmap",
                               cache_dir=full, fingerprint="full")
    assert not first.reused
    # unchanged content still reuses under "full" (the amortization holds)
    assert E.TokenStore.build(texts, max_len=4, chunk=8, backing="mmap",
                              cache_dir=full, fingerprint="full").reused
    fresh = E.TokenStore.build(mutated, max_len=4, chunk=8, backing="mmap",
                               cache_dir=full, fingerprint="full")
    assert not fresh.reused                        # mutation detected
    assert np.asarray(fresh.tokens)[2, 4, 0] == 999
    # switching fingerprint modes never trusts the other mode's marker
    assert not E.TokenStore.build(mutated, max_len=4, chunk=8,
                                  backing="mmap", cache_dir=full).reused
    with pytest.raises(ValueError):
        E.TokenStore.build(texts, max_len=4, chunk=8, fingerprint="bogus")


def test_token_store_mmap_readonly_and_empty(tmp_path):
    store = E.TokenStore.build([[1], [2]], max_len=3, chunk=2,
                               backing="mmap", cache_dir=str(tmp_path / "c"))
    with pytest.raises(ValueError):
        store.tokens[0, 0, 0] = 99                         # mode="r" maps
    empty = E.TokenStore.build([], max_len=3, chunk=8, backing="mmap",
                               cache_dir=str(tmp_path / "e"))
    assert empty.n_chunks == 0
    with pytest.raises(ValueError):
        E.TokenStore.build([[1]], max_len=2, chunk=1, backing="mmap")
    with pytest.raises(ValueError):
        E.TokenStore.build([[1]], max_len=2, chunk=1, backing="bogus")


# ---------------------------------------------------------------------------
# Staging schedule + prefetch depth
# ---------------------------------------------------------------------------


def test_plan_schedule_halving_tail():
    # 15 chunks, window 8: one full window then a halving tail 4+2+1
    assert E.plan_schedule(15, 8) == [(0, 8), (8, 4), (12, 2), (14, 1)]
    assert E.plan_schedule(16, 8) == [(0, 8), (8, 8)]
    assert E.plan_schedule(3, 1) == [(0, 1), (1, 1), (2, 1)]
    assert E.plan_schedule(0, 8) == []
    # covers every chunk exactly once, in order
    for n, w in [(37, 8), (5, 4), (9, 16)]:
        plan = E.plan_schedule(n, w)
        rows = [ci + j for ci, ww in plan for j in range(ww)]
        assert rows == list(range(n))


def test_staged_batches_prefetches_ahead_of_consumption():
    """With depth=2 the stager has already issued batch i+1's put when batch
    i is consumed (the double buffer); with depth=1 it has not (sync)."""
    texts = [[i] for i in range(12)]
    store = E.TokenStore.build(texts, max_len=2, chunk=3)
    schedule = E.plan_schedule(store.n_chunks, 1)

    for depth, max_lead in ((1, 0), (2, 1), (3, 2)):
        staged = []
        it = E.staged_batches(store, schedule, depth=depth,
                              _put=lambda x: staged.append(len(staged)) or x)
        consumed = 0
        for toks, mask in it:
            consumed += 1
            # puts come in (tokens, mask) pairs: staged batches = staged/2
            lead = staged[-1] // 2 + 1 - consumed if staged else 0
            assert lead <= max_lead
        assert consumed == len(schedule)


def test_staged_batches_values_identical_to_direct_load():
    texts = [[i, i + 3] for i in range(26)]                # ragged tail
    store = E.TokenStore.build(texts, max_len=3, chunk=4)
    schedule = E.plan_schedule(store.n_chunks, 4)
    out = list(E.staged_batches(store, schedule, depth=2))
    assert len(out) == len(schedule)
    for (ci, w), (toks, mask) in zip(schedule, out):
        ref_t = store.tokens[ci] if w == 1 else store.tokens[ci:ci + w]
        ref_m = store.mask[ci] if w == 1 else store.mask[ci:ci + w]
        np.testing.assert_array_equal(np.asarray(toks), ref_t)
        np.testing.assert_array_equal(np.asarray(mask), ref_m)


# ---------------------------------------------------------------------------
# Whole-pipeline parity: mmap + double-buffered == in-memory sync, bit for bit
# ---------------------------------------------------------------------------


def _run_pipeline(ds, spec, params, **vcfg_kw):
    vcfg = ValidationConfig(metrics=("MRR@10", "Recall@100"), k=100,
                            batch_size=64, **vcfg_kw)
    pipe = ValidationPipeline(spec, ds.corpus, ds.queries, ds.qrels, vcfg)
    run, scores, _ = pipe.engine.run(params)
    res = pipe.validate_params(params)
    return run, scores, res


@pytest.mark.parametrize("chunk", [64, 96])                # 96 -> ragged tail
def test_pipeline_mmap_double_buffered_bitwise_parity(tmp_path, ds, chunk):
    """The acceptance bar: mmap-backed + double-buffered streaming produces
    bit-for-bit identical runs/scores/metrics to in-memory sync streaming."""
    spec = _toy_spec()
    params = spec.init(jax.random.PRNGKey(1))
    base = _run_pipeline(ds, spec, params, chunk_size=chunk,
                         staging="sync", token_backing="memory")
    oooc = _run_pipeline(ds, spec, params, chunk_size=chunk,
                         staging="double_buffered", token_backing="mmap",
                         mmap_dir=str(tmp_path / f"tc{chunk}"))
    assert base[0] == oooc[0]                              # identical run
    assert base[1] == oooc[1]                              # identical scores
    assert base[2].metrics == oooc[2].metrics


def test_pipeline_double_buffered_matches_sync(ds):
    spec = _toy_spec()
    params = spec.init(jax.random.PRNGKey(2))
    sync = _run_pipeline(ds, spec, params, chunk_size=48, staging="sync")
    dbuf = _run_pipeline(ds, spec, params, chunk_size=48,
                         staging="double_buffered")
    assert sync[0] == dbuf[0] and sync[1] == dbuf[1]


def test_pipeline_staging_depth_sweep(ds):
    """The configurable prefetch depth (ValidationConfig.staging_depth) must
    not change results: depths 1, 2, and 4 produce bit-for-bit identical
    runs/scores/metrics — deeper pipelines only stage further ahead."""
    spec = _toy_spec()
    params = spec.init(jax.random.PRNGKey(3))
    ref = None
    for depth in (1, 2, 4):
        got = _run_pipeline(ds, spec, params, chunk_size=48,
                            staging_depth=depth)
        if ref is None:
            ref = got
        else:
            assert got[0] == ref[0] and got[1] == ref[1]
            assert got[2].metrics == ref[2].metrics
    # the depth actually reaches the engine (not silently defaulted)
    vcfg = ValidationConfig(staging_depth=4)
    pipe = ValidationPipeline(spec, ds.corpus, ds.queries, ds.qrels, vcfg)
    assert pipe.engine.staging_depth == 4


def test_streaming_engine_rejects_unknown_staging(ds, tmp_path):
    spec = _toy_spec()
    with pytest.raises(ValueError):
        ValidationPipeline(spec, ds.corpus, ds.queries, ds.qrels,
                           ValidationConfig(staging="bogus"))
    with pytest.raises(ValueError):
        ValidationPipeline(spec, ds.corpus, ds.queries, ds.qrels,
                           ValidationConfig(token_backing="mmap"))  # no dir
    with pytest.raises(ValueError):
        ValidationPipeline(spec, ds.corpus, ds.queries, ds.qrels,
                           ValidationConfig(staging_depth=0))
    with pytest.raises(ValueError):
        ValidationPipeline(spec, ds.corpus, ds.queries, ds.qrels,
                           ValidationConfig(token_backing="mmap",
                                            mmap_dir=str(tmp_path / "fp"),
                                            token_fingerprint="bogus"))


def test_mmap_store_via_validator_multiple_checkpoints(tmp_path, ds):
    """The mmap cache is built once and reused for every checkpoint the
    validator sees (the amortization argument)."""
    from repro.ckpt import checkpoint as ckpt
    from repro.core.validator import AsyncValidator

    spec = _toy_spec()
    root = str(tmp_path / "ck")
    for step in (1, 2):
        ckpt.save(root, step,
                  {"params": spec.init(jax.random.PRNGKey(step))})
    cache = str(tmp_path / "tokens")
    pipe = ValidationPipeline(
        spec, ds.corpus, ds.queries, ds.qrels,
        ValidationConfig(batch_size=64, token_backing="mmap",
                         mmap_dir=cache))
    assert pipe.engine.doc_store.backing == "mmap"
    v = AsyncValidator(root, pipe)
    assert v.validate_pending() == 2
    ref = ValidationPipeline(spec, ds.corpus, ds.queries, ds.qrels,
                             ValidationConfig(batch_size=64))
    for res in v.results:
        state, _ = ckpt.restore(root, res.step)
        assert res.metrics == ref.validate_params(
            state["params"], step=res.step).metrics


# ---------------------------------------------------------------------------
# Sharded query encoding (forced multi-device, subprocess)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_sharded_query_encoding_subprocess():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import engine as E
        from repro.distributed.sharding import rows_sharding

        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        rng = np.random.default_rng(0)
        D = 16
        params = {"table": jnp.asarray(rng.normal(size=(64, D)), jnp.float32)}

        def enc(params, tokens, mask):
            return jnp.take(params["table"], tokens[:, 0], axis=0)

        # 50 queries, chunk 16 (divisible by the 8 shards), ragged tail
        q_texts = [[int(i % 64), 1] for i in range(50)]
        store = E.TokenStore.build(q_texts, max_len=2, chunk=16)
        ref = E.encode_store(enc, params, store)
        sharded = E.encode_store(enc, params, store, mesh=mesh)
        assert sharded.shape == (50, D)
        np.testing.assert_allclose(np.asarray(sharded), np.asarray(ref),
                                   rtol=1e-6)
        # staged chunks land with the row sharding the shard_map expects
        s = rows_sharding(mesh)
        assert s.spec == jax.sharding.PartitionSpec(("data", "model"))

        # the full engine path: make_engine on a mesh routes query encoding
        # through the sharded stage and still scores identically
        from repro.data import corpus as corpus_lib
        from repro.core.pipeline import ValidationConfig, ValidationPipeline
        from repro.models.biencoder import EncoderSpec
        ds = corpus_lib.synthetic_retrieval_dataset(0, n_passages=200,
                                                    n_queries=20)
        def enc2(params, tokens, mask):
            emb = jnp.take(params["t"], tokens, axis=0)
            m = mask.astype(emb.dtype)[..., None]
            v = (emb * m).sum(1) / jnp.clip(m.sum(1), 1e-6)
            return v / jnp.clip(jnp.linalg.norm(v, axis=-1, keepdims=True),
                                1e-6)
        spec = EncoderSpec(
            name="toy", dim=16, encode_query=enc2, encode_passage=enc2,
            init=lambda rng: {"t": 0.1 * jax.random.normal(rng, (503, 16))},
            q_max_len=8, p_max_len=20)
        params2 = spec.init(jax.random.PRNGKey(0))
        kw = dict(metrics=("MRR@10",), k=50, batch_size=40)
        on_mesh = ValidationPipeline(
            spec, ds.corpus, ds.queries, ds.qrels,
            ValidationConfig(mesh=mesh, chunk_size=40, **kw))
        assert on_mesh.engine.query_mesh is mesh
        single = ValidationPipeline(spec, ds.corpus, ds.queries, ds.qrels,
                                    ValidationConfig(chunk_size=40, **kw))
        rm = on_mesh.validate_params(params2)
        rs = single.validate_params(params2)
        assert rm.metrics == rs.metrics, (rm.metrics, rs.metrics)
        print("SHARDED_QUERY_ENCODE_OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300)
    assert "SHARDED_QUERY_ENCODE_OK" in out.stdout, out.stdout + out.stderr
