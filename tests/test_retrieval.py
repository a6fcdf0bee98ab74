"""Exact MIPS retrieval: blocked scan, sharded hierarchical merge,
rerank scoring, and the encoder batching path."""

import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core import retrieval as R
from repro.core.encoder import encode_texts


def _qc(Q=8, N=500, D=24, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(Q, D)), jnp.float32),
            jnp.asarray(rng.normal(size=(N, D)), jnp.float32))


def test_topk_exact_matches_dense():
    q, c = _qc()
    s, i = R.topk_exact(q, c, k=25, block=64)
    full = np.asarray(q) @ np.asarray(c).T
    es, ei = jax.lax.top_k(jnp.asarray(full), 25)
    np.testing.assert_allclose(np.asarray(s), np.asarray(es), rtol=1e-5)
    assert (np.asarray(i) == np.asarray(ei)).mean() > 0.99


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 10), st.integers(1, 300), st.integers(1, 40),
       st.integers(1, 60), st.sampled_from([16, 100, 4096]))
def test_topk_exact_property(Q, N, D, k, block):
    q, c = _qc(Q, N, D, seed=Q * N + D)
    s, i = R.topk_exact(q, c, k=k, block=block)
    kk = min(k, N)
    assert s.shape == (Q, kk)
    full = np.asarray(q) @ np.asarray(c).T
    np.testing.assert_allclose(np.asarray(s[:, 0]), full.max(1), rtol=1e-5,
                               atol=1e-5)
    got = np.take_along_axis(full, np.asarray(i), axis=1)
    np.testing.assert_allclose(got, np.asarray(s), rtol=1e-5, atol=1e-5)


def test_topk_exact_block_invariance():
    q, c = _qc(5, 333, 16)
    outs = [np.asarray(R.topk_exact(q, c, k=10, block=b)[0])
            for b in (7, 64, 512)]
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-6)
    np.testing.assert_allclose(outs[0], outs[2], rtol=1e-6)


def test_retrieve_run_and_rerank_run():
    q, c = _qc(4, 60, 12)
    qids = [f"q{i}" for i in range(4)]
    dids = [f"d{i}" for i in range(60)]
    run, scores = R.retrieve_run(qids, q, dids, c, k=5)
    assert all(len(run[x]) == 5 for x in qids)
    full = np.asarray(q) @ np.asarray(c).T
    for qi, qid in enumerate(qids):
        assert run[qid][0] == dids[int(full[qi].argmax())]
    per_query = {qid: dids[:10] for qid in qids}
    rr, rs = R.rerank_run(qids, q, dids, c, per_query, k=5)
    for qid in qids:
        assert set(rr[qid]) <= set(per_query[qid])
        assert rs[qid] == sorted(rs[qid], reverse=True)


def test_topk_sharded_multidevice_subprocess():
    """Hierarchical sharded merge == dense result (8 forced host devices)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import retrieval as R
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.normal(size=(6, 16)), jnp.float32)
        c = jnp.asarray(rng.normal(size=(400, 16)), jnp.float32)
        s, i = R.topk_sharded(mesh, q, c, k=17, block=32)
        full = np.asarray(q) @ np.asarray(c).T
        es, ei = jax.lax.top_k(jnp.asarray(full), 17)
        np.testing.assert_allclose(np.asarray(s), np.asarray(es), rtol=1e-5)
        assert (np.asarray(i) == np.asarray(ei)).mean() > 0.99
        print("SHARDED_OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300)
    assert "SHARDED_OK" in out.stdout, out.stdout + out.stderr


def test_encode_texts_ragged_batching():
    """Final ragged batch is padded and sliced; single compiled shape."""
    def enc(params, tokens, mask):
        emb = jnp.take(params["t"], tokens, axis=0)
        m = mask.astype(emb.dtype)[..., None]
        return (emb * m).sum(1)

    params = {"t": jnp.asarray(np.random.default_rng(0).normal(size=(50, 8)),
                               jnp.float32)}
    texts = [[1, 2, 3], [4], [5, 6], [7, 8, 9], [10]]         # 5 texts
    embs, stats = encode_texts(enc, params, texts, max_len=4, batch_size=2)
    assert embs.shape == (5, 8)
    assert stats.n_batches == 3                                # 2+2+1(padded)
    # order and values match one-at-a-time encoding
    for i, t in enumerate(texts):
        toks = np.zeros((1, 4), np.int32)
        msk = np.zeros((1, 4), bool)
        toks[0, :len(t)] = t
        msk[0, :len(t)] = True
        one = np.asarray(enc(params, jnp.asarray(toks), jnp.asarray(msk)))[0]
        np.testing.assert_allclose(embs[i], one, rtol=1e-6)


def test_pallas_impl_matches_xla_impl():
    q, c = _qc(6, 300, 32)
    qids = [f"q{i}" for i in range(6)]
    dids = [f"d{i}" for i in range(300)]
    run_x, _ = R.retrieve_run(qids, q, dids, c, k=10, impl="xla")
    run_p, _ = R.retrieve_run(qids, q, dids, c, k=10, impl="pallas")
    for qid in qids:
        assert run_x[qid] == run_p[qid]
