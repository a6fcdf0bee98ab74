"""The running top-k merge (``_merge_topk``) against ``lax.top_k``.

The merge sorts the scores with the ids as second key instead of gathering
the ids at ``top_k``'s positions.  These tests keep the gather formula as
the reference and hold the merge to it bit for bit, scores and ids, on
tie-heavy inputs, ``-inf`` padding and the empty initial carry, and hold
every top-k path that merges through it (streaming XLA and Pallas stages,
``topk_exact``, the sharded stage and ``topk_sharded``) to a global
``lax.top_k`` over exactly representable, tie-heavy scores.  The one
departure, signed zeros, is pinned on its own.
"""

import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine as E
from repro.core import retrieval as R
from repro.core.retrieval import _merge_topk
from repro.kernels.topk_mips.ops import _merge_carry

Q, CARRY, CHUNK = 6, 20, 8
W = CARRY + CHUNK
BASE = 1000


def _reference(scores_a, idx_a, scores_b, idx_b, k):
    """``lax.top_k`` over [a ‖ b], then the ids gathered at its positions."""
    s = jnp.concatenate([scores_a, scores_b], axis=1)
    i = jnp.concatenate([idx_a, idx_b], axis=1)
    top_s, pos = jax.lax.top_k(s, k)
    return top_s, jnp.take_along_axis(i, pos, axis=1)


def _assert_bitwise(got, want):
    np.testing.assert_array_equal(np.asarray(got[0]).view(np.int32),
                                  np.asarray(want[0]).view(np.int32))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


def _ties(rng, shape):
    # one decimal, so equal scores abound; + 0.0 turns -0.0 into +0.0
    return (np.round(rng.normal(size=shape), 1) + 0.0).astype(np.float32)


def _empty_carry(width):
    return (np.full((Q, width), -np.inf, np.float32),
            np.zeros((Q, width), np.int32))


def _candidates(case, seed):
    """(carry scores, carry ids, chunk scores, chunk ids) as a fold sees
    them: the carry is an earlier fold's output over ids below ``BASE``,
    the chunk's ids are ``BASE + col``."""
    rng = np.random.default_rng(seed)
    chunk_i = np.broadcast_to(BASE + np.arange(CHUNK, dtype=np.int32),
                              (Q, CHUNK)).copy()
    chunk_s = _ties(rng, (Q, CHUNK))
    if case == "empty_carry":
        return (*_empty_carry(CARRY), chunk_s, chunk_i)
    seen_s = _ties(rng, (Q, CARRY))
    seen_i = np.sort(np.stack([rng.choice(BASE, CARRY, replace=False)
                               for _ in range(Q)]), axis=1).astype(np.int32)
    if case == "neg_inf":
        # a carry not yet full (its tail is the empty carry's -inf, ids 0)
        # and a ragged chunk whose padding columns are masked to -inf
        seen_s[:, CARRY // 2:] = -np.inf
        chunk_s[:, CHUNK - 3:] = -np.inf
    run_s, run_i = _reference(*_empty_carry(CARRY), seen_s, seen_i, CARRY)
    return np.asarray(run_s), np.asarray(run_i), chunk_s, chunk_i


@pytest.mark.parametrize("case", ["ties", "neg_inf", "empty_carry"])
@pytest.mark.parametrize("k", [1, 10, W - CHUNK, W])
def test_merge_topk_bitwise_vs_top_k_gather(case, k):
    args = _candidates(case, seed=k)
    _assert_bitwise(_merge_topk(*args, k), _reference(*args, k))


@pytest.mark.parametrize("k", [1, 10, W - CHUNK, W])
def test_merge_carry_bitwise_vs_top_k_gather(k):
    """The Pallas path's carry fold offsets the chunk-local ids by
    ``base`` and merges through the same helper."""
    run_s, run_i, chunk_s, chunk_i = _candidates("neg_inf", seed=100 + k)
    run_s, run_i = run_s[:, :k], run_i[:, :k]
    got = _merge_carry(jnp.asarray(run_s), jnp.asarray(run_i),
                       jnp.asarray(chunk_s), jnp.asarray(chunk_i - BASE),
                       jnp.int32(BASE), k=k)
    _assert_bitwise(got, _reference(run_s, run_i, chunk_s, chunk_i, k))


@pytest.mark.parametrize("n_chunks", [2, 5])
@pytest.mark.parametrize("k", [1, 10, W - CHUNK, W])
def test_fold_over_chunks_bitwise_vs_top_k_gather(k, n_chunks):
    """Chunk after chunk, as the streaming stage folds them (the last one
    ragged): each fold's output is the next fold's carry, so equal scores
    must keep their order across folds, not only within one."""
    rng = np.random.default_rng(k * n_chunks)
    got = want = _empty_carry(k)
    for c in range(n_chunks):
        s = _ties(rng, (Q, CHUNK))
        if c == n_chunks - 1:
            s[:, CHUNK - 3:] = -np.inf
        i = np.broadcast_to(c * CHUNK + np.arange(CHUNK, dtype=np.int32),
                            (Q, CHUNK))
        got = _merge_topk(*got, s, i, k)
        want = _reference(*want, s, i, k)
        _assert_bitwise(got, want)


# ---------------------------------------------------------------------------
# Every top-k path against one global lax.top_k, on exact tie-heavy scores
# ---------------------------------------------------------------------------

VOCAB, DIM = 16, 6


def _integer_setup(N, seed=0):
    """Embeddings in {-1, 0, 1}: every score is a small integer, exact in
    any summation order, and most scores tie; docs repeat table rows."""
    rng = np.random.default_rng(seed)
    table = rng.integers(-1, 2, size=(VOCAB, DIM)).astype(np.float32)
    params = {"table": jnp.asarray(table)}
    doc_texts = [[int(i % VOCAB)] for i in range(N)]
    c_emb = jnp.asarray(table[np.arange(N) % VOCAB])
    q_emb = jnp.asarray(rng.integers(-1, 2, size=(Q, DIM)), jnp.float32)
    return params, doc_texts, c_emb, q_emb


def _gather_encode(params, tokens, mask):
    del mask
    return jnp.take(params["table"], tokens[:, 0], axis=0)


@pytest.mark.parametrize("path", ["xla", "pallas", "topk_exact"])
@pytest.mark.parametrize("k", [1, 10, 37])
def test_topk_paths_bitwise_vs_global_top_k_on_ties(path, k):
    N, chunk = 45, 16
    params, doc_texts, c_emb, q_emb = _integer_setup(N)
    if path == "topk_exact":
        got = R.topk_exact(q_emb, c_emb, k=k, block=chunk)
    else:
        stage_cls = {"xla": E.StreamTopKStage,
                     "pallas": E.PallasStreamTopKStage}[path]
        store = E.TokenStore.build(doc_texts, max_len=2, chunk=chunk)
        stage = stage_cls(_gather_encode, k=k,
                          query_ids=[f"q{i}" for i in range(Q)],
                          doc_ids=[f"d{i}" for i in range(N)])
        got = stage.init(q_emb)
        for toks, mask, base, n_valid in store.chunks():
            got = stage.step(params, q_emb, got, toks, mask, base, n_valid)
    _assert_bitwise(got, jax.lax.top_k(q_emb @ c_emb.T, k))


def test_sharded_paths_bitwise_vs_global_top_k_on_ties():
    """The hierarchical merge gathers shard-major lists: ids ascend with
    the shard, so ties come out as a global ``lax.top_k`` has them."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import engine as E
        from repro.core import retrieval as R

        mesh = jax.make_mesh((2, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        rng = np.random.default_rng(0)
        N, Q, D, chunk = 48, 6, 6, 8
        table = rng.integers(-1, 2, size=(16, D)).astype(np.float32)
        params = {"table": jnp.asarray(table)}
        doc_texts = [[int(i % 16)] for i in range(N)]
        c_emb = jnp.asarray(table[np.arange(N) % 16])
        q_emb = jnp.asarray(rng.integers(-1, 2, size=(Q, D)), jnp.float32)

        def enc(params, tokens, mask):
            return jnp.take(params["table"], tokens[:, 0], axis=0)

        def same(got, want):
            return (np.array_equal(np.asarray(got[0]).view(np.int32),
                                   np.asarray(want[0]).view(np.int32))
                    and np.array_equal(np.asarray(got[1]),
                                       np.asarray(want[1])))

        for k in (1, 10, 37):
            want = jax.lax.top_k(q_emb @ c_emb.T, k)
            assert same(R.topk_sharded(mesh, q_emb, c_emb, k=k, block=8),
                        want), ("topk_sharded", k)
            store = E.TokenStore.build(doc_texts, max_len=2, chunk=chunk)
            stage = E.ShardedStreamTopKStage(
                enc, mesh, k=k, query_ids=[f"q{i}" for i in range(Q)],
                doc_ids=[f"d{i}" for i in range(N)])
            carry = stage.init(q_emb)
            for toks, mask, base, n_valid in store.chunks():
                carry = stage.step(params, q_emb, carry, toks, mask, base,
                                   n_valid)
            assert same(carry, want), ("sharded stage", k)
        print("SHARDED_TIES_OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300)
    assert "SHARDED_TIES_OK" in out.stdout, out.stdout + out.stderr


def test_merge_topk_signed_zeros_tie_by_position():
    """``lax.top_k`` ranks +0.0 above -0.0; the merge ties them and keeps
    position order, carry before chunk, as the Pallas merge's ``==``
    comparison does."""
    run_s = jnp.asarray([[1.0, -0.0, 0.0]], jnp.float32)
    run_i = jnp.asarray([[7, 3, 9]], jnp.int32)
    chunk_s = jnp.asarray([[-0.0, 0.0, -1.0]], jnp.float32)
    chunk_i = jnp.asarray([[10, 11, 12]], jnp.int32)
    s, i = _merge_topk(run_s, run_i, chunk_s, chunk_i, 5)
    np.testing.assert_array_equal(np.asarray(i), [[7, 3, 9, 10, 11]])
    np.testing.assert_array_equal(np.signbit(np.asarray(s)),
                                  [[False, True, False, True, False]])
    _, ref_i = _reference(run_s, run_i, chunk_s, chunk_i, 5)
    np.testing.assert_array_equal(np.asarray(ref_i), [[7, 9, 11, 3, 10]])


def test_merge_topk_lowers_without_gather():
    """The ids ride the sort: the merge's program holds a sort and no
    gather (the fused step is not lowered here, as the encoder's
    embedding lookup is a gather of its own)."""
    k, chunk, q = 20, 8, 8
    shapes = (jax.ShapeDtypeStruct((q, k), jnp.float32),
              jax.ShapeDtypeStruct((q, k), jnp.int32),
              jax.ShapeDtypeStruct((q, chunk), jnp.float32),
              jax.ShapeDtypeStruct((q, chunk), jnp.int32))
    text = jax.jit(_merge_topk, static_argnums=4).lower(*shapes, k).as_text()
    assert "stablehlo.sort" in text
    assert "gather" not in text
    ref = jax.jit(_reference, static_argnums=4).lower(*shapes, k).as_text()
    assert "gather" in ref
