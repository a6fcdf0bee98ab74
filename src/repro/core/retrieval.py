"""Exact MIPS top-k retrieval — the paper's retrieval stage, TPU-native.

Replaces the paper's host-side FAISS flat index: the corpus embedding matrix
stays device-resident (row-sharded at scale) and retrieval is a blocked
matmul + running top-k:

  * ``topk_exact``       — single-device: ``lax.scan`` over corpus blocks with
                           an online top-k merge (XLA path; the Pallas kernel
                           in ``repro.kernels.topk_mips`` is the TPU-target
                           implementation of the same loop, selected with
                           impl="pallas").
  * ``topk_sharded``     — shard_map over a mesh: corpus rows sharded, local
                           top-k per shard, hierarchical merge via all_gather
                           of the k candidates/shard (collective volume
                           O(devices x k) — negligible vs the scan).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.precision import chunk_scores, validate_score_dtype


def _merge_topk(scores_a, idx_a, scores_b, idx_b, k: int):
    """Merge two (Q, ka/kb) candidate sets into (Q, k)."""
    s = jnp.concatenate([scores_a, scores_b], axis=1)
    i = jnp.concatenate([idx_a, idx_b], axis=1)
    top_s, pos = jax.lax.top_k(s, k)
    return top_s, jnp.take_along_axis(i, pos, axis=1)


@functools.partial(jax.jit, static_argnames=("k", "block", "unroll",
                                             "score_dtype"))
def topk_exact(q_emb: jnp.ndarray, c_emb: jnp.ndarray, *, k: int,
               block: int = 4096, unroll: int = 1,
               score_dtype: str = "f32"):
    """q_emb (Q, D) x c_emb (N, D) -> (scores (Q,k), indices (Q,k)).

    Scans corpus blocks, carrying a running top-k so the full (Q, N) score
    matrix is never materialized (N can be 10^7).  ``score_dtype`` (static)
    picks the scoring precision via :func:`repro.core.precision.
    chunk_scores`; ``"f32"`` compiles the literal legacy expression.
    Per-row quantization makes the block scores block-size independent, so
    every precision agrees with the streaming stages at equal dtype."""
    Q, D = q_emb.shape
    N = c_emb.shape[0]
    k = min(k, N)
    nb = max(1, min(block, N))
    n_blocks = -(-N // nb)
    padN = n_blocks * nb
    c = jnp.pad(c_emb, ((0, padN - N), (0, 0)))
    c = c.reshape(n_blocks, nb, D)

    init_s = jnp.full((Q, k), -jnp.inf, jnp.float32)
    init_i = jnp.zeros((Q, k), jnp.int32)

    def body(carry, inp):
        run_s, run_i = carry
        cb, bi = inp
        if score_dtype == "f32":
            s = (q_emb @ cb.T).astype(jnp.float32)           # (Q, nb)
        else:
            s = chunk_scores(q_emb, cb, score_dtype)         # (Q, nb)
        base = bi * nb
        valid = (base + jnp.arange(nb))[None, :] < N
        s = jnp.where(valid, s, -jnp.inf)
        kk = min(k, nb)
        bs, bidx = jax.lax.top_k(s, kk)
        bidx = bidx + base
        return _merge_topk(run_s, run_i, bs, bidx.astype(jnp.int32), k), None

    (scores, idx), _ = jax.lax.scan(body, (init_s, init_i),
                                    (c, jnp.arange(n_blocks)),
                                    unroll=(n_blocks if unroll <= 0
                                            else min(unroll, n_blocks)))
    return scores, idx


def _hierarchical_topk_merge(s, i, axis_names, k: int):
    """Reduce per-shard (Q, kk) candidates to the global (Q, <=k) top-k by
    all-gathering one mesh axis at a time, innermost first.  A flat n-way
    gather moves (n_shards-1) x Q x k candidate rows per device; two 16-way
    levels move 2 x 15 x Q x k — ~8.5x less wire on the 16x16 mesh
    (EXPERIMENTS.md §Perf).  Must run inside shard_map."""
    for merge_ax in reversed(tuple(axis_names)):
        all_s = jax.lax.all_gather(s, merge_ax, axis=0, tiled=False)
        all_i = jax.lax.all_gather(i, merge_ax, axis=0, tiled=False)
        Sn = all_s.shape[0] * all_s.shape[2]
        flat_s = jnp.moveaxis(all_s, 0, 1).reshape(s.shape[0], Sn)
        flat_i = jnp.moveaxis(all_i, 0, 1).reshape(s.shape[0], Sn)
        s, pos = jax.lax.top_k(flat_s, min(k, Sn))
        i = jnp.take_along_axis(flat_i, pos, axis=1)
    return s, i


def _hierarchical_slot_max(x, axis_names):
    """Slot-aligned sibling of :func:`_hierarchical_topk_merge` for the
    sharded rerank stage: per-shard partial candidate-score matrices are
    already aligned on the (Q, Cmax) slot grid (each slot names one global
    corpus row, which lives on exactly one shard), so the cross-shard merge
    degenerates from a gather+top-k to an elementwise max — reduced one mesh
    axis at a time, innermost first, like the top-k merge, but each level is
    a ``pmax`` (the reduction happens on the wire, so the per-level volume is
    Q x Cmax instead of the gather's n_ax x Q x Cmax).  Must run inside
    shard_map."""
    for merge_ax in reversed(tuple(axis_names)):
        x = jax.lax.pmax(x, merge_ax)
    return x


def topk_sharded(mesh, q_emb, c_emb, *, k: int, axis_names=("data", "model"),
                 block: int = 4096, score_dtype: str = "f32"):
    """Distributed exact top-k: corpus rows sharded over ``axis_names``.

    Each shard computes a local top-k over its rows (global indices), then a
    hierarchical merge all-gathers the (k-candidate) lists and reduces.
    ``score_dtype`` threads to the per-shard :func:`topk_exact`; per-ROW
    quantization means each shard's quantized scores equal the single-device
    slice, so sharded narrow-dtype runs match unsharded ones.
    """
    n_shards = int(np.prod([mesh.shape[a] for a in axis_names]))
    N = c_emb.shape[0]
    rows = N // n_shards
    assert rows * n_shards == N, "corpus rows must divide shards (pad first)"
    kk = min(k, rows)

    def local(q, c_local):
        ax = axis_names[0] if len(axis_names) == 1 else axis_names
        shard_id = jax.lax.axis_index(ax)
        s, i = topk_exact(q, c_local, k=kk, block=block,
                          score_dtype=score_dtype)
        i = i + shard_id * rows
        return _hierarchical_topk_merge(s, i, axis_names, k)

    spec_c = P(axis_names if len(axis_names) > 1 else axis_names[0])
    # check_vma=False: the inner lax.scan carry starts replicated and
    # becomes device-varying after the first block — a legal pattern the
    # varying-manual-axes checker can't type; outputs are re-replicated by
    # the final merge anyway.
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(), spec_c),
                       out_specs=(P(), P()), check_vma=False)
    return fn(q_emb, c_emb)


def retrieve_run(query_ids, q_emb, doc_ids, c_emb, *, k: int,
                 impl: str = "xla", mesh=None, block: int = 4096,
                 score_dtype: str = "f32"):
    """Build a {qid: [docid...]} run (+scores) from embeddings."""
    validate_score_dtype(score_dtype)
    if impl == "pallas":
        from repro.kernels.topk_mips import ops as mips_ops
        scores, idx = mips_ops.topk_mips(jnp.asarray(q_emb),
                                         jnp.asarray(c_emb), k=k,
                                         score_dtype=score_dtype)
    elif mesh is not None:
        scores, idx = topk_sharded(mesh, jnp.asarray(q_emb),
                                   jnp.asarray(c_emb), k=k, block=block,
                                   score_dtype=score_dtype)
    else:
        scores, idx = topk_exact(jnp.asarray(q_emb), jnp.asarray(c_emb),
                                 k=k, block=block, score_dtype=score_dtype)
    scores = np.asarray(scores)
    idx = np.asarray(idx)
    run, run_scores = {}, {}
    for qi, qid in enumerate(query_ids):
        run[qid] = [doc_ids[j] for j in idx[qi]]
        run_scores[qid] = [float(s) for s in scores[qi]]
    return run, run_scores


def pad_candidates(query_ids, doc_ids, per_query: dict):
    """Per-query candidate lists -> a padded (Q, Cmax) matrix of corpus row
    positions (-1 = padding), plus the filtered candidate id lists."""
    doc_pos = {d: i for i, d in enumerate(doc_ids)}
    cands = [[d for d in per_query.get(qid, []) if d in doc_pos]
             for qid in query_ids]
    c_max = max((len(c) for c in cands), default=0)
    idx = np.full((len(query_ids), max(c_max, 1)), -1, np.int32)
    for qi, row in enumerate(cands):
        idx[qi, :len(row)] = [doc_pos[d] for d in row]
    return idx, cands


def rank_candidates(query_ids, s, cands, *, k: int):
    """Candidate-score matrix -> ({qid: [docid...]}, {qid: [score...]}).

    The ONE selection routine every rerank path (dense/blocked materialized,
    streaming single-device, streaming sharded) finalizes through: a
    *stable* descending sort of the (Q, Cmax) score matrix, keeping the top
    ``min(k, len(cands[q]))`` slots per query.  Stability is what makes the
    cross-mode parity guarantee bit-for-bit: duplicate doc ids (and any
    other exact score ties) resolve to the lower candidate slot regardless
    of which path produced the matrix, so identical score matrices imply
    identical runs — not just identical up to tie order.  Padding slots are
    ``-inf`` and sort last; they are additionally fenced off by the
    per-query candidate count, so a ``k`` larger than the candidate list
    never surfaces a pad.
    """
    s = np.asarray(s)
    order = np.argsort(-s, axis=1, kind="stable")
    run, run_scores = {}, {}
    for qi, qid in enumerate(query_ids):
        keep = order[qi, :min(k, len(cands[qi]))]
        run[qid] = [cands[qi][j] for j in keep]
        run_scores[qid] = [float(s[qi, j]) for j in keep]
    return run, run_scores


# default per-block candidate-gather budget for the materialized rerank path
RERANK_BLOCK_BYTES = 256 << 20


def _quantize_values_np(x: np.ndarray, score_dtype: str) -> np.ndarray:
    """Value-level quantization for the host-side rerank path: return the
    f32 array whose entries are exactly what the device would score at
    ``score_dtype`` — bf16 is a round-trip through the storage dtype (a
    bf16 x bf16 product is exact in f32, so f32 math over round-tripped
    values IS the device bf16-input/f32-accumulate matmul up to summation
    order), int8 is dequantized per-row symmetric quantization
    (:func:`repro.core.precision.quantize_rows_np`)."""
    if score_dtype == "bf16":
        return np.asarray(np.asarray(x, jnp.bfloat16), np.float32)
    if score_dtype == "int8":
        from repro.core.precision import quantize_rows_np
        vals, scale = quantize_rows_np(x)
        return vals.astype(np.float32) * scale
    raise ValueError(f"unexpected score_dtype {score_dtype!r}")


def rerank_run(query_ids, q_emb, doc_ids, c_emb, per_query: dict, *, k: int,
               q_block: int = None, block_bytes: int = RERANK_BLOCK_BYTES,
               score_dtype: str = "f32"):
    """RocketQA-style re-rank validation: score only each query's candidate
    list (no global top-k).  ``score_dtype`` quantizes the embeddings at
    value level before the (unchanged, f32) blocked einsum — see
    :func:`_quantize_values_np`.

    Memory model — query-blocked materialized gather: the candidate
    embeddings are gathered one *query block* at a time, ``(Q_block, Cmax,
    D)`` per gather followed by one batched matmul, so peak candidate-block
    memory is ``O(Q_block x Cmax x D)`` instead of the dense gather's
    ``O(Q x Cmax x D)`` (~21 GB at MS MARCO rerank scale: Q=7k, Cmax=1000,
    D=768).  ``q_block`` pins the block height explicitly; when ``None``
    (default) it is auto-sized so one block's gather fits ``block_bytes``
    (256 MiB default), clamped to [1, Q].  Per-element math is unchanged —
    each (q, c) dot product reduces over D exactly as in the dense gather —
    so runs and scores are bit-for-bit identical for every block size,
    including the Q_block=1 and Q_block>=Q extremes (enforced by
    tests/test_rerank_parity.py).  Selection is the shared
    :func:`rank_candidates` (stable tie-break), the same routine the
    streaming rerank stages finalize through.
    """
    validate_score_dtype(score_dtype)
    q = np.asarray(q_emb)
    c = np.asarray(c_emb)
    if score_dtype != "f32":
        q = _quantize_values_np(q, score_dtype)
        c = _quantize_values_np(c, score_dtype)
    cand_idx, cands = pad_candidates(query_ids, doc_ids, per_query)
    valid = cand_idx >= 0
    if not valid.any():
        return {qid: [] for qid in query_ids}, {qid: [] for qid in query_ids}
    Q, c_max = cand_idx.shape
    if q_block is None:
        row_bytes = c_max * c.shape[-1] * c.dtype.itemsize
        q_block = int(max(1, block_bytes // max(row_bytes, 1)))
    q_block = max(1, min(int(q_block), Q))
    s = np.full((Q, c_max), -np.inf, np.float32)
    clipped = np.clip(cand_idx, 0, max(len(doc_ids) - 1, 0))
    for b0 in range(0, Q, q_block):
        b1 = min(b0 + q_block, Q)
        sub = c[clipped[b0:b1]]                       # (Q_block, Cmax, D)
        sb = np.einsum("qcd,qd->qc", sub, q[b0:b1])   # (Q_block, Cmax)
        s[b0:b1] = np.where(valid[b0:b1], sb, -np.inf)
    return rank_candidates(query_ids, s, cands, k=k)
