"""Device-resident serving index, built through the validator's data path.

The whole serve<->validate bit-parity story lives here: the corpus is
tokenized into the SAME pre-padded :class:`~repro.core.engine.TokenStore`
geometry the validation engines use (``chunk_geometry``), encoded with the
SAME cached encoder (``encode_store``), and searched through the SAME
top-k dispatch ``retrieve_run`` uses (``topk_exact`` / ``topk_sharded`` /
pallas ``topk_mips``), with the SAME ``score_dtype`` semantics
(:mod:`repro.core.precision`).  Because encoders are row-independent and
the streaming fold is bit-for-bit equal to the materialized kernels
(locked since PR 1), a query answered here scores exactly what the
validator scored for the promoted checkpoint.

Storage follows the ``MaterializedEngine`` precedent: ``bf16`` stores the
resident ``(N, D)`` matrix in bfloat16 (half the bytes; scoring casts are
then no-ops, value-identical to the validator's f32->bf16 cast), ``int8``
keeps the f32 matrix and quantizes per-row at score time (per-row scales
are chunk/shard-independent, so quantized scores match the streaming
path's exactly).

Sharded corpora whose row count doesn't divide the mesh are zero-padded
(pads land in the LAST shard only) and searches over-request
``k + n_pad`` before a host-side pad filter — every shard's real top-k
survives its local cut, so the filtered prefix equals the unpadded
answer.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import (TokenStore, chunk_geometry, doc_cache_dir,
                               encode_store, place_params)
from repro.core.precision import validate_score_dtype
from repro.core.retrieval import topk_exact, topk_sharded


@dataclasses.dataclass
class ServeConfig:
    """Serving-tier knobs.  The scoring fields (``score_dtype`` / ``impl``
    / ``mesh`` / ``block``) deliberately mirror
    :class:`~repro.core.suite.ValidationConfig` — an index built with the
    validator's values serves bit-identical answers; ``chunk_size`` /
    ``batch_size`` feed the same :func:`chunk_geometry` so the corpus
    TokenStore is padded exactly like the validator's."""

    k: int = 10                       # results per query
    score_dtype: str = "f32"          # f32 | bf16 | int8 (resident storage
                                      # + scoring precision, see module doc)
    impl: str = "xla"                 # xla | pallas top-k kernel
    mesh: Any = None                  # shard corpus rows over this mesh
    block: int = 4096                 # topk scan block rows
    batch_size: int = 64              # corpus encode rows (chunk geometry)
    chunk_size: Optional[int] = None  # override: TokenStore chunk rows
    max_batch: int = 8                # query micro-batch (QueryService)
    flush_ms: float = 4.0             # max-latency flush (QueryService)
    max_pending: int = 256            # admission bound (QueryService)
    token_backing: str = "memory"     # memory | mmap TokenStore backing
    mmap_dir: Optional[str] = None
    token_fingerprint: str = "fast"


@dataclasses.dataclass
class ServingIndex:
    """One checkpoint's immutable serving state: the device-resident
    corpus embeddings PLUS the checkpoint params (queries must be encoded
    by the same checkpoint the corpus was), swapped as a unit by the
    promoter's atomic pointer flip."""

    step: int
    params: Any
    doc_ids: List[str]
    emb: jnp.ndarray                  # (N + n_pad, D) device-resident
    n_docs: int                       # real rows (pads excluded)
    score_dtype: str
    impl: str
    mesh: Any
    axis_names: Optional[Tuple[str, ...]]
    block: int
    build_s: float

    @property
    def n_pad(self) -> int:
        return int(self.emb.shape[0]) - self.n_docs

    def topk(self, q_emb, *, k: int):
        """Raw top-k over the resident matrix — the validator's
        ``retrieve_run`` dispatch verbatim, plus the pad over-request on
        the sharded path.  Returns host ``(scores, idx)`` truncated to
        ``k`` real rows per query."""
        kk = min(k + self.n_pad, int(self.emb.shape[0]))
        if self.impl == "pallas":
            from repro.kernels.topk_mips import ops as mips_ops
            s, i = mips_ops.topk_mips(jnp.asarray(q_emb), self.emb, k=kk,
                                      score_dtype=self.score_dtype)
        elif self.mesh is not None:
            s, i = topk_sharded(self.mesh, jnp.asarray(q_emb), self.emb,
                                k=kk, axis_names=self.axis_names,
                                block=self.block,
                                score_dtype=self.score_dtype)
        else:
            s, i = topk_exact(jnp.asarray(q_emb), self.emb, k=kk,
                              block=self.block,
                              score_dtype=self.score_dtype)
        s, i = np.asarray(s), np.asarray(i)
        if not self.n_pad:
            return s[:, :k], i[:, :k]
        out_s = np.empty((s.shape[0], k), s.dtype)
        out_i = np.empty((s.shape[0], k), i.dtype)
        for qi in range(s.shape[0]):
            keep = i[qi] < self.n_docs          # pads score 0; drop them
            out_s[qi] = s[qi, keep][:k]
            out_i[qi] = i[qi, keep][:k]
        return out_s, out_i

    def search(self, q_emb, *, k: int):
        """Per-row answers: ``(ids_rows, score_rows)`` lists — row ``r``
        of ``q_emb`` gets its top-``k`` doc ids and scores.  Positional
        (not a dict) so duplicate query ids inside one micro-batch can't
        collide."""
        s, i = self.topk(q_emb, k=k)
        ids = [[self.doc_ids[j] for j in row] for row in i]
        scores = [[float(v) for v in row] for row in s]
        return ids, scores

    def search_run(self, query_ids: Sequence[str], q_emb, *, k: int):
        """``retrieve_run``-shaped convenience: ``({qid: [docid...]},
        {qid: [score...]})`` for parity harnesses and TREC writers."""
        ids, scores = self.search(q_emb, k=k)
        return ({q: r for q, r in zip(query_ids, ids)},
                {q: r for q, r in zip(query_ids, scores)})


class IndexBuilder:
    """Builds a :class:`ServingIndex` per promoted checkpoint.

    The corpus TokenStore is padded ONCE at construction (the expensive,
    checkpoint-independent half) and reused across every build — the same
    built-once-shared-forever discipline as the suite's store cache; only
    the encode pass reruns per checkpoint, through the same jitted/sharded
    encoder the validator streams with."""

    def __init__(self, spec, corpus: Dict[str, Sequence[int]],
                 cfg: Optional[ServeConfig] = None):
        self.cfg = cfg if cfg is not None else ServeConfig()
        validate_score_dtype(self.cfg.score_dtype)
        self.spec = spec
        self.doc_ids = list(corpus)
        chunk, _ = chunk_geometry(self.cfg, len(self.doc_ids), self.cfg.mesh)
        self.store = TokenStore.build(
            [corpus[d] for d in self.doc_ids],
            max_len=spec.p_max_len, chunk=chunk,
            backing=self.cfg.token_backing,
            cache_dir=doc_cache_dir(self.cfg.mmap_dir),
            fingerprint=self.cfg.token_fingerprint)
        self.index_builds = 0

    def build(self, params, step: int) -> ServingIndex:
        cfg = self.cfg
        t0 = time.time()
        axis_names = (tuple(cfg.mesh.axis_names)
                      if cfg.mesh is not None else None)
        # device-resident like the index: every query micro-batch encodes
        # with these params
        params = place_params(params, cfg.mesh)
        c_emb = encode_store(self.spec.encode_passage, params, self.store,
                             mesh=cfg.mesh, axis_names=axis_names)
        n_docs = int(c_emb.shape[0])
        if cfg.mesh is not None:
            n_shards = int(np.prod([cfg.mesh.shape[a] for a in axis_names]))
            pad = (-n_docs) % n_shards
            if pad:
                c_emb = jnp.concatenate(
                    [c_emb, jnp.zeros((pad, c_emb.shape[1]), c_emb.dtype)])
        if cfg.score_dtype == "bf16":
            # resident matrix shrinks 2x; scoring's bf16 cast becomes a
            # no-op over values the validator's f32->bf16 cast produced
            c_emb = jnp.asarray(c_emb, jnp.bfloat16)
        if cfg.mesh is not None:
            from repro.distributed.sharding import rows_sharding
            c_emb = jax.device_put(c_emb,
                                   rows_sharding(cfg.mesh, axis_names))
        c_emb.block_until_ready()
        self.index_builds += 1
        return ServingIndex(
            step=int(step), params=params, doc_ids=self.doc_ids, emb=c_emb,
            n_docs=n_docs, score_dtype=cfg.score_dtype, impl=cfg.impl,
            mesh=cfg.mesh, axis_names=axis_names, block=cfg.block,
            build_s=time.time() - t0)
