"""Device-resident serving index, scored by the validator's own stage.

The corpus is tokenized into the validator's :class:`~repro.core.engine.
TokenStore` geometry (``chunk_geometry`` of the held
:class:`~repro.core.suite.ValidationConfig`) and encoded with the same
cached encoder (``encode_store``).  The embeddings stay resident
chunk-major: chunk ``c`` holds rows ``[c * chunk, (c + 1) * chunk)``, the
ragged last chunk zero-padded.  A query block is answered by folding those
chunks through the retrieval stage ``make_stage`` builds for the config —
the same fused score → mask → merge the validator runs, with an encoder
that returns the resident chunk as it is.  Rows past a chunk's ``n_valid``
are masked inside the stage exactly as in the validator, so the answer is
the validator's, bit for bit, with no over-request and no host-side pad
filter.

``score_dtype`` follows :mod:`repro.core.precision` inside the stage;
``bf16`` additionally stores the resident chunks in bfloat16 (half the
bytes; the stage's bf16 cast is then a no-op over the values the
validator's cast produces).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import (Stage, TokenStore, chunk_geometry,
                               doc_cache_dir, encode_store, make_stage,
                               place_params)
from repro.core.suite import ValidationConfig


@dataclasses.dataclass
class ServeConfig:
    """Serving-tier knobs: the request plane's own fields plus the
    :class:`~repro.core.suite.ValidationConfig` the index scores with.
    Every scoring and geometry field — ``k``, ``score_dtype``, ``impl``,
    ``mesh``, ``batch_size``/``chunk_size``, the TokenStore backing — is
    read from ``scoring`` exactly as the validator reads it, so an index
    built with the validator's config serves the validator's answers."""

    max_batch: int = 8                # query micro-batch (QueryService)
    flush_ms: float = 4.0             # max-latency flush (QueryService)
    max_pending: int = 256            # admission bound (QueryService)
    scoring: ValidationConfig = dataclasses.field(
        default_factory=lambda: ValidationConfig(k=10))


def _resident(params, emb, _mask):
    """The index stage's encoder: the chunk is already embedded."""
    return emb


def _windowed(stage: Stage) -> bool:
    """The single-device XLA stage folds every chunk in one dispatch."""
    return getattr(stage, "window", 1) > 1


@dataclasses.dataclass
class ServingIndex:
    """One checkpoint's immutable serving state: the device-resident
    corpus embeddings PLUS the checkpoint params (queries must be encoded
    by the same checkpoint the corpus was), swapped as a unit by the
    promoter's atomic pointer flip."""

    step: int
    params: Any
    doc_ids: List[str]
    stage: Stage                      # the validator's retrieval stage
    # one (embeddings, base row(s), valid rows) per dispatch: the stacked
    # (n_chunks, chunk, D) corpus for the windowed stage, else each
    # (chunk, D) chunk placed as the stage's input_sharding expects
    folds: Tuple[Tuple[Any, Any, Any], ...]
    build_s: float

    @property
    def n_docs(self) -> int:
        return len(self.doc_ids)

    def topk(self, q_emb, *, k: int):
        """Host ``(scores, rows)`` of the top ``min(k, stage k)`` corpus
        rows for every row of ``q_emb``, folded chunk by chunk."""
        q_emb = jnp.asarray(q_emb)
        fold = (self.stage.step_window if _windowed(self.stage)
                else self.stage.step)
        carry = self.stage.init(q_emb)
        for emb, base, n_valid in self.folds:
            carry = fold(None, q_emb, carry, emb, emb, base, n_valid)
        return np.asarray(carry[0])[:, :k], np.asarray(carry[1])[:, :k]

    def search(self, q_emb, *, k: int):
        """Per-row answers: ``(ids_rows, score_rows)`` lists — row ``r``
        of ``q_emb`` gets its top-``k`` doc ids and scores.  Positional
        (not a dict) so duplicate query ids inside one micro-batch can't
        collide."""
        s, i = self.topk(q_emb, k=k)
        return [[self.doc_ids[j] for j in row] for row in i], s.tolist()

    def search_run(self, query_ids: Sequence[str], q_emb, *, k: int):
        """``retrieve_run``-shaped convenience: ``({qid: [docid...]},
        {qid: [score...]})`` for parity harnesses and TREC writers."""
        ids, scores = self.search(q_emb, k=k)
        return ({q: r for q, r in zip(query_ids, ids)},
                {q: r for q, r in zip(query_ids, scores)})


class IndexBuilder:
    """Builds a :class:`ServingIndex` per promoted checkpoint.

    The corpus TokenStore (the expensive, checkpoint-independent half) and
    the retrieval stage are built ONCE at construction and shared by every
    build; only the encode pass reruns per checkpoint, through the same
    jitted/sharded encoder the validator streams with."""

    def __init__(self, spec, corpus: Dict[str, Sequence[int]],
                 cfg: Optional[ServeConfig] = None):
        self.cfg = cfg if cfg is not None else ServeConfig()
        vcfg = self.cfg.scoring
        self.spec = spec
        self.doc_ids = list(corpus)
        chunk, _ = chunk_geometry(vcfg, len(self.doc_ids), vcfg.mesh)
        self.store = TokenStore.build(
            [corpus[d] for d in self.doc_ids],
            max_len=spec.p_max_len, chunk=chunk,
            backing=vcfg.token_backing,
            cache_dir=doc_cache_dir(vcfg.mmap_dir),
            fingerprint=vcfg.token_fingerprint)
        self.stage = make_stage(_resident, mode="retrieval", impl=vcfg.impl,
                                mesh=vcfg.mesh, k=vcfg.k, query_ids=[],
                                doc_ids=self.doc_ids,
                                scan_window=vcfg.scan_window,
                                score_dtype=vcfg.score_dtype)
        self.index_builds = 0

    def build(self, params, step: int) -> ServingIndex:
        vcfg, store = self.cfg.scoring, self.store
        t0 = time.time()
        # device-resident like the index: every query micro-batch encodes
        # with these params
        params = place_params(params, vcfg.mesh)
        emb = encode_store(self.spec.encode_passage, params, store,
                           mesh=vcfg.mesh)
        if vcfg.score_dtype == "bf16":
            emb = jnp.asarray(emb, jnp.bfloat16)
        n, chunk = store.n_texts, store.chunk
        emb = jnp.pad(emb, ((0, store.n_chunks * chunk - n), (0, 0)))
        emb = emb.reshape(store.n_chunks, chunk, emb.shape[-1])
        n_valid = [store.rows_valid(c) for c in range(store.n_chunks)]
        if _windowed(self.stage) and n:
            bases = jnp.arange(store.n_chunks, dtype=jnp.int32) * chunk
            folds = ((emb, bases, jnp.asarray(n_valid, jnp.int32)),)
        else:
            put = getattr(self.stage, "input_sharding", None)
            folds = tuple((jax.device_put(emb[c], put), c * chunk,
                           n_valid[c]) for c in range(store.n_chunks))
        jax.block_until_ready([f[0] for f in folds])
        self.index_builds += 1
        return ServingIndex(step=int(step), params=params,
                            doc_ids=self.doc_ids, stage=self.stage,
                            folds=folds, build_s=time.time() - t0)
