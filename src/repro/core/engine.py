"""Streaming device-resident ValidationEngine — encode→top-k with no host hop.

The legacy ``ValidationPipeline`` path materialized the full ``(N, D)`` corpus
embedding matrix on host (one ``np.asarray`` per batch), then shipped it back
to device for retrieval: 2x the memory traffic and a hard host-RAM cap on
corpus size.  This module replaces that with a staged, device-resident
pipeline:

  1. :class:`TokenStore` — the corpus is padded ONCE into fixed-shape
     ``(chunk, L)`` token/mask chunks (the paper's §3 pre-tokenization
     argument, extended to pre-padding: the cost amortizes across every
     checkpoint the validator ever sees, and every chunk compiles to the
     same XLA program).  With ``backing="mmap"`` the chunks live in
     memory-mapped files on disk (built once, reused across checkpoints and
     processes), so even the corpus *tokens* can exceed host RAM.
  2. A **fused encode→top-k streaming loop** — each chunk is encoded on
     device and its scores are immediately folded into the running ``(Q, k)``
     top-k carry inside one jitted step; the chunk's embedding buffer is an
     XLA temporary, freed as soon as the step retires.  Peak embedding
     memory is ``O(chunk x D + Q x k)`` — the ``(N, D)`` matrix is *never*
     materialized, on host or device, so the corpus can exceed host RAM.
  3. **Pipelined host→device staging** (:func:`staged_batches`) — the
     async ``jax.device_put`` of chunk ``i+1`` is issued while chunk ``i``'s
     fused step is still in flight, for both the single-device and
     ``shard_map`` paths (sharded chunks are placed with the row sharding
     the step's ``in_specs`` expect, so no re-layout happens at dispatch).
     The prefetch depth is configurable (``staging_depth``; 2 = the classic
     double buffer, deeper for remote-storage token stores).  Peak
     host-staged token memory is ``O(depth x window x chunk x L)``.
  4. A shared :class:`Stage` interface through which every validation mode
     (``retrieval``, ``rerank``, ``average_rank``) and every implementation
     (``xla``, ``pallas`` via ``repro.kernels.topk_mips``, sharded via
     ``shard_map`` on the validator mesh) is routed — rerank included: the
     sharded rerank stage shards chunk rows over the mesh and folds per-
     shard candidate scores with a slot-aligned hierarchical merge, so
     ``make_stage(mode="rerank", mesh=...)`` scales exactly like retrieval.
     Query encoding routes through the same sharded path
     (``encode_store(mesh=...)``) so huge query sets shard with the corpus.

``MaterializedEngine`` preserves the legacy encode-all-then-retrieve path
behind the same interface for A/B benchmarking
(``benchmarks/bench_streaming_engine.py``) and backward compatibility.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import hashlib
import json
import os
import time
from typing import (Any, Callable, Dict, Iterator, List, Mapping, Optional,
                    Sequence, Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.encoder import cached_compiled, encode_texts, jitted_encoder
from repro.core.metrics import RankedRun, RankedScores, RunIds
from repro.core.precision import chunk_scores, validate_score_dtype
from repro.core.registry import (ENGINES, IMPLS, MODES, STAGES,
                                 register_engine, register_impl,
                                 register_mode, register_stage)
from repro.core.retrieval import (_hierarchical_slot_max,
                                  _hierarchical_topk_merge, _merge_topk,
                                  pad_candidates, rank_candidates, rerank_run,
                                  retrieve_run)
from repro.data.corpus import Tokens, pad_batch
from repro.obs import HELD_ASSIGNMENTS_METRIC, MERGE_SORTS_METRIC

Run = Mapping[str, List[str]]
Scores = Mapping[str, List[float]]


def _donate(*argnums: int) -> tuple:
    """Donation positions for the top-k carry — skipped on CPU where XLA
    cannot alias the buffers (it would only warn)."""
    return () if jax.default_backend() == "cpu" else argnums


_NULL_CM = contextlib.nullcontext()


def _span(tel, name: str, **attrs):
    """``tel.span(name)``, or a shared no-op without telemetry."""
    return _NULL_CM if tel is None else tel.span(name, **attrs)


def _in_scope(scope: str, fn: Callable) -> Callable:
    """``fn`` traced under ``jax.named_scope(scope)``, so a jit of it names
    its device ops by ``scope``."""
    def scoped(*args):
        with jax.named_scope(scope):
            return fn(*args)
    return scoped


# ---------------------------------------------------------------------------
# Stage 1: TokenStore — pad/chunk the corpus once, amortized over checkpoints
# ---------------------------------------------------------------------------


_STORE_META = "store_meta.json"
_STORE_TOKENS = "tokens.int32.bin"
_STORE_MASK = "mask.bool.bin"
_STORE_MANIFEST = "chunk_hashes.json"
_STORE_VERSION = 1


def _chunk_hash(texts: Sequence[Tokens]) -> str:
    """Content hash of one chunk's texts (the unit of the full-fingerprint
    manifest: a changed chunk hash means exactly that chunk must be
    re-padded and re-written)."""
    h = hashlib.sha1()
    for t in texts:
        h.update(np.asarray(list(t), np.int64).tobytes())
        h.update(b"|")
    return h.hexdigest()


def _full_fingerprint(chunk_hashes: Sequence[str], *, n: int, max_len: int,
                      chunk: int) -> str:
    """Overall full-content fingerprint, derived from the per-chunk hashes
    so the digest and the manifest can never disagree."""
    h = hashlib.sha1()
    h.update(f"v{_STORE_VERSION}:full:{n}:{max_len}:{chunk}".encode())
    for ch in chunk_hashes:
        h.update(ch.encode())
    return h.hexdigest()


def _store_fingerprint(texts: Sequence[Tokens], *, max_len: int,
                       chunk: int, mode: str = "fast") -> str:
    """Content fingerprint for mmap-cache reuse.

    ``mode="fast"`` (default): geometry plus a hash of the first/last 16
    texts.  Deliberately O(1) in corpus size — the point of the cache is to
    NOT re-read millions of texts per checkpoint.  The documented hazard:
    a caller that mutates the *middle* of a corpus in place (same length,
    same edges) gets a stale cache hit; such callers must use a fresh
    ``cache_dir`` or opt into ``mode="full"``.

    ``mode="full"``: hashes every text — O(corpus) per build, but any
    single-token mutation anywhere invalidates the cache.  The two modes
    hash disjoint tag prefixes, so switching modes always rebuilds rather
    than trusting the other mode's marker.
    """
    if mode not in ("fast", "full"):
        raise ValueError(f"unknown fingerprint mode {mode!r} "
                         "(expected 'fast' or 'full')")
    if mode == "full":
        n_chunks = -(-len(texts) // max(chunk, 1)) if len(texts) else 0
        hashes = [_chunk_hash(texts[ci * chunk:(ci + 1) * chunk])
                  for ci in range(n_chunks)]
        return _full_fingerprint(hashes, n=len(texts), max_len=max_len,
                                 chunk=chunk)
    h = hashlib.sha1()
    h.update(f"v{_STORE_VERSION}:{mode}:{len(texts)}:{max_len}:{chunk}"
             .encode())
    for t in list(texts[:16]) + list(texts[-16:]):
        h.update(np.asarray(list(t), np.int64).tobytes())
        h.update(b"|")
    return h.hexdigest()


@dataclasses.dataclass
class TokenStore:
    """Corpus tokens padded into fixed-shape device-friendly chunks.

    ``tokens``/``mask`` are ``(n_chunks, chunk, L)`` host arrays; every chunk
    has the same shape (the final ragged chunk is zero-padded and masked by
    ``n_valid``), so the fused step compiles exactly once.  With
    ``backing="mmap"`` they are read-only ``numpy.memmap`` views over files
    in ``cache_dir`` and only the staged chunks ever occupy host RAM.
    """

    tokens: np.ndarray          # (n_chunks, chunk, L) int32
    mask: np.ndarray            # (n_chunks, chunk, L) bool
    chunk: int
    n_texts: int
    backing: str = "memory"     # memory | mmap
    cache_dir: Optional[str] = None
    reused: bool = False        # mmap only: True when cache files were reused
    rebuilt_chunks: int = 0     # chunks padded+written by THIS build (0 on a
                                # cache hit; < n_chunks on a full-fingerprint
                                # incremental rebuild via the hash manifest)

    @classmethod
    def build(cls, texts: Sequence[Tokens], *, max_len: int, chunk: int,
              backing: str = "memory", cache_dir: Optional[str] = None,
              fingerprint: str = "fast") -> "TokenStore":
        """Pad ``texts`` into ``(n_chunks, chunk, max_len)`` token/mask arrays.

        ``backing="memory"`` (default) holds both arrays in host RAM.

        ``backing="mmap"`` spills them to memory-mapped files under
        ``cache_dir`` (required), built once and reused by every later
        ``build`` with the same geometry + content fingerprint — across
        checkpoints AND across processes.  ``fingerprint`` picks the cache
        key: ``"fast"`` (default) is O(1) in corpus size (geometry + edge
        texts — a *middle* mutation with unchanged edges is a documented
        stale hit; use a fresh ``cache_dir`` or ``"full"``), ``"full"``
        hashes every text so any in-place mutation rebuilds the cache (see
        :func:`_store_fingerprint`).  On-disk format (version 1):

        * ``store_meta.json`` — ``{"version", "n_texts", "chunk", "max_len",
          "n_chunks", "fingerprint"}``; written LAST, so a torn build (crash
          mid-write) is never mistaken for a valid cache.
        * ``tokens.int32.bin`` — raw C-order ``(n_chunks, chunk, max_len)``
          little-endian int32, zero-padded past each text's length and past
          ``n_texts`` in the final ragged chunk.
        * ``mask.bool.bin`` — raw C-order ``(n_chunks, chunk, max_len)``
          1-byte bool, ``True`` exactly on real token positions.
        * ``chunk_hashes.json`` — ``fingerprint="full"`` only: the per-chunk
          content-hash manifest ``{"version", "hashes": [sha1, ...]}``.  On a
          rebuild with unchanged geometry, only chunks whose hash differs
          from the manifest are re-padded and re-written (the memmaps are
          opened ``r+``), so full-fidelity revalidation costs O(changed
          chunks) of padding/IO instead of O(corpus) — change detection
          itself is a hash pass, which is what ``full`` already paid.
          Written immediately before the meta marker; fast-mode rebuilds
          delete it so it can never describe bins they rewrote.

        The build itself streams chunk by chunk, so peak host memory during
        construction is ``O(chunk x max_len)`` regardless of corpus size;
        afterwards the maps are reopened read-only (``mode="r"``) so the
        cache cannot be corrupted by a stray write.
        """
        if fingerprint not in ("fast", "full"):
            raise ValueError(f"unknown fingerprint mode {fingerprint!r} "
                             "(expected 'fast' or 'full')")
        n = len(texts)
        chunk = max(1, chunk)
        n_chunks = -(-n // chunk) if n else 0
        shape = (n_chunks, chunk, max_len)
        if backing == "memory":
            toks = np.zeros(shape, np.int32)
            mask = np.zeros(shape, bool)
            for ci in range(n_chunks):
                part = list(texts[ci * chunk:(ci + 1) * chunk])
                t, m = pad_batch(part, max_len)
                toks[ci, :len(part)] = t
                mask[ci, :len(part)] = m
            return cls(tokens=toks, mask=mask, chunk=chunk, n_texts=n,
                       rebuilt_chunks=n_chunks)
        if backing != "mmap":
            raise ValueError(f"unknown TokenStore backing {backing!r} "
                             "(expected 'memory' or 'mmap')")
        if not cache_dir:
            raise ValueError("TokenStore backing='mmap' needs a cache_dir")
        os.makedirs(cache_dir, exist_ok=True)
        meta_path = os.path.join(cache_dir, _STORE_META)
        tok_path = os.path.join(cache_dir, _STORE_TOKENS)
        mask_path = os.path.join(cache_dir, _STORE_MASK)
        manifest_path = os.path.join(cache_dir, _STORE_MANIFEST)
        chunk_hashes: Optional[List[str]] = None
        if fingerprint == "full":
            chunk_hashes = [_chunk_hash(texts[ci * chunk:(ci + 1) * chunk])
                            for ci in range(n_chunks)]
            fp = _full_fingerprint(chunk_hashes, n=n, max_len=max_len,
                                   chunk=chunk)
        else:
            fp = _store_fingerprint(texts, max_len=max_len, chunk=chunk,
                                    mode=fingerprint)
        meta = {"version": _STORE_VERSION, "n_texts": n, "chunk": chunk,
                "max_len": max_len, "n_chunks": n_chunks, "fingerprint": fp}
        n_slots = int(np.prod(shape))
        stored = None
        if os.path.exists(meta_path):
            try:
                with open(meta_path) as f:
                    stored = json.load(f)
            except ValueError:      # torn/truncated meta: rebuild, not crash
                stored = None
        # a valid marker alone is not enough: the bins must exist with
        # exactly the bytes the marker promises (a partially copied or
        # hand-cleaned cache_dir must rebuild, not crash or mis-map)
        sizes_ok = True
        if n_chunks:
            try:
                sizes_ok = (os.path.getsize(tok_path) == n_slots * 4
                            and os.path.getsize(mask_path) == n_slots)
            except OSError:
                sizes_ok = False
        same_geometry = stored is not None and all(
            stored.get(k) == meta[k]
            for k in ("version", "n_texts", "chunk", "max_len", "n_chunks"))
        reused = (same_geometry and sizes_ok
                  and stored.get("fingerprint") == fp)
        rebuilt: List[int] = []
        if not reused and n_chunks:
            # full-fingerprint incremental rebuild: when the geometry is
            # unchanged and the previous *full* build left a per-chunk hash
            # manifest, only chunks whose hash changed are re-padded and
            # re-written — O(changed chunks) instead of O(corpus).  The
            # manifest is trustworthy because every code path that rewrites
            # the bins either rewrites it too (full builds, below) or
            # removes it (fast builds), and a reused cache touches neither.
            prev_hashes: Optional[List[str]] = None
            if same_geometry and sizes_ok and chunk_hashes is not None:
                try:
                    with open(manifest_path) as f:
                        prev = json.load(f)
                    if (prev.get("version") == _STORE_VERSION
                            and isinstance(prev.get("hashes"), list)
                            and len(prev["hashes"]) == n_chunks):
                        prev_hashes = prev["hashes"]
                except (OSError, ValueError):
                    prev_hashes = None
            incremental = prev_hashes is not None
            rebuilt = ([ci for ci in range(n_chunks)
                        if prev_hashes[ci] != chunk_hashes[ci]]
                       if incremental else list(range(n_chunks)))
            # invalidate the old commit marker FIRST: if this rebuild dies
            # mid-write, no stale meta can bless the half-rewritten bins
            if os.path.exists(meta_path):
                os.remove(meta_path)
            if not incremental and os.path.exists(manifest_path):
                # bins are about to stop matching the old manifest; a fast
                # build writes no replacement, so the stale one must go
                os.remove(manifest_path)
            wmode = "r+" if incremental else "w+"
            wt = np.memmap(tok_path, dtype=np.int32, mode=wmode, shape=shape)
            wm = np.memmap(mask_path, dtype=bool, mode=wmode, shape=shape)
            for ci in rebuilt:
                part = list(texts[ci * chunk:(ci + 1) * chunk])
                t, m = pad_batch(part, max_len)
                wt[ci] = 0
                wm[ci] = False
                wt[ci, :len(part)] = t
                wm[ci, :len(part)] = m
            wt.flush()
            wm.flush()
            del wt, wm
        if not reused:
            if chunk_hashes is not None:
                # manifest before meta: a crash in between leaves no meta,
                # forcing a rebuild — never a meta blessing a stale manifest
                tmp = manifest_path + ".tmp"
                with open(tmp, "w") as f:
                    json.dump({"version": _STORE_VERSION,
                               "hashes": chunk_hashes}, f)
                os.replace(tmp, manifest_path)
            # commit marker: meta written LAST, and atomically (a crash
            # mid-write must leave no half-valid marker behind)
            tmp = meta_path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(meta, f)
            os.replace(tmp, meta_path)
        if n_chunks:
            toks = np.memmap(tok_path, dtype=np.int32, mode="r", shape=shape)
            mask = np.memmap(mask_path, dtype=bool, mode="r", shape=shape)
        else:
            toks = np.zeros(shape, np.int32)
            mask = np.zeros(shape, bool)
        return cls(tokens=toks, mask=mask, chunk=chunk, n_texts=n,
                   backing="mmap", cache_dir=cache_dir, reused=reused,
                   rebuilt_chunks=len(rebuilt))

    @property
    def n_chunks(self) -> int:
        return self.tokens.shape[0]

    def rows_valid(self, ci: int) -> int:
        return min(self.chunk, self.n_texts - ci * self.chunk)

    def chunks(self) -> Iterator[Tuple[jnp.ndarray, jnp.ndarray, int, int]]:
        """Yield (tokens, mask, base_row, n_valid_rows) per chunk."""
        for ci in range(self.n_chunks):
            yield (jnp.asarray(self.tokens[ci]), jnp.asarray(self.mask[ci]),
                   ci * self.chunk, self.rows_valid(ci))

    def candidate_map(self, cand_idx: np.ndarray) -> "CandidateMap":
        """Precompute candidate membership against THIS store's chunking.

        ``cand_idx`` is the padded ``(Q, Cmax)`` slot map of global corpus
        rows from :func:`repro.core.retrieval.pad_candidates` (-1 = pad).
        The result is what lets rerank stages touch only the corpus that
        matters: a per-chunk ``(chunk,)`` row-membership mask (is this row
        any query's candidate?) plus per-chunk counts the engine uses to
        skip — never stage, never encode — chunks with zero candidate rows.
        Built once per validator lifetime, like the store itself.
        """
        rows = np.unique(cand_idx[cand_idx >= 0])
        rows = rows[rows < self.n_texts]
        row_mask = np.zeros((self.n_chunks, self.chunk), bool)
        if rows.size and self.n_chunks:
            row_mask[rows // self.chunk, rows % self.chunk] = True
        return CandidateMap(slot_map=np.asarray(cand_idx, np.int32),
                            row_mask=row_mask,
                            chunk_counts=row_mask.sum(axis=1),
                            chunk=self.chunk)


@dataclasses.dataclass
class CandidateMap:
    """Per-chunk candidate membership for the rerank stages (built on the
    TokenStore side, where the chunk geometry lives).

    ``slot_map`` is the replicated ``(Q, Cmax)`` candidate slot map (global
    corpus rows, -1 = pad); ``row_mask[ci]`` is the ``(chunk,)`` mask of
    rows in chunk ``ci`` that appear in ANY query's candidate set; and
    ``chunk_counts[ci]`` is its popcount — zero means the chunk holds no
    candidates and the engine skips it entirely (no staging, no encode).
    """

    slot_map: np.ndarray        # (Q, Cmax) int32 global rows, -1 = pad
    row_mask: np.ndarray        # (n_chunks, chunk) bool candidate membership
    chunk_counts: np.ndarray    # (n_chunks,) int per-chunk candidate rows
    chunk: int

    def has_candidates(self, ci: int) -> bool:
        return bool(self.chunk_counts[ci])


# Sharded-encoder cache keyed on (encode_fn, mesh, axis_names) — one compiled
# shard_map executable per encoder+mesh, shared across checkpoints (the same
# per-checkpoint retrace bug ``jitted_encoder`` fixes for the 1-device path).
# Bounded-LRU via encoder.cached_compiled, same policy as _JIT_CACHE.
_SHARDED_ENC_CACHE: "collections.OrderedDict" = collections.OrderedDict()


def _sharded_encoder(encode_fn: Callable, mesh,
                     axis_names: Tuple[str, ...]) -> Callable:
    ax = axis_names[0] if len(axis_names) == 1 else axis_names

    def build():
        return jax.jit(jax.shard_map(
            encode_fn, mesh=mesh, in_specs=(P(), P(ax), P(ax)),
            out_specs=P(ax), check_vma=False))

    return cached_compiled(_SHARDED_ENC_CACHE, (encode_fn, mesh, axis_names),
                           build)


def place_params(params, mesh=None):
    """``params`` on the default device, or replicated over ``mesh``."""
    if mesh is None:
        return jax.device_put(params)
    from repro.distributed.sharding import replicated_sharding
    return jax.device_put(params, replicated_sharding(mesh))


def encode_store(encode_fn: Callable, params, store: TokenStore, *,
                 mesh=None, axis_names=None) -> jnp.ndarray:
    """Encode a TokenStore fully — used for queries, whose ``(Q, D)`` matrix
    is part of the streaming carry anyway.  Stays on device.

    With ``mesh`` the chunk rows are sharded over ``axis_names`` and each
    shard encodes its rows under one ``shard_map`` — the same sharded stage
    the corpus streams through, so huge query sets scale with the mesh
    instead of capping on one device.  Requires ``store.chunk`` divisible by
    the shard count (:func:`chunk_geometry` gives each shard whole rows).
    """
    if mesh is None:
        fn = jitted_encoder(encode_fn)
        put = None
    else:
        from repro.distributed.sharding import rows_sharding
        axis_names = tuple(axis_names or mesh.axis_names)
        fn = _sharded_encoder(encode_fn, mesh, axis_names)
        put = rows_sharding(mesh, axis_names)
    outs = []
    for toks, mask in staged_batches(store,
                                     plan_schedule(store.n_chunks, 1),
                                     sharding=put):
        outs.append(fn(params, toks, mask))
    if not outs:
        return jnp.zeros((0, 1), jnp.float32)
    return jnp.concatenate(outs, axis=0)[:store.n_texts]


# ---------------------------------------------------------------------------
# Stage 2: host→device staging — double-buffered device_put ahead of compute
# ---------------------------------------------------------------------------


def plan_schedule(n_chunks: int, window: int) -> List[Tuple[int, int]]:
    """Dispatch schedule ``[(first_chunk, n_chunks_in_batch), ...]``.

    ``window`` > 1 groups that many chunks per dispatch with a halving tail:
    a corpus of C chunks costs ~C/window + log2(window) dispatches and at
    most log2(window)+2 compiled programs (amortized across every checkpoint
    the engine ever validates)."""
    out: List[Tuple[int, int]] = []
    ci, w = 0, max(1, window)
    while ci < n_chunks:
        while w > 1 and ci + w > n_chunks:
            w //= 2
        out.append((ci, w))
        ci += w
    return out


def staged_batches(store: TokenStore, schedule: Sequence[Tuple[int, int]], *,
                   sharding=None, depth: int = 2,
                   _put: Callable = None) -> Iterator[Tuple[Any, Any]]:
    """Yield ``(tokens, mask)`` device buffers for each schedule entry,
    staged ``depth`` batches ahead of the consumer.

    ``depth=1`` is synchronous staging (copy, then compute).  ``depth=2``
    (default) is the double buffer: when batch ``i`` is yielded, batch
    ``i+1``'s ``jax.device_put`` has already been issued, so the host→device
    copy of the next chunk overlaps the fused encode→top-k step of the
    current one — the consumer's compute dispatch returns before the copy is
    needed.  Peak host-staged token memory is ``O(depth x w x chunk x L)``
    (with a memory-backed store the whole corpus is resident anyway; with
    ``backing="mmap"`` this bound is the engine's entire host token
    footprint).

    ``sharding`` (a ``Sharding``) places each batch directly in the layout
    the consuming jitted step expects — for the ``shard_map`` stage the rows
    land pre-sharded across the mesh, so dispatch does no re-layout.
    """
    put = _put or (lambda x: jax.device_put(x, sharding))
    depth = max(1, depth)

    def stage(ci: int, w: int) -> Tuple[Any, Any]:
        if w == 1:
            return put(store.tokens[ci]), put(store.mask[ci])
        return put(store.tokens[ci:ci + w]), put(store.mask[ci:ci + w])

    q: "collections.deque" = collections.deque()
    idx = 0
    while q or idx < len(schedule):
        while idx < len(schedule) and len(q) < depth:
            q.append(stage(*schedule[idx]))
            idx += 1
        yield q.popleft()


# ---------------------------------------------------------------------------
# Stage 2+3: fused encode→fold stages behind one interface
# ---------------------------------------------------------------------------


class Stage:
    """One streaming validation strategy: a device carry folded chunk by chunk.

    ``init(q_emb) -> carry``; ``step(params, q_emb, carry, toks, mask, base,
    n_valid) -> carry``; ``finalize(carry) -> (run, run_scores)``, where
    the engine hands ``finalize`` the carry already copied to the host.

    Each jitted program a step dispatches goes through :meth:`_call`: with a
    ``telemetry`` attached (the engine attaches its own), the program's
    device scopes are registered on it once per argument shapes.
    """

    name = "stage"
    telemetry = None

    def _call(self, fn: Callable, *args):
        tel = self.telemetry
        if tel is not None:
            tel.register_program(fn, args)
        return fn(*args)

    def init(self, q_emb: jnp.ndarray):
        raise NotImplementedError

    def step(self, params, q_emb, carry, toks, mask, base: int, n_valid: int):
        raise NotImplementedError

    def finalize(self, carry) -> Tuple[Run, Scores]:
        raise NotImplementedError


def merge_group(k: int, chunk: int, w: int) -> int:
    """How many chunks of a ``w``-chunk scan window one top-k merge takes:
    the largest divisor ``g`` of ``w`` with ``g * chunk <= k``, at least 1.

    A merge sorts the ``k`` carried candidates with the new columns, so a
    wide carry (k 1,000 against 64-row chunks) is cheaper merged once over
    ``g`` chunks, and a narrow one (k 10) once per chunk, where one sort
    over ``k + g * chunk`` columns would cost more than ``g`` over
    ``k + chunk``."""
    return max(g for g in range(1, w + 1)
               if w % g == 0 and (g == 1 or g * chunk <= k))


class StreamTopKStage(Stage):
    """Retrieval mode, XLA path: encode a chunk and merge its local top-k into
    the running (Q, k) carry in a single jitted (fused) step.

    ``window`` > 1 additionally compiles a ``lax.scan`` over that many chunks
    so the engine can fold a whole window of chunks per dispatch; the
    Python/dispatch overhead amortizes ``window``-fold.  The window merges
    every ``g = merge_group(k, chunk, w)`` chunks: an inner scan encodes and
    scores ``g`` chunks with the per-chunk math, and one sort merges their
    ``(Q, g x chunk)`` scores into the carry (``g`` = 1 is the per-chunk
    fold).  The lists are bit for bit those of the per-chunk fold (see
    :func:`repro.core.retrieval._topk_with_ids`).  Token staging grows to
    O(window x chunk x L), score staging to O(Q x g x chunk); embeddings
    stay O(chunk x D).  With a ``telemetry`` attached, each dispatch adds
    the merges it runs to the ``merge.sorts`` counter.

    With ``encode_counted`` (an encoder that also returns the int32 count of
    routed (token, held expert) assignments) the carry gains that count,
    summed over the stream on the device, and ``finalize`` records it in the
    telemetry's ``moe.held_assignments`` histogram, one observation per
    stream.
    """

    name = "topk_xla"

    def __init__(self, encode_fn: Callable, *, k: int, query_ids: List[str],
                 doc_ids: List[str], window: int = 8,
                 score_dtype: str = "f32",
                 encode_counted: Optional[Callable] = None):
        self.query_ids = query_ids
        self.doc_ids = doc_ids
        self.run_ids = RunIds(query_ids, doc_ids)
        self.k = max(1, min(k, len(doc_ids))) if doc_ids else 0
        self.window = max(1, window)
        self.score_dtype = validate_score_dtype(score_dtype)
        self.counted = encode_counted is not None
        k_carry = self.k

        def scored(q_emb, params, toks, mask, n_valid):
            """One chunk's masked (Q, chunk) scores, its column indices and
            the encoder's held count (None without ``encode_counted``)."""
            held = None
            with jax.named_scope("encode"):
                if encode_counted is None:
                    emb = encode_fn(params, toks, mask)       # (chunk, D)
                else:
                    emb, held = encode_counted(params, toks, mask)
            chunk = toks.shape[0]
            col = jnp.arange(chunk, dtype=jnp.int32)
            with jax.named_scope("score"):
                # static precision branch: "f32" keeps the literal legacy
                # expression (bit-for-bit); narrow dtypes cast the chunk's
                # embeddings once, right here, and dequantize to f32 scores
                # before the mask + merge below ever see them.
                if score_dtype == "f32":
                    s = (q_emb @ emb.T).astype(jnp.float32)   # (Q, chunk)
                else:
                    s = chunk_scores(q_emb, emb, score_dtype)  # (Q, chunk)
                s = jnp.where((col < n_valid)[None, :], s, -jnp.inf)
            return s, col, held

        def fold(carry, q_emb, params, toks, mask, base, n_valid):
            run_s, run_i = carry[:2]
            s, col, held = scored(q_emb, params, toks, mask, n_valid)
            # one top-k sort over [carry ‖ chunk]: selecting top-k of the union
            # directly is identical to local-top-k-then-merge (top-k of a set
            # equals top-k of carry ∪ top-k(chunk)) but does one sort of
            # width k+chunk instead of two of width chunk and 2k.
            with jax.named_scope("merge"):
                gcol = jnp.broadcast_to((col + base)[None, :], s.shape)
                merged = _merge_topk(run_s, run_i, s, gcol, k_carry)
            return merged if held is None else merged + (carry[2] + held,)

        def fold_group(carry, q_emb, params, toks_g, mask_g, bases, n_valids):
            """Score ``g`` chunks, then one sort of width k + g x chunk."""
            run_s, run_i = carry[:2]

            def body(held, inp):
                toks, mask, base, n_valid = inp
                s, col, h = scored(q_emb, params, toks, mask, n_valid)
                return (held if h is None else (held[0] + h,)), \
                    (s, col + base)

            held, (s, ids) = jax.lax.scan(body, carry[2:],
                                          (toks_g, mask_g, bases, n_valids))
            g, Q, chunk = s.shape
            with jax.named_scope("merge"):
                # (g, Q, chunk) -> (Q, g x chunk), the chunks in order
                s = jnp.moveaxis(s, 0, 1).reshape(Q, g * chunk)
                ids = jnp.broadcast_to(ids.reshape(1, g * chunk), s.shape)
                merged = _merge_topk(run_s, run_i, s, ids, k_carry)
            return merged + held

        def fused(params, q_emb, run_s, run_i, toks, mask, base, n_valid,
                  *held):
            return fold((run_s, run_i) + held, q_emb, params, toks, mask,
                        base, n_valid)

        def fused_window(params, q_emb, run_s, run_i, toks_w, mask_w,
                         bases, n_valids, *held):
            w, chunk = toks_w.shape[:2]
            g = merge_group(k_carry, chunk, w)
            if g == 1:
                def body(carry, inp):
                    toks, mask, base, n_valid = inp
                    return fold(carry, q_emb, params, toks, mask, base,
                                n_valid), None
                xs = (toks_w, mask_w, bases, n_valids)
            else:
                def body(carry, inp):
                    return fold_group(carry, q_emb, params, *inp), None
                xs = tuple(x.reshape((w // g, g) + x.shape[1:])
                           for x in (toks_w, mask_w, bases, n_valids))
            carry, _ = jax.lax.scan(body, (run_s, run_i) + held, xs)
            return carry

        self._fused = jax.jit(fused, donate_argnums=_donate(2, 3))
        self._fused_window = jax.jit(fused_window,
                                     donate_argnums=_donate(2, 3))

    def _count_merges(self, n: int) -> None:
        tel = self.telemetry
        if tel is not None:
            tel.metrics.counter(MERGE_SORTS_METRIC).inc(n)

    def init(self, q_emb):
        Q = q_emb.shape[0]
        carry = (jnp.full((Q, self.k), -jnp.inf, jnp.float32),
                 jnp.zeros((Q, self.k), jnp.int32))
        return carry + (jnp.zeros((), jnp.int32),) if self.counted else carry

    def step(self, params, q_emb, carry, toks, mask, base, n_valid):
        run_s, run_i, *held = carry
        self._count_merges(1)
        return self._call(self._fused, params, q_emb, run_s, run_i, toks,
                          mask, jnp.asarray(base, jnp.int32),
                          jnp.asarray(n_valid, jnp.int32), *held)

    def step_window(self, params, q_emb, carry, toks_w, mask_w, bases,
                    n_valids):
        """Fold ``window`` chunks in one dispatch (scan inside the jit)."""
        run_s, run_i, *held = carry
        w, chunk = toks_w.shape[:2]
        self._count_merges(w // merge_group(self.k, chunk, w))
        return self._call(self._fused_window, params, q_emb, run_s, run_i,
                          toks_w, mask_w, jnp.asarray(bases, jnp.int32),
                          jnp.asarray(n_valids, jnp.int32), *held)

    def finalize(self, carry):
        run_s, run_i = np.asarray(carry[0]), np.asarray(carry[1])
        tel = self.telemetry
        if self.counted and tel is not None:
            tel.metrics.histogram(HELD_ASSIGNMENTS_METRIC).observe(
                int(carry[2]))
        # the (Q, k) arrays read as run and score dicts: a query's lists
        # are built only when a consumer reads them
        return RankedRun(run_i, self.run_ids), RankedScores(run_s,
                                                            self.run_ids)


class PallasStreamTopKStage(StreamTopKStage):
    """Retrieval mode, Pallas path: the chunk's local top-k runs in the
    ``topk_mips`` Mosaic kernel (VMEM-resident running candidates), then the
    chunk-carry merge folds it into the engine carry."""

    name = "topk_pallas"

    def __init__(self, encode_fn: Callable, *, k: int, query_ids: List[str],
                 doc_ids: List[str], score_dtype: str = "f32"):
        # window=1: every chunk must go through the Pallas kernel, not the
        # XLA scan fallback.
        super().__init__(encode_fn, k=k, query_ids=query_ids, doc_ids=doc_ids,
                         window=1, score_dtype=score_dtype)
        self._encode = jax.jit(_in_scope("encode", encode_fn))

    def step(self, params, q_emb, carry, toks, mask, base, n_valid):
        from repro.kernels.topk_mips import ops as mips_ops
        emb = self._call(self._encode, params, toks, mask)    # device-resident
        run_s, run_i = carry
        return mips_ops.topk_mips_chunk(q_emb, emb, run_s, run_i, base=base,
                                        n_valid=n_valid,
                                        score_dtype=self.score_dtype)


class ShardedStreamTopKStage(StreamTopKStage):
    """Retrieval mode on the validator mesh: each chunk's rows are sharded
    over ``axis_names``; every shard encodes and local-top-ks its rows, a
    hierarchical all-gather merge (innermost axis first — same wire math as
    ``retrieval.topk_sharded``) re-replicates the chunk candidates, and the
    carry merge happens replicated.  The whole streaming step runs under one
    ``shard_map``."""

    name = "topk_sharded"

    def __init__(self, encode_fn: Callable, mesh, *, k: int,
                 query_ids: List[str], doc_ids: List[str],
                 axis_names=None, score_dtype: str = "f32"):
        # window=1: the scan-window fast path is single-device XLA; every
        # sharded chunk must go through the shard_map step below.
        super().__init__(encode_fn, k=k, query_ids=query_ids,
                         doc_ids=doc_ids, window=1, score_dtype=score_dtype)
        axis_names = tuple(axis_names or mesh.axis_names)
        k_carry = self.k
        ax = axis_names[0] if len(axis_names) == 1 else axis_names

        def local(params, q_emb, run_s, run_i, toks, mask, base, n_valid):
            with jax.named_scope("encode"):
                emb = encode_fn(params, toks, mask)           # (rows, D) local
            rows = toks.shape[0]
            shard = jax.lax.axis_index(ax)
            col = shard * rows + jnp.arange(rows, dtype=jnp.int32)
            with jax.named_scope("score"):
                # per-ROW quantization is sharding-independent, so each
                # shard's local quantized scores equal the single-device
                # stage's slice
                if score_dtype == "f32":
                    s = (q_emb @ emb.T).astype(jnp.float32)   # (Q, rows)
                else:
                    s = chunk_scores(q_emb, emb, score_dtype)  # (Q, rows)
                s = jnp.where((col < n_valid)[None, :], s, -jnp.inf)
            with jax.named_scope("merge"):
                kk = min(k_carry, rows)
                bs, pos = jax.lax.top_k(s, kk)
                bi = shard * rows + pos + base                # global doc rows
                bs, bi = _hierarchical_topk_merge(bs, bi, axis_names,
                                                  k_carry)
                return _merge_topk(run_s, run_i, bs, bi, k_carry)

        spec_rows = P(ax)
        # check_vma=False: the carry is replicated-in, device-varying
        # mid-step, re-replicated by the final merge — same legal pattern
        # topk_sharded documents.
        self._fused = jax.jit(jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(), P(), P(), P(), spec_rows, spec_rows, P(), P()),
            out_specs=(P(), P()), check_vma=False))
        # layout staged token chunks must be device_put with so the step's
        # in_specs find them already resident (no re-layout at dispatch)
        from repro.distributed.sharding import rows_sharding
        self.input_sharding = rows_sharding(mesh, axis_names)

    def step(self, params, q_emb, carry, toks, mask, base, n_valid):
        run_s, run_i = carry
        return self._call(self._fused, params, q_emb, run_s, run_i, toks,
                          mask, jnp.asarray(base, jnp.int32),
                          jnp.asarray(n_valid, jnp.int32))


class StreamRerankStage(Stage):
    """Rerank / average-rank modes: the carry is the padded per-query
    candidate score matrix (Q, Cmax); each chunk's scores are gathered into
    it where the candidates' global rows fall inside the chunk.

    With a ``store`` the stage precomputes a :class:`CandidateMap` — the
    per-chunk ``(chunk,)`` candidate-row masks plus the replicated
    ``(Q, Cmax)`` slot map — so (a) the engine skips chunks with zero
    candidate rows (``wants_chunk``) and (b) the fused step only ever scores
    rows that appear in some query's candidate set (non-members are masked
    to ``-inf`` before the slot gather; members are untouched, so the carry
    is bit-for-bit what the unmasked step produced).  Finalization routes
    through the shared :func:`repro.core.retrieval.rank_candidates`, the
    same stable-tie-break selection the materialized ``rerank_run`` uses —
    that sharing is what makes cross-mode runs identical, not just close.
    """

    name = "rerank"

    def __init__(self, encode_fn: Callable, *, k: int, query_ids: List[str],
                 doc_ids: List[str], per_query: Dict[str, List[str]],
                 store: Optional[TokenStore] = None,
                 score_dtype: str = "f32", compact: bool = False):
        self.query_ids = query_ids
        self.k = k
        self.score_dtype = validate_score_dtype(score_dtype)
        cand_idx, self.cands = pad_candidates(query_ids, doc_ids, per_query)
        self.cmap = store.candidate_map(cand_idx) \
            if store is not None and store.n_chunks else None
        # gather compaction: at very sparse candidate depths most rows of a
        # surviving chunk are non-candidates that get encoded and masked to
        # -inf anyway.  Packing the candidate rows into dense pseudo-chunks
        # (and remapping the slot map onto them) makes every encoded row a
        # candidate — bit-for-bit identical scores for any row-independent
        # encoder, since the same token rows land in the same slots.  The
        # engine streams self.store_override instead of the original store.
        self.store_override: Optional[TokenStore] = None
        if compact and self.cmap is not None:
            packed = self._pack_candidates(store, cand_idx)
            if packed is not None:
                cand_idx, self.store_override = packed
                self.cmap = self.store_override.candidate_map(cand_idx)
        self.cand_idx = jnp.asarray(cand_idx)
        self._row_masks: Dict[int, jnp.ndarray] = {}

        def fused(params, q_emb, cand_s, cand_idx, toks, mask, row_mask,
                  base, n_valid):
            with jax.named_scope("encode"):
                emb = encode_fn(params, toks, mask)           # (chunk, D)
            chunk = toks.shape[0]
            with jax.named_scope("score"):
                if score_dtype == "f32":
                    s = (q_emb @ emb.T).astype(jnp.float32)   # (Q, chunk)
                else:
                    s = chunk_scores(q_emb, emb, score_dtype)  # (Q, chunk)
                # score only candidate-member rows (membership precomputed
                # per chunk on the TokenStore side); hit slots always
                # reference member rows, so the gather below sees unmasked
                # scores.
                s = jnp.where(row_mask[None, :], s, -jnp.inf)
            with jax.named_scope("merge"):
                local = cand_idx - base
                hit = (cand_idx >= 0) & (local >= 0) & (local < n_valid)
                g = jnp.take_along_axis(s, jnp.clip(local, 0, chunk - 1),
                                        axis=1)
                return jnp.where(hit, g, cand_s)

        self._fused = jax.jit(fused, donate_argnums=_donate(2,))

    @staticmethod
    def _pack_candidates(store: TokenStore, cand_idx: np.ndarray):
        """Pack candidate token rows into dense pseudo-chunks.

        Returns ``(remapped_cand_idx, compact_store)``, or ``None`` when the
        candidate set is not sparse enough to pay for itself (the compacted
        store must need at most HALF the chunks the chunk-skipping schedule
        would already encode).  Host cost is one gather of
        O(candidate_rows x L) tokens, amortized across every checkpoint the
        stage validates — the same once-per-lifetime deal as the
        CandidateMap itself.
        """
        rows = np.unique(cand_idx[cand_idx >= 0])
        rows = rows[rows < store.n_texts]
        if not rows.size or not store.n_chunks:
            return None
        row_mask = np.zeros((store.n_chunks, store.chunk), bool)
        row_mask[rows // store.chunk, rows % store.chunk] = True
        surviving = int((row_mask.any(axis=1)).sum())
        n_compact = -(-int(rows.size) // store.chunk)
        if n_compact * 2 > surviving:
            return None
        L = store.tokens.shape[2]
        flat_t = store.tokens.reshape(store.n_chunks * store.chunk, L)
        flat_m = store.mask.reshape(store.n_chunks * store.chunk, L)
        toks = np.zeros((n_compact, store.chunk, L), np.int32)
        mask = np.zeros((n_compact, store.chunk, L), bool)
        toks.reshape(-1, L)[:rows.size] = flat_t[rows]   # memmap-safe copy
        mask.reshape(-1, L)[:rows.size] = flat_m[rows]
        compact = TokenStore(tokens=toks, mask=mask, chunk=store.chunk,
                             n_texts=int(rows.size))
        remapped = np.where(
            cand_idx >= 0,
            np.searchsorted(rows, np.clip(cand_idx, 0, None))
            .astype(np.int32),
            np.int32(-1))
        return np.asarray(remapped, np.int32), compact

    def wants_chunk(self, ci: int) -> bool:
        """False for chunks holding no candidate rows — the engine neither
        stages nor encodes them (a skipped chunk cannot write any slot, so
        skipping preserves bit-for-bit parity)."""
        return self.cmap is None or self.cmap.has_candidates(ci)

    def _row_mask(self, ci: int, chunk: int) -> jnp.ndarray:
        """Device-cached (chunk,) membership mask for chunk ``ci`` (all-True
        when the stage was built without a store)."""
        key = ci if self.cmap is not None else -1
        m = self._row_masks.get(key)
        if m is None:
            host = self.cmap.row_mask[ci] if self.cmap is not None \
                else np.ones((chunk,), bool)
            m = self._place_mask(host)
            self._row_masks[key] = m
        return m

    def _place_mask(self, host: np.ndarray) -> jnp.ndarray:
        return jnp.asarray(host)

    def init(self, q_emb):
        Q = q_emb.shape[0]
        return jnp.full((Q, self.cand_idx.shape[1]), -jnp.inf, jnp.float32)

    def step(self, params, q_emb, carry, toks, mask, base, n_valid):
        ci = base // (self.cmap.chunk if self.cmap is not None
                      else max(toks.shape[0], 1))
        return self._call(self._fused, params, q_emb, carry, self.cand_idx,
                          toks, mask, self._row_mask(ci, toks.shape[0]),
                          jnp.asarray(base, jnp.int32),
                          jnp.asarray(n_valid, jnp.int32))

    def finalize(self, carry):
        return rank_candidates(self.query_ids, np.asarray(carry), self.cands,
                               k=self.k)


class ShardedStreamRerankStage(StreamRerankStage):
    """Rerank / average-rank modes on the validator mesh — rerank as a
    first-class mesh citizen, mirroring :class:`ShardedStreamTopKStage`.

    Each chunk's rows are sharded over ``axis_names`` (the engine stages
    them pre-sharded via ``input_sharding``, like the retrieval stage);
    every shard encodes its rows under the one compiled ``shard_map`` step,
    scores only its candidate-member rows, and gathers them into its local
    view of the replicated ``(Q, Cmax)`` slot carry.  Because every slot
    names one global corpus row — which lives on exactly one shard of one
    chunk — the cross-shard fold is the slot-aligned degenerate case of the
    retrieval stage's hierarchical all-gather merge: an elementwise max per
    mesh axis, innermost first (:func:`~repro.core.retrieval.
    _hierarchical_slot_max`), which re-replicates the carry.  The slot map
    and query matrix stay replicated; collective volume per chunk is
    O(axes x Q x Cmax), independent of corpus size.  Carry, finalize, and
    chunk-skipping are inherited — so sharded runs are bit-for-bit the
    single-device runs (tests/test_rerank_parity.py).
    """

    name = "rerank_sharded"

    def __init__(self, encode_fn: Callable, mesh, *, k: int,
                 query_ids: List[str], doc_ids: List[str],
                 per_query: Dict[str, List[str]],
                 store: Optional[TokenStore] = None, axis_names=None,
                 score_dtype: str = "f32", compact: bool = False):
        super().__init__(encode_fn, k=k, query_ids=query_ids,
                         doc_ids=doc_ids, per_query=per_query, store=store,
                         score_dtype=score_dtype, compact=compact)
        axis_names = tuple(axis_names or mesh.axis_names)
        ax = axis_names[0] if len(axis_names) == 1 else axis_names

        def local(params, q_emb, cand_s, cand_idx, toks, mask, row_mask,
                  base, n_valid):
            with jax.named_scope("encode"):
                emb = encode_fn(params, toks, mask)       # (rows, D) local
            rows = toks.shape[0]
            shard = jax.lax.axis_index(ax)
            col = shard * rows + jnp.arange(rows, dtype=jnp.int32)
            with jax.named_scope("score"):
                # per-row quantization: shard-local quantized scores equal
                # the single-device stage's slice (see ShardedStreamTopKStage)
                if score_dtype == "f32":
                    s = (q_emb @ emb.T).astype(jnp.float32)   # (Q, rows)
                else:
                    s = chunk_scores(q_emb, emb, score_dtype)  # (Q, rows)
                s = jnp.where((row_mask & (col < n_valid))[None, :], s,
                              -jnp.inf)
            with jax.named_scope("merge"):
                pos = cand_idx - base - shard * rows      # shard-local slot
                hit = (cand_idx >= 0) & (cand_idx - base < n_valid) \
                    & (pos >= 0) & (pos < rows)
                g = jnp.take_along_axis(s, jnp.clip(pos, 0, rows - 1),
                                        axis=1)
                part = jnp.where(hit, g, cand_s)
                # slot-aligned hierarchical merge: each slot's row lives on
                # one shard, so max(part over shards) == the written score
                # where a shard hit and the (replicated) carry elsewhere.
                return _hierarchical_slot_max(part, axis_names)

        spec_rows = P(ax)
        # check_vma=False: the carry enters replicated, is device-varying
        # after the per-shard slot writes, and is re-replicated by the final
        # merge — the same legal pattern ShardedStreamTopKStage documents.
        self._fused = jax.jit(jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(), P(), P(), P(), spec_rows, spec_rows, spec_rows,
                      P(), P()),
            out_specs=P(), check_vma=False), donate_argnums=_donate(2,))
        from repro.distributed.sharding import replicated_sharding, \
            rows_sharding
        # staged token chunks (and the per-chunk row masks) land pre-sharded;
        # the slot map is placed replicated once so dispatch does no
        # re-layout on any step.
        self.input_sharding = rows_sharding(mesh, axis_names)
        self.cand_idx = jax.device_put(self.cand_idx,
                                       replicated_sharding(mesh))

    def _place_mask(self, host: np.ndarray) -> jnp.ndarray:
        return jax.device_put(host, self.input_sharding)


# ---------------------------------------------------------------------------
# Registry wiring: modes route to impls route to stage names; stage names
# resolve to normalized factories.  Third-party stages plug in with
# @register_stage("name") plus a @register_mode / @register_impl route that
# returns that name — no edits to make_stage required.
# ---------------------------------------------------------------------------


@register_impl("xla")
def _route_impl_xla(*, mesh=None) -> str:
    return "topk_sharded" if mesh is not None else "topk_xla"


@register_impl("pallas")
def _route_impl_pallas(*, mesh=None) -> str:
    # the Pallas chunk-carry kernel is single-device; a mesh does not
    # override it (mesh users pick impl="xla", the shard_map path)
    return "topk_pallas"


@register_mode("retrieval")
def _route_mode_retrieval(*, impl: str, mesh=None, per_query=None) -> str:
    return IMPLS.get(impl)(mesh=mesh)


@register_mode("rerank")
@register_mode("average_rank")
def _route_mode_rerank(*, impl: str, mesh=None, per_query=None) -> str:
    if not per_query:           # no candidate lists -> plain retrieval path
        return IMPLS.get(impl)(mesh=mesh)
    return "rerank_sharded" if mesh is not None else "rerank"


@register_stage("topk_xla")
def _stage_topk_xla(encode_fn, *, k, query_ids, doc_ids, scan_window=8,
                    mesh=None, per_query=None, store=None,
                    score_dtype="f32", rerank_compact=False) -> Stage:
    return StreamTopKStage(encode_fn, k=k, query_ids=query_ids,
                           doc_ids=doc_ids, window=scan_window,
                           score_dtype=score_dtype)


@register_stage("topk_pallas")
def _stage_topk_pallas(encode_fn, *, k, query_ids, doc_ids, scan_window=8,
                       mesh=None, per_query=None, store=None,
                       score_dtype="f32", rerank_compact=False) -> Stage:
    return PallasStreamTopKStage(encode_fn, k=k, query_ids=query_ids,
                                 doc_ids=doc_ids, score_dtype=score_dtype)


@register_stage("topk_sharded")
def _stage_topk_sharded(encode_fn, *, k, query_ids, doc_ids, scan_window=8,
                        mesh=None, per_query=None, store=None,
                        score_dtype="f32", rerank_compact=False) -> Stage:
    return ShardedStreamTopKStage(encode_fn, mesh, k=k, query_ids=query_ids,
                                  doc_ids=doc_ids, score_dtype=score_dtype)


@register_stage("rerank")
def _stage_rerank(encode_fn, *, k, query_ids, doc_ids, scan_window=8,
                  mesh=None, per_query=None, store=None,
                  score_dtype="f32", rerank_compact=True) -> Stage:
    return StreamRerankStage(encode_fn, k=max(k, 1000), query_ids=query_ids,
                             doc_ids=doc_ids, per_query=per_query,
                             store=store, score_dtype=score_dtype,
                             compact=rerank_compact)


@register_stage("rerank_sharded")
def _stage_rerank_sharded(encode_fn, *, k, query_ids, doc_ids, scan_window=8,
                          mesh=None, per_query=None, store=None,
                          score_dtype="f32", rerank_compact=True) -> Stage:
    return ShardedStreamRerankStage(encode_fn, mesh, k=max(k, 1000),
                                    query_ids=query_ids, doc_ids=doc_ids,
                                    per_query=per_query, store=store,
                                    score_dtype=score_dtype,
                                    compact=rerank_compact)


def make_stage(encode_fn: Callable, *, mode: str, impl: str, k: int,
               query_ids: List[str], doc_ids: List[str],
               per_query: Optional[Dict[str, List[str]]] = None,
               mesh=None, scan_window: int = 8,
               store: Optional[TokenStore] = None,
               score_dtype: str = "f32",
               rerank_compact: bool = True) -> Stage:
    """Route (mode, impl, mesh) to a Stage — the single dispatch point every
    validation path goes through, now resolved through the component
    registries: the ``mode`` route picks a stage name (consulting the
    ``impl`` route for the retrieval family), and the name resolves to a
    registered stage factory.  ``(mode="rerank", mesh=...)`` just works:
    rerank shards over the validator mesh exactly like retrieval does.
    ``store`` (the corpus TokenStore) lets the rerank stages precompute
    per-chunk candidate membership for chunk skipping (and, with
    ``rerank_compact``, pack sparse candidate rows into dense
    pseudo-chunks).  ``score_dtype`` picks the scoring precision
    (f32/bf16/int8) every stage family threads through
    :mod:`repro.core.precision`.  Unknown mode/impl/stage names raise
    listing the registered alternatives."""
    name = MODES.get(mode)(impl=impl, mesh=mesh, per_query=per_query)
    return STAGES.get(name)(encode_fn, k=k, query_ids=query_ids,
                            doc_ids=doc_ids, per_query=per_query, mesh=mesh,
                            scan_window=scan_window, store=store,
                            score_dtype=score_dtype,
                            rerank_compact=rerank_compact)


# ---------------------------------------------------------------------------
# Engines
# ---------------------------------------------------------------------------


class StreamingEngine:
    """Drive a Stage over a TokenStore: the full validation data path with
    peak embedding memory O(chunk x D + Q x k) — and, with an mmap-backed
    store, peak host token memory O(staging_depth x window x chunk x L).

    ``staging_depth`` is the prefetch depth of :func:`staged_batches`:
    2 (default) is the classic double buffer; deeper pipelines (3, 4, ...)
    keep that many batches' ``device_put`` in flight, which hides the
    longer/burstier latencies of remote-storage TokenStores (S3/GCS-backed
    mmap) at a host-memory cost of O(depth x window x chunk x L).  Stages
    exposing ``wants_chunk`` (the rerank stages, via their candidate maps)
    prune the schedule BEFORE staging, so skipped chunks are never read off
    the store backing at all.
    """

    name = "streaming"

    def __init__(self, spec, doc_store: TokenStore, query_store: TokenStore,
                 stage: Stage, *, staging: str = "double_buffered",
                 staging_depth: int = 2, query_mesh=None,
                 query_axis_names=None, telemetry=None):
        if staging not in ("double_buffered", "sync"):
            raise ValueError(f"unknown staging {staging!r} "
                             "(expected 'double_buffered' or 'sync')")
        if staging_depth < 1:
            raise ValueError(f"staging_depth must be >= 1, got "
                             f"{staging_depth!r}")
        self.spec = spec
        self.doc_store = doc_store
        self.query_store = query_store
        self.stage = stage
        self.staging = staging
        self.staging_depth = staging_depth
        self.query_mesh = query_mesh
        self.query_axis_names = query_axis_names
        # nullable repro.obs.Telemetry: a span at each boundary of a run
        # (placed, encoded, staged and dispatch per batch, drain, finalize)
        # and the staging-wait histogram.  Observation only — the schedule,
        # staging, and scoring math are identical with or without it.
        self.telemetry = telemetry

    @property
    def score_dtype(self) -> str:
        """Scoring precision of the wired stage — surfaced so the suite can
        ledger it alongside the engine name."""
        return getattr(self.stage, "score_dtype", "f32")

    def run(self, params) -> Tuple[Run, Scores, Dict[str, float]]:
        tel = self.telemetry
        # place the checkpoint once: host arrays (a restored checkpoint)
        # handed to every jitted dispatch are copied to the device per
        # dispatch, and queued dispatches keep all those copies alive
        with _span(tel, "placed"):
            params = place_params(params, self.query_mesh)
        t0 = time.time()
        with _span(tel, "encoded", role="query"):
            q_emb = encode_store(self.spec.encode_query, params,
                                 self.query_store, mesh=self.query_mesh,
                                 axis_names=self.query_axis_names)
            q_emb.block_until_ready()
        t_query = time.time() - t0

        t0 = time.time()
        # a compacting rerank stage re-packed the candidate rows into its
        # own dense pseudo-chunk store; stream that instead of the corpus
        store = getattr(self.stage, "store_override", None) or self.doc_store
        carry = self.stage.init(q_emb)
        window = getattr(self.stage, "window", 1)
        use_window = window > 1 and hasattr(self.stage, "step_window")
        schedule = plan_schedule(store.n_chunks, window if use_window else 1)
        # candidate-aware pruning: a rerank stage knows (from its
        # CandidateMap) which chunks hold candidate rows; the rest are
        # dropped from the schedule before staging ever reads them.
        wants = getattr(self.stage, "wants_chunk", None)
        if wants is not None:
            schedule = [(ci, w) for ci, w in schedule
                        if w > 1 or wants(ci)]
        # prefetch pipeline: batch i+depth-1's device_put is already in
        # flight when batch i's fused step dispatches (depth=2 is the double
        # buffer; sync staging forces depth=1 — copy, then compute — kept
        # for A/B benchmarking).
        batches = staged_batches(
            store, schedule,
            depth=1 if self.staging == "sync" else self.staging_depth,
            sharding=getattr(self.stage, "input_sharding", None))
        self.stage.telemetry = tel
        # explicit next() instead of zip() so telemetry can span the
        # staging wait of each batch apart from its dispatch; the iteration
        # order and count are identical to a zip(schedule, batches) loop.
        t_wait = 0.0
        for ci, w in schedule:
            if tel is None:
                toks, mask = next(batches)
                carry = self._step(params, q_emb, carry, toks, mask, store,
                                   ci, w)
            else:
                with tel.span("staged") as staged:
                    toks, mask = next(batches)
                t_wait += staged.dur
                with tel.span("dispatch"):
                    carry = self._step(params, q_emb, carry, toks, mask,
                                       store, ci, w)
        with _span(tel, "drain"):
            jax.block_until_ready(carry)
        t_stream = time.time() - t0
        if tel is not None:
            tel.metrics.histogram("engine.staging_wait_s").observe(t_wait)

        t0 = time.time()
        with _span(tel, "finalize"):
            with _span(tel, "finalize.fetch"):
                carry = jax.device_get(carry)
            with _span(tel, "finalize.build"):
                run, scores = self.stage.finalize(carry)
        t_final = time.time() - t0
        # key names kept from the legacy path: the ledger/CSV schema is
        # stable across engines.  encode_corpus_s is the fused loop (encode
        # AND fold — they are one program now); retrieve_s is the host-side
        # finalize only: the carry's copy to the host and the stage's
        # finalize (run views for the top-k stages, lists for rerank).
        timings = {"encode_corpus_s": t_stream, "encode_query_s": t_query,
                   "retrieve_s": t_final,
                   "total_s": t_query + t_stream + t_final}
        return run, scores, timings

    def _step(self, params, q_emb, carry, toks, mask, store: TokenStore,
              ci: int, w: int):
        """Dispatch the fused step over ``w`` chunks from chunk ``ci``."""
        if w > 1:
            bases = store.chunk * np.arange(ci, ci + w, dtype=np.int32)
            n_valids = np.asarray([store.rows_valid(j) for j in
                                   range(ci, ci + w)], np.int32)
            return self.stage.step_window(params, q_emb, carry, toks, mask,
                                          bases, n_valids)
        return self.stage.step(params, q_emb, carry, toks, mask,
                               store.chunk * ci, store.rows_valid(ci))


class MaterializedEngine:
    """The legacy path — encode everything, then retrieve — behind the same
    engine interface.  Kept for A/B benchmarks and as the fallback for
    encoders that cannot stream (none known)."""

    name = "materialized"

    def __init__(self, spec, doc_texts: List[Tokens], query_texts: List[Tokens],
                 *, mode: str, k: int, impl: str, batch_size: int,
                 query_ids: List[str], doc_ids: List[str],
                 per_query: Optional[Dict[str, List[str]]] = None, mesh=None,
                 rerank_block: Optional[int] = None,
                 score_dtype: str = "f32", telemetry=None):
        self.telemetry = telemetry
        self.spec = spec
        self.doc_texts = doc_texts
        self.query_texts = query_texts
        self.mode = mode
        self.k = k
        self.impl = impl
        self.batch_size = batch_size
        self.query_ids = query_ids
        self.doc_ids = doc_ids
        self.per_query = per_query
        self.mesh = mesh
        # queries per rerank candidate-gather block (None = auto from the
        # rerank_run memory budget); see rerank_run's docstring.
        self.rerank_block = rerank_block
        self.score_dtype = validate_score_dtype(score_dtype)

    def run(self, params) -> Tuple[Run, Scores, Dict[str, float]]:
        tel = self.telemetry
        t0 = time.time()
        with _span(tel, "encoded", role="corpus"):
            c_emb, _ = encode_texts(self.spec.encode_passage, params,
                                    self.doc_texts,
                                    max_len=self.spec.p_max_len,
                                    batch_size=self.batch_size)
            if self.score_dtype == "bf16":
                # the resident (N, D) matrix — THE memory cost this engine
                # pays that streaming doesn't — shrinks 2x; scoring casts
                # back per block with f32 accumulation.  int8 keeps the f32
                # matrix and quantizes at score time (value-level parity
                # with streaming beats resident shrink for the A/B baseline
                # engine).
                c_emb = np.asarray(jnp.asarray(c_emb, jnp.bfloat16))
        t_corpus = time.time() - t0
        t0 = time.time()
        with _span(tel, "encoded", role="query"):
            q_emb, _ = encode_texts(self.spec.encode_query, params,
                                    self.query_texts,
                                    max_len=self.spec.q_max_len,
                                    batch_size=self.batch_size)
        t_query = time.time() - t0

        t0 = time.time()
        if self.mode in ("rerank", "average_rank") and self.per_query:
            run, scores = rerank_run(self.query_ids, q_emb, self.doc_ids,
                                     c_emb, self.per_query,
                                     k=max(self.k, 1000),
                                     q_block=self.rerank_block,
                                     score_dtype=self.score_dtype)
        else:
            run, scores = retrieve_run(self.query_ids, q_emb, self.doc_ids,
                                       c_emb, k=self.k, impl=self.impl,
                                       mesh=self.mesh,
                                       score_dtype=self.score_dtype)
        t_retrieve = time.time() - t0
        timings = {"encode_corpus_s": t_corpus, "encode_query_s": t_query,
                   "retrieve_s": t_retrieve,
                   "total_s": t_corpus + t_query + t_retrieve}
        return run, scores, timings


@dataclasses.dataclass
class ValidationStore:
    """The sampled data one validation task runs over — the single "store"
    argument of :func:`make_engine`.

    Built by :class:`repro.core.suite.ValidationSuite` (one per task, after
    the task's sampler ran) or by any caller that already knows its subset.
    ``doc_store``/``query_store`` are optional pre-built
    :class:`TokenStore`\\ s: the suite fills ``doc_store`` from its shared
    cache so tasks over the same sampled corpus pad it exactly once; when
    absent, the engine factory builds them from the texts.
    """

    query_ids: List[str]
    query_texts: List[Tokens]
    doc_ids: List[str]
    doc_texts: List[Tokens]
    per_query: Optional[Dict[str, List[str]]] = None
    doc_store: Optional[TokenStore] = None
    query_store: Optional[TokenStore] = None


def chunk_geometry(vcfg, n_docs: int, mesh=None) -> Tuple[int, int]:
    """(corpus chunk rows, query chunk rows) for a config.  ``chunk_size``
    defaults to ``batch_size`` (legacy-equivalent encode granularity).  A
    chunk keeps its configured width whatever the corpus size: rows past
    the corpus are masked by ``n_valid``, so every consumer of one config
    (validator, serving index, ``topk_exact`` at ``block=chunk``) scores
    the same ``(Q, chunk)`` blocks.  With a mesh both widths are per
    shard: a dispatch holds ``chunk`` rows on each shard, so a shard scores
    exactly the block a single device does — for EVERY mode: retrieval,
    rerank, and average_rank all shard through the same ``make_stage``
    dispatch.  Shared by the engine factories, the serving index and the
    suite's TokenStore cache (two tasks share a store only when this
    geometry matches).  ``n_docs`` no longer shapes it."""
    del n_docs
    chunk = max(1, vcfg.chunk_size or vcfg.batch_size)
    q_chunk = max(1, vcfg.batch_size)
    if mesh is not None:
        n_shards = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
        chunk *= n_shards
        q_chunk *= n_shards
    return chunk, q_chunk


def doc_cache_dir(mmap_dir: Optional[str], index: int = 0) -> Optional[str]:
    """Cache subdirectory for the ``index``-th distinct corpus TokenStore
    under ``mmap_dir``.  Index 0 keeps the historical ``corpus_tokens`` name
    (single-task runs and their existing caches); later stores (a multi-task
    suite over several corpora) get numbered siblings."""
    if not mmap_dir:
        return None
    name = "corpus_tokens" if index == 0 else f"corpus_tokens_{index}"
    return os.path.join(mmap_dir, name)


@register_engine("streaming")
def make_streaming_engine(spec, store: ValidationStore, vcfg):
    """The default fused encode→top-k data path (see module docstring)."""
    mesh = vcfg.mesh
    chunk, q_chunk = chunk_geometry(vcfg, len(store.doc_texts), mesh)
    tel = getattr(vcfg, "telemetry", None)
    doc_store = store.doc_store
    if doc_store is None:
        if vcfg.token_backing == "mmap" and not vcfg.mmap_dir:
            raise ValueError("token_backing='mmap' needs mmap_dir")
        with _span(tel, "store_build", n_docs=len(store.doc_texts),
                   backing=vcfg.token_backing):
            doc_store = TokenStore.build(
                store.doc_texts, max_len=spec.p_max_len, chunk=chunk,
                backing=vcfg.token_backing,
                cache_dir=doc_cache_dir(vcfg.mmap_dir),
                fingerprint=vcfg.token_fingerprint)
    query_store = store.query_store
    if query_store is None:
        query_store = TokenStore.build(store.query_texts,
                                       max_len=spec.q_max_len, chunk=q_chunk)
    score_dtype = getattr(vcfg, "score_dtype", "f32")
    counted = getattr(spec, "encode_passage_counted", None)
    if counted is not None and MODES.get(vcfg.mode)(
            impl=vcfg.impl, mesh=mesh,
            per_query=store.per_query) == "topk_xla":
        # the single-device retrieval stage carries an expert trunk's count
        # of routed work; the other stages encode without it
        stage = StreamTopKStage(spec.encode_passage, k=vcfg.k,
                                query_ids=store.query_ids,
                                doc_ids=store.doc_ids,
                                window=vcfg.scan_window,
                                score_dtype=score_dtype,
                                encode_counted=counted)
    else:
        stage = make_stage(spec.encode_passage, mode=vcfg.mode,
                           impl=vcfg.impl, k=vcfg.k,
                           query_ids=store.query_ids, doc_ids=store.doc_ids,
                           per_query=store.per_query, mesh=mesh,
                           scan_window=vcfg.scan_window, store=doc_store,
                           score_dtype=score_dtype,
                           rerank_compact=getattr(vcfg, "rerank_compact",
                                                  True))
    return StreamingEngine(spec, doc_store, query_store, stage,
                           staging=vcfg.staging,
                           staging_depth=vcfg.staging_depth, query_mesh=mesh,
                           telemetry=tel)


# declares that this factory consumes ValidationStore.doc_store when one is
# supplied: the ValidationSuite routes the corpus TokenStore through its
# shared cache for every factory carrying this attribute, so corpus-sharing
# tasks pad the store once.  Third-party engines opt in the same way.
make_streaming_engine.uses_token_stores = True


@register_engine("materialized")
def make_materialized_engine(spec, store: ValidationStore, vcfg):
    """The legacy encode-all-then-retrieve path, for A/B benchmarking."""
    return MaterializedEngine(spec, store.doc_texts, store.query_texts,
                              mode=vcfg.mode, k=vcfg.k, impl=vcfg.impl,
                              batch_size=vcfg.batch_size,
                              query_ids=store.query_ids,
                              doc_ids=store.doc_ids,
                              per_query=store.per_query, mesh=vcfg.mesh,
                              rerank_block=vcfg.rerank_block,
                              score_dtype=getattr(vcfg, "score_dtype",
                                                  "f32"),
                              telemetry=getattr(vcfg, "telemetry", None))


def make_engine(spec, store: ValidationStore, vcfg):
    """Build the engine a :class:`~repro.core.suite.ValidationConfig` asks
    for.  The whole config travels intact — engine factories read the fields
    they care about (``engine``, ``mode``, ``impl``, ``k``, staging/backing
    knobs, ``mesh``) instead of every call site exploding 15 kwargs.  The
    ``engine`` name resolves through the :data:`~repro.core.registry.
    ENGINES` registry, so third-party engines registered with
    ``@register_engine`` are constructed exactly like the built-ins;
    unknown engine/mode/impl names raise listing the registered
    alternatives."""
    MODES.get(vcfg.mode)            # fail fast, with alternatives, even for
    IMPLS.get(vcfg.impl)            # engines that defer stage construction
    return ENGINES.get(vcfg.engine)(spec, store, vcfg)
