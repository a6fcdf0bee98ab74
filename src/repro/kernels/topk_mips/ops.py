"""jit'd dispatch wrapper for the topk_mips Pallas kernel.

Handles shape padding (queries to bq, corpus rows to bn, feature dim to the
128-lane MXU width) and backend selection: on the CPU backend
``interpret=True`` executes the kernel body in Python for correctness
validation; on any other backend the Mosaic kernel compiles natively or
raises.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.topk_mips.kernel import (topk_mips_kernel,
                                            topk_mips_kernel_int8)

SCORE_DTYPES = ("f32", "bf16", "int8")


def _pad_to(x: int, m: int) -> int:
    return -(-x // m) * m


def quantize_int8(x: jnp.ndarray):
    """Symmetric per-row int8 quantization: ``x`` (..., D) f32 ->
    (values (..., D) int8, scales (..., 1) f32) with ``values * scales ~ x``.

    Per-ROW granularity on purpose: a row's quantized image is independent
    of how the corpus is chunked or sharded, so the streaming, sharded, and
    materialized engines all score the exact same int8 corpus — quantized
    cross-engine parity stays tie-level, not tolerance-level.  All-zero rows
    get scale 1 (not 0), keeping the dequantized scores finite.
    """
    x = jnp.asarray(x, jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    vals = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return vals, scale


def topk_mips(q: jnp.ndarray, c: jnp.ndarray, *, k: int, bq: int = 128,
              bn: int = 1024, interpret: bool | None = None,
              n_valid: int | None = None, score_dtype: str = "f32"):
    """Exact top-k MIPS: q (Q, D) x c (N, D) -> (scores, indices) (Q, k).

    ``n_valid`` (static) marks how many leading corpus rows are real; trailing
    rows (fixed-shape chunk padding from the streaming engine) are masked out
    of the top-k.  Defaults to all rows.

    ``score_dtype`` picks the scoring precision: ``"f32"`` (default — the
    path below, bit-for-bit unchanged), ``"bf16"`` (inputs cast to bf16, f32
    MXU accumulation — half the tile bytes), or ``"int8"`` (symmetric
    per-row quantization, exact int32 accumulation, per-tile scales folded
    in before the f32 carry merge — a quarter of the tile bytes).
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    if score_dtype == "f32":
        return _topk_mips_jit(q, c, k=k, bq=bq, bn=bn, interpret=interpret,
                              n_valid=n_valid)
    if score_dtype == "bf16":
        return _topk_mips_jit(jnp.asarray(q, jnp.bfloat16),
                              jnp.asarray(c, jnp.bfloat16), k=k, bq=bq,
                              bn=bn, interpret=interpret, n_valid=n_valid)
    if score_dtype == "int8":
        return _topk_mips_int8_jit(q, c, k=k, bq=bq, bn=bn,
                                   interpret=interpret, n_valid=n_valid)
    raise ValueError(f"unknown score_dtype {score_dtype!r} "
                     f"(expected one of {SCORE_DTYPES})")


@functools.partial(jax.jit, static_argnames=("k", "bq", "bn", "interpret",
                                             "n_valid"))
def _topk_mips_jit(q, c, *, k, bq, bn, interpret, n_valid):
    # jitted end to end so the padding/slicing around the kernel compiles
    # into one program — the streaming engine calls this once per corpus
    # chunk, where eager per-call pads would dominate the hot loop.
    Q, D = q.shape
    N = c.shape[0]
    if n_valid is None:
        n_valid = N
    n_valid = min(n_valid, N)
    k_eff = min(k, n_valid)
    bq = min(bq, _pad_to(Q, 8))
    bn = min(bn, _pad_to(max(N, k_eff), 128))
    kp = k_eff                                     # k <= bn guaranteed below
    if kp > bn:
        bn = _pad_to(kp, 128)
    Dp = _pad_to(D, 128)
    Qp = _pad_to(Q, bq)
    Np = _pad_to(N, bn)
    qp = jnp.pad(q, ((0, Qp - Q), (0, Dp - D)))
    cp = jnp.pad(c, ((0, Np - N), (0, Dp - D)))
    # the streaming stage's chunk fold: scores and local top-k in one kernel
    with jax.named_scope("merge"):
        scores, idx = topk_mips_kernel(qp, cp, k=kp, n_valid=n_valid, bq=bq,
                                       bn=bn, interpret=interpret)
    return scores[:Q], idx[:Q]


@functools.partial(jax.jit, static_argnames=("k", "bq", "bn", "interpret",
                                             "n_valid"))
def _topk_mips_int8_jit(q, c, *, k, bq, bn, interpret, n_valid):
    # same padding contract as _topk_mips_jit; quantization happens BEFORE
    # feature-dim padding (zero columns would not change per-row |max| but
    # quantizing first keeps the scales equal to the engine-side ones, which
    # see unpadded (chunk, D) embeddings).  Value padding is 0 and scale
    # padding is 1, so padded rows score exactly 0 before the n_valid mask
    # turns them into -inf.
    Q, D = q.shape
    N = c.shape[0]
    if n_valid is None:
        n_valid = N
    n_valid = min(n_valid, N)
    k_eff = min(k, n_valid)
    bq = min(bq, _pad_to(Q, 8))
    bn = min(bn, _pad_to(max(N, k_eff), 128))
    kp = k_eff
    if kp > bn:
        bn = _pad_to(kp, 128)
    Dp = _pad_to(D, 128)
    Qp = _pad_to(Q, bq)
    Np = _pad_to(N, bn)
    qv, qs = quantize_int8(q)
    cv, cs = quantize_int8(c)
    qp = jnp.pad(qv, ((0, Qp - Q), (0, Dp - D)))
    cp = jnp.pad(cv, ((0, Np - N), (0, Dp - D)))
    qsp = jnp.pad(qs, ((0, Qp - Q), (0, 0)), constant_values=1.0)
    csp = jnp.pad(cs, ((0, Np - N), (0, 0)), constant_values=1.0)
    with jax.named_scope("merge"):
        scores, idx = topk_mips_kernel_int8(qp, cp, qsp, csp.reshape(1, Np),
                                            k=kp, n_valid=n_valid, bq=bq,
                                            bn=bn, interpret=interpret)
    return scores[:Q], idx[:Q]


@functools.partial(jax.jit, static_argnames=("k",))
def _merge_carry(run_s, run_i, chunk_s, chunk_i, base, *, k: int):
    """Fold a chunk-local top-k (indices relative to the chunk) into the
    running (Q, k) carry.  ``base`` is dynamic — one compile per chunk shape,
    not per chunk position."""
    # imported here: repro.core.precision imports this module
    from repro.core.retrieval import _merge_topk
    return _merge_topk(run_s, run_i, chunk_s, chunk_i + base, k)


def topk_mips_chunk(q: jnp.ndarray, c_chunk: jnp.ndarray, run_s: jnp.ndarray,
                    run_i: jnp.ndarray, *, base, n_valid: int | None = None,
                    bq: int = 128, bn: int = 1024,
                    interpret: bool | None = None,
                    score_dtype: str = "f32"):
    """Chunk-carry entry point for the streaming ValidationEngine.

    Computes the local top-k of one fixed-shape corpus chunk with the Pallas
    kernel and merges it into the running ``(Q, k)`` carry — the chunk's
    embeddings never leave the device and the full corpus scores are never
    materialized.  ``base`` (dynamic) is the chunk's global row offset;
    ``n_valid`` (static, at most two distinct values per corpus: full chunks
    and the ragged tail) masks chunk padding rows.
    """
    k = run_s.shape[1]
    n = c_chunk.shape[0] if n_valid is None else min(n_valid, c_chunk.shape[0])
    if n <= 0:
        return run_s, run_i
    s, i = topk_mips(q, c_chunk, k=min(k, n), bq=bq, bn=bn,
                     interpret=interpret, n_valid=n_valid,
                     score_dtype=score_dtype)
    return _merge_carry(run_s, run_i, s, i.astype(jnp.int32),
                        jnp.asarray(base, jnp.int32), k=k)
