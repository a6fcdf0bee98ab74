"""Pallas TPU kernel: blocked exact MIPS with a running top-k in VMEM.

The paper's retrieval stage — score every corpus vector against every
validation query and keep the top-k — is a flat inner-product scan.  The
GPU/host baseline (FAISS ``IndexFlatIP``) streams the corpus through CPU
SIMD registers; the TPU-native rethink is:

  * corpus tiles (``bn x D``) stream HBM -> VMEM once; each tile hits the
    MXU against a resident query tile (``bq x D``) — a (bq, D) x (D, bn)
    matmul with f32 accumulation;
  * the per-query *running top-k* (scores + global indices) lives in VMEM
    scratch across the whole corpus sweep — candidates never round-trip to
    HBM per tile (the FAISS heap equivalent, kept on-chip);
  * the merge is the top-k of ``[running ‖ tile_scores]``, taken by ``k``
    rounds of max-extraction on the VPU (Mosaic lowers no sort);
  * grid = (q_tiles, corpus_tiles), corpus innermost ("arbitrary"
    semantics — the running top-k is carried across corpus steps; q tiles
    are embarrassingly parallel).

Dims: D and bn are multiples of 128 (MXU lane width); bq a multiple of 8
(sublane).  ``ops.topk_mips`` pads inputs and slices the result.

Precision (``ops.topk_mips(score_dtype=...)``):

  * ``f32``  — the kernel below, untouched;
  * ``bf16`` — the SAME kernel body with bf16 query/corpus tiles: the MXU
    eats bf16 natively and ``preferred_element_type=jnp.float32`` keeps the
    accumulator (and therefore the running top-k carry) in f32, so the
    ``-inf`` padding mask and the tournament merge are unchanged — only the
    HBM->VMEM tile traffic halves;
  * ``int8`` — :func:`topk_mips_kernel_int8`: int8 tiles hit the MXU with an
    exact int32 accumulator; per-row scale factors ride alongside the tiles
    into VMEM (a ``(bq, 1)`` query-scale column and a ``(1, bn)``
    corpus-tile scale row) and are folded into the scores BEFORE the
    ``-inf`` mask and the running-carry tournament merge, so the carry
    itself stays plain f32 — narrow dtypes never touch the merge.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_LOWEST = float(jnp.finfo(jnp.float32).min)
_NO_POS = 2 ** 30


def _merge_tile(run_s, run_i, scores, col, k: int):
    """Fold one tile into the running top-k: top-k of [carry ‖ tile].

    Mosaic lowers no sort or ``top_k``, so the merge is ``k`` rounds of
    max-extraction on the VPU.  Each round takes the row max, picks the
    lowest position holding it in the concatenated order (``lax.top_k``'s
    tie order), writes it to output slot ``j`` and retires that position by
    setting its key to ``-inf``.  Real ``-inf`` scores (padding, the empty
    carry) enter as the lowest finite float so a retired slot is never
    picked twice, and leave as ``-inf`` again.
    """
    bq, bn = scores.shape
    pos_r = jax.lax.broadcasted_iota(jnp.int32, (bq, k), 1)
    pos_t = jax.lax.broadcasted_iota(jnp.int32, (bq, bn), 1) + k
    cur_i = run_i[...]

    def finite(x):
        return jnp.where(x == -jnp.inf, _LOWEST, x)

    def body(j, carry):
        kr, kt, out_s, out_i = carry
        m = jnp.maximum(jnp.max(kr, axis=1, keepdims=True),
                        jnp.max(kt, axis=1, keepdims=True))
        p = jnp.minimum(
            jnp.min(jnp.where(kr == m, pos_r, _NO_POS), axis=1,
                    keepdims=True),
            jnp.min(jnp.where(kt == m, pos_t, _NO_POS), axis=1,
                    keepdims=True))
        idx = (jnp.sum(jnp.where(pos_r == p, cur_i, 0), axis=1,
                       keepdims=True)
               + jnp.sum(jnp.where(pos_t == p, col, 0), axis=1,
                         keepdims=True))
        here = pos_r == j
        out_s = jnp.where(here, jnp.where(m == _LOWEST, -jnp.inf, m), out_s)
        out_i = jnp.where(here, idx, out_i)
        return (jnp.where(pos_r == p, -jnp.inf, kr),
                jnp.where(pos_t == p, -jnp.inf, kt), out_s, out_i)

    _, _, top_s, top_i = jax.lax.fori_loop(
        0, k, body, (finite(run_s[...]), finite(scores),
                     jnp.zeros((bq, k), jnp.float32),
                     jnp.zeros((bq, k), jnp.int32)))
    run_s[...] = top_s
    run_i[...] = top_i


def _mips_kernel(q_ref, c_ref, out_s_ref, out_i_ref, run_s, run_i, *,
                 k: int, bn: int, n_total: int):
    """One (q_tile, c_tile) grid step.

    q_ref: (bq, D) VMEM; c_ref: (bn, D) VMEM;
    out_s_ref / out_i_ref: (bq, k) output tiles;
    run_s / run_i: (bq, k) VMEM scratch carried across corpus steps.
    """
    ci = pl.program_id(1)
    n_ctiles = pl.num_programs(1)

    @pl.when(ci == 0)
    def _init():
        run_s[...] = jnp.full_like(run_s, -jnp.inf)
        run_i[...] = jnp.zeros_like(run_i)

    # MXU: (bq, D) x (D, bn) -> (bq, bn), f32 accumulation
    scores = jax.lax.dot_general(
        q_ref[...], c_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    base = ci * bn
    col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1) + base
    valid = col < n_total                       # mask corpus padding rows
    scores = jnp.where(valid, scores, -jnp.inf)

    _merge_tile(run_s, run_i, scores, col, k)

    @pl.when(ci == n_ctiles - 1)
    def _flush():
        out_s_ref[...] = run_s[...]
        out_i_ref[...] = run_i[...]


def _mips_kernel_int8(q_ref, c_ref, qs_ref, cs_ref, out_s_ref, out_i_ref,
                      run_s, run_i, *, k: int, bn: int, n_total: int):
    """Quantized sibling of :func:`_mips_kernel`.

    q_ref: (bq, D) int8; c_ref: (bn, D) int8;
    qs_ref: (bq, 1) f32 per-query-row scales (resident across the sweep);
    cs_ref: (1, bn) f32 per-corpus-row scales, sliced per corpus tile;
    run_s / run_i: (bq, k) f32/i32 VMEM scratch — the carry stays f32, the
    scales are folded into the tile scores before the mask and the merge.
    """
    ci = pl.program_id(1)
    n_ctiles = pl.num_programs(1)

    @pl.when(ci == 0)
    def _init():
        run_s[...] = jnp.full_like(run_s, -jnp.inf)
        run_i[...] = jnp.zeros_like(run_i)

    # MXU: int8 x int8 -> exact int32 accumulation, then dequantize with the
    # per-row scales (outer product of the two scale vectors) into f32 —
    # BEFORE masking, so -inf padding survives the narrow input dtype.
    raw = jax.lax.dot_general(
        q_ref[...], c_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)
    scores = raw.astype(jnp.float32) * qs_ref[...] * cs_ref[...]

    base = ci * bn
    col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1) + base
    valid = col < n_total                       # mask corpus padding rows
    scores = jnp.where(valid, scores, -jnp.inf)

    _merge_tile(run_s, run_i, scores, col, k)

    @pl.when(ci == n_ctiles - 1)
    def _flush():
        out_s_ref[...] = run_s[...]
        out_i_ref[...] = run_i[...]


@functools.partial(jax.jit,
                   static_argnames=("k", "n_valid", "bq", "bn", "interpret"))
def topk_mips_kernel(q: jnp.ndarray, c: jnp.ndarray, *, k: int,
                     n_valid: int, bq: int = 128, bn: int = 1024,
                     interpret: bool = False):
    """q: (Q, D), c: (N, D) — Q % bq == 0, N % bn == 0, D % 128 == 0.

    ``n_valid`` <= N marks real (non-padding) corpus rows.  Returns
    (scores (Q, k) f32, indices (Q, k) i32).  ``k`` <= bn.
    """
    Q, D = q.shape
    N = c.shape[0]
    assert Q % bq == 0 and N % bn == 0 and k <= bn and D % 128 == 0
    grid = (Q // bq, N // bn)

    kernel = functools.partial(_mips_kernel, k=k, bn=bn, n_total=n_valid)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, D), lambda qi, ci: (qi, 0)),
            pl.BlockSpec((bn, D), lambda qi, ci: (ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bq, k), lambda qi, ci: (qi, 0)),
            pl.BlockSpec((bq, k), lambda qi, ci: (qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Q, k), jnp.float32),
            jax.ShapeDtypeStruct((Q, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, k), jnp.float32),
            pltpu.VMEM((bq, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, c)


@functools.partial(jax.jit,
                   static_argnames=("k", "n_valid", "bq", "bn", "interpret"))
def topk_mips_kernel_int8(q: jnp.ndarray, c: jnp.ndarray,
                          q_scale: jnp.ndarray, c_scale: jnp.ndarray, *,
                          k: int, n_valid: int, bq: int = 128,
                          bn: int = 1024, interpret: bool = False):
    """Quantized top-k MIPS: q (Q, D) int8, c (N, D) int8, q_scale (Q, 1)
    f32 per-query-row scales, c_scale (1, N) f32 per-corpus-row scales.

    Same grid/blocking contract as :func:`topk_mips_kernel` (Q % bq == 0,
    N % bn == 0, k <= bn, D % 128 == 0); the scale vectors are blocked
    alongside the tiles — ``c_scale`` arrives one ``(1, bn)`` slice per
    corpus tile — and folded into the scores before the f32 carry merge.
    Returns (scores (Q, k) f32, indices (Q, k) i32).
    """
    Q, D = q.shape
    N = c.shape[0]
    assert Q % bq == 0 and N % bn == 0 and k <= bn and D % 128 == 0
    assert q_scale.shape == (Q, 1) and c_scale.shape == (1, N)
    grid = (Q // bq, N // bn)

    kernel = functools.partial(_mips_kernel_int8, k=k, bn=bn, n_total=n_valid)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, D), lambda qi, ci: (qi, 0)),
            pl.BlockSpec((bn, D), lambda qi, ci: (ci, 0)),
            pl.BlockSpec((bq, 1), lambda qi, ci: (qi, 0)),
            pl.BlockSpec((1, bn), lambda qi, ci: (0, ci)),
        ],
        out_specs=[
            pl.BlockSpec((bq, k), lambda qi, ci: (qi, 0)),
            pl.BlockSpec((bq, k), lambda qi, ci: (qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Q, k), jnp.float32),
            jax.ShapeDtypeStruct((Q, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, k), jnp.float32),
            pltpu.VMEM((bq, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, c, q_scale, c_scale)
