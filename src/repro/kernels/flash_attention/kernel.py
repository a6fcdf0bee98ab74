"""Pallas TPU kernel: fused (flash) attention with online softmax.

Used by the corpus encoder (bidirectional, the paper's hot encode path) and
the LM backbones (causal, GQA).  VMEM tiling:

  * q tile (bq, d) resident; k/v tiles (bk, d) stream;
  * online softmax: running row-max ``m``, normalizer ``l`` and the
    f32 accumulator ``acc`` live in VMEM scratch across kv tiles — the
    (S, T) score matrix never exists in HBM;
  * causal blocks strictly above the diagonal are skipped via ``pl.when``
    (compute skipped, DMA still scheduled — Mosaic hoists the cheap case);
  * GQA: the kv-head block index is ``h // group`` — no KV duplication.

Grid: (batch, heads, q_blocks, kv_blocks), kv innermost ("arbitrary").
``m``/``l`` are stored lane-replicated (bq, 128) — the standard Mosaic
layout trick for row statistics.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_LANES = 128
_NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  bq: int, bk: int, causal: bool, t_valid: int,
                  scale: float):
    qi, ki = pl.program_id(2), pl.program_id(3)
    n_k = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * bq
    k_start = ki * bk
    run = (not causal) or (k_start <= q_start + bq - 1)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < t_valid
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            mask = mask & (kpos <= qpos)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scr[:, :1]                                # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                               # (bq, bk)
        corr = jnp.exp(m_prev - m_new)                       # (bq, 1)
        l_new = corr * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)                  # (bk, d)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == n_k - 1)
    def _flush():
        l = l_scr[:, :1]
        safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0] = (acc_scr[...] / safe).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "t_valid", "bq", "bk", "interpret"))
def flash_attention_kernel(q, k, v, *, causal: bool, t_valid: int,
                           bq: int = 256, bk: int = 256,
                           interpret: bool = False):
    """q: (B, H, S, d); k, v: (B, KV, T, d); H % KV == 0.

    S % bq == 0, T % bk == 0, d % 128 == 0 (ops.py pads).  ``t_valid``
    masks key padding.  Returns (B, H, S, d) in q.dtype.
    """
    B, H, S, d = q.shape
    KV, T = k.shape[1], k.shape[2]
    assert H % KV == 0 and S % bq == 0 and T % bk == 0 and d % _LANES == 0
    group = H // KV
    grid = (B, H, S // bq, T // bk)
    scale = 1.0 / (d ** 0.5)

    kernel = functools.partial(_flash_kernel, bq=bq, bk=bk, causal=causal,
                               t_valid=t_valid, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
