"""Pallas TPU kernel: single-token GQA decode attention over a long KV cache.

The ``decode_32k`` / ``long_500k`` serving cells are dominated by streaming
the KV cache (arithmetic intensity ~= G, the GQA group size) — a pure
HBM-bandwidth workload.  The kernel:

  * grid = (batch, kv_heads, cache_blocks), cache innermost;
  * the G query rows of one kv head (a (G, d) tile, G = H // KV) stay
    resident; cache tiles (bk, d) stream through VMEM exactly once;
  * online softmax (running m / l / acc scratch) — no (H, T) score tensor;
  * the *dynamic* cache length arrives via scalar-memory (SMEM) so blocks
    past the valid prefix are skipped entirely (``pl.when``) — with a
    524k-token cache capacity and a 32k prefix, 94% of the sweep is DMA
    that never happens.

q rows per tile are padded to the 8-row sublane minimum in ops.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_LANES = 128
_NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, bk: int, scale: float):
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)
    length = len_ref[0]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    k_start = ki * bk

    @pl.when(k_start < length)                   # skip blocks past the prefix
    def _step():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (G, d)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < length, s, _NEG_INF)

        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = jnp.broadcast_to(
            corr * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True),
            l_scr.shape)
        v = v_ref[0, 0].astype(jnp.float32)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(ki == n_k - 1)
    def _flush():
        l = l_scr[:, :1]
        safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0] = (acc_scr[...] / safe).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def decode_attention_kernel(length, q, k, v, *, bk: int = 512,
                            interpret: bool = False):
    """length: (1,) i32 valid cache length; q: (B, KV, G, d);
    k, v: (B, KV, T, d); T % bk == 0, d % 128 == 0, G % 8 == 0.
    Returns (B, KV, G, d) in q.dtype."""
    B, KV, G, d = q.shape
    T = k.shape[2]
    assert T % bk == 0 and d % _LANES == 0 and G % 8 == 0
    grid = (B, KV, T // bk)
    scale = 1.0 / (d ** 0.5)

    kernel = functools.partial(_decode_kernel, bk=bk, scale=scale)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, G, d), lambda b, h, ki, _: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, bk, d), lambda b, h, ki, _: (b, h, ki, 0)),
                pl.BlockSpec((1, 1, bk, d), lambda b, h, ki, _: (b, h, ki, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, G, d),
                                   lambda b, h, ki, _: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, _LANES), jnp.float32),
                pltpu.VMEM((G, _LANES), jnp.float32),
                pltpu.VMEM((G, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(length, q, k, v)
