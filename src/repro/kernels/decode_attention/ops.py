"""jit'd dispatch wrapper for the decode_attention Pallas kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.kernel import decode_attention_kernel


def _pad_to(x: int, m: int) -> int:
    return -(-x // m) * m


def decode_attention(q, k, v, length, *, bk: int = 512,
                     interpret: bool | None = None):
    """q: (B, KV, G, d); k, v: (B, KV, T, d); length: int or (1,) i32.

    Returns (B, KV, G, d) in q.dtype."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    B, KV, G, d = q.shape
    T = k.shape[2]
    bk = min(bk, _pad_to(T, 128))
    Gp, dp, Tp = _pad_to(G, 8), _pad_to(d, 128), _pad_to(T, bk)
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, Gp - G), (0, dp - d)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, Tp - T), (0, dp - d)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, Tp - T), (0, dp - d)))
    if dp != d:
        qp = qp * (dp ** 0.5) / (d ** 0.5)
    length = jnp.asarray(length, jnp.int32).reshape((1,))
    out = decode_attention_kernel(length, qp, kp, vp, bk=bk,
                                  interpret=interpret)
    return out[:, :, :G, :d]
