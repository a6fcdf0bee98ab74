"""jit'd dispatch wrapper for the flash_attention Pallas kernel.

Pads (S, T) to block multiples and d to the 128-lane width, then slices.
On the CPU backend the kernel body runs in interpret mode; elsewhere it
compiles for the chip or raises.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_kernel


def _pad_to(x: int, m: int) -> int:
    return -(-x // m) * m


def flash_attention(q, k, v, *, causal: bool = False,
                    t_valid: int | None = None, bq: int = 256, bk: int = 256,
                    interpret: bool | None = None):
    """q: (B, H, S, d); k, v: (B, KV, T, d) -> (B, H, S, d)."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    B, H, S, d = q.shape
    T = k.shape[2]
    t_valid = T if t_valid is None else t_valid
    bq = min(bq, _pad_to(S, 8))
    bk = min(bk, _pad_to(T, 128))
    Sp, Tp, dp = _pad_to(S, bq), _pad_to(T, bk), _pad_to(d, 128)
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, Sp - S), (0, dp - d)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, Tp - T), (0, dp - d)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, Tp - T), (0, dp - d)))
    # the kernel scales by 1/sqrt(d_padded); rescale so it matches 1/sqrt(d)
    if dp != d:
        qp = qp * (dp ** 0.5) / (d ** 0.5)
    out = flash_attention_kernel(qp, kp, vp, causal=causal, t_valid=t_valid,
                                 bq=bq, bk=bk, interpret=interpret)
    return out[:, :, :S, :d]
