"""Work that validation requires, counted from the configuration file.

Counts only what the algorithm needs: the encoder forward at each text's
real (unpadded) token count, and the query-passage scoring.  Padding is not
work, so a program that stops computing it raises the shares built on these
counts instead of making them stale.  Embedding lookups, LayerNorm, softmax
and GELU are left out: they are not matrix products and are a small part.
"""

from __future__ import annotations

import json
import os
from typing import Sequence

import numpy as np

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def encoder_flops(cfg: dict, lengths: Sequence[int]) -> float:
    """Forward FLOPs of the transformer trunk over texts of ``lengths``
    tokens: per layer, the Q/K/V and output projections, the two MLP
    products, and the two attention products over the real tokens."""
    t = cfg["transformer"]
    d, inner, ff = t["d_model"], t["n_heads"] * t["head_dim"], t["d_ff"]
    L = np.asarray(lengths, np.float64)
    per_token = 2 * d * 3 * inner + 2 * inner * d + 2 * 2 * d * ff
    attention = 2 * 2 * inner * L ** 2
    return float(t["n_layers"] * (per_token * L.sum() + attention.sum()))


def scoring_flops(n_queries: int, n_passages: int, dim: int) -> float:
    """Every query against every passage: one dot product of ``dim``."""
    return 2.0 * n_queries * n_passages * dim


def weight_bytes(cfg: dict) -> float:
    """Bytes of the trunk's weight matrices in their stored dtype: what one
    encode call has to read at least once."""
    t = cfg["transformer"]
    d, inner, ff = t["d_model"], t["n_heads"] * t["head_dim"], t["d_ff"]
    n = t["n_layers"] * (3 * d * inner + inner * d + 2 * d * ff)
    return float(n * np.dtype(cfg["param_dtype"]).itemsize)


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; a kind that is not
    in ``peaks.json`` is an error."""
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{_PEAKS}; known: {sorted(table)}")
    return table[device_kind]
