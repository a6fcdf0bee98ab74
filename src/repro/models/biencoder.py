"""Dense-retriever bi-encoder — the paper's model (§3 Encoder protocol, in JAX).

Asyncval's torch protocol is:

    class Encoder(torch.nn.Module):
        def __init__(self, ckpt_path, async_args): ...
        def encode_passage(self, psg) -> Tensor
        def encode_query(self, qry) -> Tensor

The JAX-native equivalent is :class:`EncoderSpec` — a pair of pure functions
over a parameter pytree, plus a loader that restores the pytree from a
checkpoint path (see ``repro.ckpt``).  Any architecture in the registry can be
wrapped into an EncoderSpec (LM backbones mean-pool; recsys models use their
item/user towers), which is how the paper's technique stays arch-agnostic.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.models import nn
from repro.models import transformer as tfm


@dataclasses.dataclass
class EncoderSpec:
    """JAX-native Asyncval Encoder protocol.

    encode_query / encode_passage: (params, tokens (B,L) int32, mask (B,L) bool)
      -> (B, dim) float32 embeddings.
    """
    name: str
    dim: int
    encode_query: Callable[[Any, jnp.ndarray, jnp.ndarray], jnp.ndarray]
    encode_passage: Callable[[Any, jnp.ndarray, jnp.ndarray], jnp.ndarray]
    init: Callable[[Any], Any]                      # rng -> Param tree
    q_max_len: int = 32
    p_max_len: int = 128
    # encode_passage that also returns the int32 count of routed
    # (token, held expert) assignments; set for a trunk with expert layers,
    # and used by the single-device retrieval stage (topk_xla) alone
    encode_passage_counted: Optional[Callable] = None


def biencoder_spec(cfg: tfm.TransformerConfig, *, pooling: str = "cls",
                   q_max_len: int = 32, p_max_len: int = 128) -> EncoderSpec:
    """Shared-weight bi-encoder over a transformer trunk (Tevatron default)."""

    def enc(params, tokens, mask):
        return tfm.encode(params, cfg, tokens, mask, pooling)

    def enc_counted(params, tokens, mask):
        return tfm.encode(params, cfg, tokens, mask, pooling, count_held=True)

    return EncoderSpec(name=cfg.name, dim=cfg.d_model,
                       encode_query=enc, encode_passage=enc,
                       init=lambda rng: tfm.init(rng, cfg),
                       q_max_len=q_max_len, p_max_len=p_max_len,
                       encode_passage_counted=(enc_counted if cfg.is_moe
                                               else None))


def contrastive_loss(params, spec: EncoderSpec, batch, *, temperature: float = 1.0):
    """In-batch-negative softmax CE (Tevatron / DPR training objective).

    batch: {"q_tokens": (B, Lq), "q_mask": (B, Lq),
            "p_tokens": (B, n_psg, Lp), "p_mask": (B, n_psg, Lp)}
    p[i, 0] is the positive for query i; all other passages in the batch act
    as negatives (n_psg - 1 explicit hard negatives per query supported).
    """
    q = spec.encode_query(params, batch["q_tokens"], batch["q_mask"])      # (B, D)
    B, n_psg, Lp = batch["p_tokens"].shape
    p_tok = batch["p_tokens"].reshape(B * n_psg, Lp)
    p_msk = batch["p_mask"].reshape(B * n_psg, Lp)
    p = spec.encode_passage(params, p_tok, p_msk)                          # (B*n, D)
    scores = (q @ p.T) / temperature                                       # (B, B*n)
    labels = jnp.arange(B) * n_psg                                         # positives
    lse = jax.nn.logsumexp(scores, axis=-1)
    pos = jnp.take_along_axis(scores, labels[:, None], axis=1)[:, 0]
    loss = jnp.mean(lse - pos)
    acc = jnp.mean((jnp.argmax(scores, axis=-1) == labels).astype(jnp.float32))
    return loss, {"contrastive_acc": acc}


def loss_fn(params, cfg: tfm.TransformerConfig, batch):
    """Registry-compatible loss entry (family='biencoder')."""
    spec = biencoder_spec(cfg)
    return contrastive_loss(params, spec, batch)
