"""LM-family transformer supporting the assigned architecture pool.

One flexible implementation covers:
  * dense llama-style (deepseek-67b)            — GQA, RoPE, SwiGLU, RMSNorm
  * qwen2 (0.5b / 72b)                          — GQA + QKV bias
  * arctic-480b                                 — dense FFN + *residual* 128-expert top-2 MoE
  * deepseek-v2-lite-16b                        — MLA (kv_lora=512) + 64-expert top-6 MoE,
                                                  2 shared experts, first layer dense
  * BERT-style encoder (paper's bi-encoder)     — post-LN, GELU, learned positions, bidir

Design notes
  * layers are stacked (leading L dim) and iterated with ``lax.scan`` so compile
    time is O(1) in depth; ``jax.checkpoint`` around the block gives remat.
  * attention is computed in query chunks (``lax.scan`` over q blocks) so the
    full (S, T) score matrix is never materialized — the XLA-level analogue of
    the Pallas flash kernel in ``repro.kernels.flash_attention`` (the TPU-target
    path; selected with ``attn_impl="pallas"``).
  * MoE uses sort-based gather/scatter dispatch (no GShard one-hot einsum): the
    dispatched activation tensor is the only O(tokens x topk x d_model) buffer.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import nn

# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TransformerConfig:
    name: str = "transformer"
    n_layers: int = 2
    d_model: int = 128
    n_heads: int = 4
    n_kv_heads: int = 4
    head_dim: int = 32
    d_ff: int = 512
    vocab_size: int = 1000
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    causal: bool = True
    act: str = "swiglu"                 # swiglu | gelu
    use_rope: bool = True
    max_position_embeddings: int = 0    # learned positions when >0 (BERT style)
    norm_style: str = "pre"             # pre (rms) | post (layernorm, BERT)
    # --- MoE ---
    moe_num_experts: int = 0
    moe_top_k: int = 0
    moe_d_ff: int = 0
    moe_num_shared: int = 0
    moe_mode: str = "replace"           # replace | residual (arctic)
    moe_capacity_factor: float = 1.25
    first_k_dense: int = 0
    router_aux_coef: float = 0.01
    norm_topk_prob: bool = True         # renormalise the top-k gates to sum 1
    # the chip's share of each expert layer (expert parallelism): the router
    # scores all moe_num_experts, w1/w3/w2 hold experts
    # [moe_first_held, moe_first_held + moe_experts_held); 0 holds them all
    moe_experts_held: int = 0
    moe_first_held: int = 0
    # --- MLA ---
    mla: bool = False
    kv_lora_rank: int = 0
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    # --- YaRN rope scaling of MLA (DeepSeek-V2 rope_scaling); 0 = plain RoPE
    yarn_factor: float = 0.0
    yarn_original_max_position: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0
    # --- execution ---
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    remat: bool = True
    q_chunk: int = 512                  # attention query-chunk size
    vocab_chunk: int = 0                # 0 = full logits; >0 = chunked xent
    attn_impl: str = "xla"              # xla | pallas (TPU target)
    # --- cost-extraction unrolls (roofline methodology) ---
    # XLA's cost_analysis counts a while(scan) body ONCE, not x trip-count;
    # the dry-run's cost-extraction variant fully unrolls every inner scan
    # (layers / attention q-chunks / vocab chunks) at reduced depth so
    # per-layer costs are counted exactly, then extrapolates to full depth.
    layer_unroll: int = 1
    attn_unroll: int = 1
    xent_unroll: int = 1
    # Expand KV heads to full H for the score/PV einsums (training only —
    # no cache involved).  With KV < TP degree, the grouped (B,S,KV,G,hd)
    # layout cannot shard heads over "model" (KV=8 < 16) and the O(S*T)
    # score tensor replicates across the TP axis; expansion restores a flat
    # (B,S,H,hd) layout that shards.  kv bytes grow G-fold but the score
    # tensor shrinks TP-fold — the Megatron GQA-under-TP training layout.
    attn_expand_kv: bool = False

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def is_moe(self) -> bool:
        return self.moe_num_experts > 0

    @property
    def experts_held(self) -> int:
        """Routed experts whose weights this layer holds."""
        return self.moe_experts_held or self.moe_num_experts


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _attn_init(rng, cfg: TransformerConfig):
    """Attention parameters for one layer (un-stacked)."""
    rngs = nn.split_rngs(rng, 8)
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = cfg.param_dtype
    p = {}
    if cfg.mla:
        qdim = H * (cfg.qk_nope_dim + cfg.qk_rope_dim)
        p["wq"] = nn.fanin_init(rngs[0], (D, qdim), ("embed", "heads"), dtype=dt)
        # joint down-projection -> [c_kv (kv_lora) | k_rope (rope_dim)]
        p["wdkv"] = nn.fanin_init(rngs[1], (D, cfg.kv_lora_rank + cfg.qk_rope_dim),
                                  ("embed", "kv_lora"), dtype=dt)
        p["kv_norm"] = nn.rmsnorm_init(cfg.kv_lora_rank, axes=("kv_lora",), dtype=dt)
        p["wuk"] = nn.fanin_init(rngs[2], (cfg.kv_lora_rank, H * cfg.qk_nope_dim),
                                 ("kv_lora", "heads"), dtype=dt)
        p["wuv"] = nn.fanin_init(rngs[3], (cfg.kv_lora_rank, H * cfg.v_head_dim),
                                 ("kv_lora", "heads"), dtype=dt)
        p["wo"] = nn.fanin_init(rngs[4], (H * cfg.v_head_dim, D), ("heads", "embed"),
                                fan_in=H * cfg.v_head_dim, dtype=dt)
    else:
        p["wq"] = nn.fanin_init(rngs[0], (D, H * hd), ("embed", "heads"), dtype=dt)
        p["wk"] = nn.fanin_init(rngs[1], (D, KV * hd), ("embed", "kv_heads"), dtype=dt)
        p["wv"] = nn.fanin_init(rngs[2], (D, KV * hd), ("embed", "kv_heads"), dtype=dt)
        p["wo"] = nn.fanin_init(rngs[3], (H * hd, D), ("heads", "embed"),
                                fan_in=H * hd, dtype=dt)
        if cfg.qkv_bias:
            p["bq"] = nn.zeros_init((H * hd,), ("heads",), dtype=dt)
            p["bk"] = nn.zeros_init((KV * hd,), ("kv_heads",), dtype=dt)
            p["bv"] = nn.zeros_init((KV * hd,), ("kv_heads",), dtype=dt)
    return p


def _dense_mlp_init(rng, cfg: TransformerConfig, d_ff: int):
    rngs = nn.split_rngs(rng, 3)
    D, dt = cfg.d_model, cfg.param_dtype
    if cfg.act == "swiglu":
        return {"w1": nn.fanin_init(rngs[0], (D, d_ff), ("embed", "mlp"), dtype=dt),
                "w3": nn.fanin_init(rngs[1], (D, d_ff), ("embed", "mlp"), dtype=dt),
                "w2": nn.fanin_init(rngs[2], (d_ff, D), ("mlp", "embed"),
                                    fan_in=d_ff, dtype=dt)}
    return {"w1": nn.linear_init(rngs[0], D, d_ff, ("embed", "mlp"), bias=True, dtype=dt),
            "w2": nn.linear_init(rngs[1], d_ff, D, ("mlp", "embed"), bias=True, dtype=dt)}


def _moe_init(rng, cfg: TransformerConfig):
    rngs = nn.split_rngs(rng, 5)
    D, E, F, dt = cfg.d_model, cfg.moe_num_experts, cfg.moe_d_ff, cfg.param_dtype
    if cfg.moe_first_held + cfg.experts_held > E:
        raise ValueError(f"experts {cfg.moe_first_held}.."
                         f"{cfg.moe_first_held + cfg.experts_held - 1} held "
                         f"of {E}")
    p = {"router": nn.normal_init(rngs[0], (D, E), ("embed", "expert"),
                                  stddev=0.02, dtype=jnp.float32)}
    E = cfg.experts_held
    p["w1"] = nn.fanin_init(rngs[1], (E, D, F), ("expert", "embed", "mlp"),
                            fan_in=D, dtype=dt)
    p["w3"] = nn.fanin_init(rngs[2], (E, D, F), ("expert", "embed", "mlp"),
                            fan_in=D, dtype=dt)
    p["w2"] = nn.fanin_init(rngs[3], (E, F, D), ("expert", "mlp", "embed"),
                            fan_in=F, dtype=dt)
    if cfg.moe_num_shared:
        p["shared"] = _dense_mlp_init(rngs[4], cfg, cfg.moe_num_shared * F)
    return p


def _norm_init(cfg: TransformerConfig):
    if cfg.norm_style == "post":
        return nn.layernorm_init(cfg.d_model, dtype=cfg.param_dtype)
    return nn.rmsnorm_init(cfg.d_model, dtype=cfg.param_dtype)


def _layer_init(rng, cfg: TransformerConfig, *, moe: bool):
    r1, r2, r3 = nn.split_rngs(rng, 3)
    p = {"attn_norm": _norm_init(cfg), "mlp_norm": _norm_init(cfg),
         "attn": _attn_init(r1, cfg)}
    if moe:
        p["moe"] = _moe_init(r2, cfg)
        if cfg.moe_mode == "residual":
            p["mlp"] = _dense_mlp_init(r3, cfg, cfg.d_ff)
    else:
        p["mlp"] = _dense_mlp_init(r3, cfg, cfg.d_ff)
    return p


def init(rng, cfg: TransformerConfig):
    """Returns a Param tree. Layer params are stacked along a leading L axis."""
    r_emb, r_layers, r_head, r_pos = nn.split_rngs(rng, 4)

    params = {"embed": nn.embedding_init(r_emb, cfg.vocab_size, cfg.d_model,
                                         axes=("vocab", "embed"), dtype=cfg.param_dtype)}
    if cfg.max_position_embeddings:
        params["pos_embed"] = nn.embedding_init(
            r_pos, cfg.max_position_embeddings, cfg.d_model,
            axes=("pos", "embed"), dtype=cfg.param_dtype)

    n_dense = cfg.first_k_dense if cfg.is_moe else cfg.n_layers
    n_moe = cfg.n_layers - n_dense if cfg.is_moe else 0

    def stack_init(r, n, moe):
        if n == 0:
            return None
        rngs = jnp.stack([jnp.asarray(x) for x in nn.split_rngs(r, n)])
        def one(rr):
            return _layer_init(rr, cfg, moe=moe)
        return jax.vmap(lambda rr: one(rr))(rngs)

    r_dense, r_moe = nn.split_rngs(r_layers, 2)
    dense_stack = stack_init(r_dense, n_dense, moe=False)
    if dense_stack is not None:
        # vmap strips Param wrappers' aux? No: vmap maps over arrays inside Param
        params["dense_layers"] = dense_stack
    moe_stack = stack_init(r_moe, n_moe, moe=True)
    if moe_stack is not None:
        params["moe_layers"] = moe_stack

    params["final_norm"] = _norm_init(cfg)
    if not cfg.tie_embeddings:
        params["lm_head"] = {"w": nn.fanin_init(r_head, (cfg.d_model, cfg.vocab_size),
                                                ("embed", "vocab"), dtype=cfg.param_dtype)}
    return params


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def _norm(p, x, cfg):
    if cfg.norm_style == "post":
        return nn.layernorm(p, x, cfg.norm_eps)
    return nn.rmsnorm(p, x, cfg.norm_eps)


def _chunked_attention(q, k, v, *, causal: bool, q_offset, q_chunk: int,
                       kv_mask=None, unroll: int = 1,
                       scale: Optional[float] = None):
    """Grouped-query attention computed in query chunks.

    q: (B, S, KV, G, hd) ; k, v: (B, T, KV, hd).
    q_offset: scalar — absolute position of q[0] (for causal masking in decode).
    kv_mask: optional (B, T) validity mask.
    scale: softmax scale of the logits (default 1/sqrt(hd)).
    Returns (B, S, KV, G, hd).
    """
    B, S, KV, G, hd = q.shape
    T = k.shape[1]
    dv = v.shape[-1]  # may differ from hd (MLA)
    scale = jnp.asarray(1.0 / (hd ** 0.5) if scale is None else scale,
                        jnp.float32)
    nq = max(1, min(q_chunk, S))
    n_chunks = -(-S // nq)
    pad = n_chunks * nq - S
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
    qc = q.reshape(B, n_chunks, nq, KV, G, hd)
    kpos = jnp.arange(T)

    def one_chunk(carry, inp):
        qi, ci = inp
        # bf16 operands + f32 accumulation (preferred_element_type) — the
        # MXU-native form.  Explicit .astype(f32) on k made XLA materialize
        # an f32 copy of the whole KV cache (loop-hoisted out of the layer
        # scan: +2x cache memory measured on qwen2-72b decode).
        s = jnp.einsum("bqkgd,btkd->bkgqt", qi * scale.astype(qi.dtype), k,
                       preferred_element_type=jnp.float32)
        if causal:
            qpos = q_offset + ci * nq + jnp.arange(nq)
            m = kpos[None, :] <= qpos[:, None]          # (nq, T)
            s = jnp.where(m[None, None, None], s, -1e30)
        if kv_mask is not None:
            s = jnp.where(kv_mask[:, None, None, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgqt,btkd->bqkgd", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
        return carry, o.astype(v.dtype)

    # checkpoint each chunk: without it, differentiating the scan stacks the
    # (B, KV, G, nq, T) softmax residuals across ALL chunks — O(S*T) memory,
    # exactly what chunking exists to avoid.  With it, backward recomputes
    # each chunk's scores on the fly (the flash-attention backward).
    _, outs = jax.lax.scan(jax.checkpoint(one_chunk), None,
                           (jnp.moveaxis(qc, 1, 0), jnp.arange(n_chunks)),
                           unroll=(n_chunks if unroll <= 0
                                   else min(unroll, n_chunks)))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, n_chunks * nq, KV, G, dv)
    return out[:, :S]


def _attention(p, x, cfg: TransformerConfig, *, positions, cache=None,
               cache_index=None, kv_mask=None):
    """Standard (non-MLA) GQA attention. Returns (out, new_cache)."""
    B, S, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    cd = cfg.compute_dtype
    q = x @ p["wq"].astype(cd)
    k = x @ p["wk"].astype(cd)
    v = x @ p["wv"].astype(cd)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(cd)
        k = k + p["bk"].astype(cd)
        v = v + p["bv"].astype(cd)
    q = nn.constrain(q.reshape(B, S, H, hd),
                     ("act_batch", "act_seq", "act_heads", None))
    k = nn.constrain(k.reshape(B, S, KV, hd),
                     ("act_batch", "act_seq", "act_kv_heads", None))
    v = nn.constrain(v.reshape(B, S, KV, hd),
                     ("act_batch", "act_seq", "act_kv_heads", None))
    if cfg.use_rope:
        q = nn.apply_rope(q, positions, cfg.rope_theta)
        k = nn.apply_rope(k, positions, cfg.rope_theta)

    if cache is not None:
        # write this step's k/v at cache_index (decode: S == 1)
        ck, cv = cache["k"], cache["v"]
        ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, cache_index, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, cache_index, 0, 0))
        k_all, v_all = ck.astype(cd), cv.astype(cd)
        new_cache = {"k": ck, "v": cv}
        T = ck.shape[1]
        # positions < cache_index + S are populated (prefill writes S at once)
        valid = jnp.arange(T)[None, :] < (cache_index + S)
        kv_mask = valid if kv_mask is None else (kv_mask & valid)
        q_offset = cache_index
    else:
        k_all, v_all = k, v
        new_cache = None
        q_offset = jnp.asarray(0, jnp.int32)

    if cfg.attn_expand_kv and cache is None:
        g = H // KV
        k_all = nn.constrain(jnp.repeat(k_all, g, axis=2),
                             ("act_batch", "act_seq", "act_heads", None))
        v_all = nn.constrain(jnp.repeat(v_all, g, axis=2),
                             ("act_batch", "act_seq", "act_heads", None))
        qg = q.reshape(B, S, H, 1, hd)
        out = _chunked_attention(qg, k_all, v_all, causal=cfg.causal,
                                 q_offset=q_offset, q_chunk=cfg.q_chunk,
                                 kv_mask=kv_mask, unroll=cfg.attn_unroll)
        out = out.reshape(B, S, H * hd)
        out = out @ p["wo"].astype(cd)
        return out, new_cache
    if cfg.attn_impl == "pallas" and cache is None and kv_mask is None:
        # TPU-target fused kernel (interpret-mode on CPU).  The cached /
        # masked paths keep the XLA implementation: decode uses the
        # decode_attention kernel via serving code, and ragged kv masks
        # need the t_valid scalar plumbing of ops.flash_attention.
        from repro.kernels.flash_attention.ops import flash_attention
        o = flash_attention(jnp.moveaxis(q, 1, 2), jnp.moveaxis(k_all, 1, 2),
                            jnp.moveaxis(v_all, 1, 2), causal=cfg.causal)
        out = jnp.moveaxis(o, 1, 2).reshape(B, S, H * hd)
    else:
        qg = q.reshape(B, S, KV, H // KV, hd)
        out = _chunked_attention(qg, k_all, v_all, causal=cfg.causal,
                                 q_offset=q_offset, q_chunk=cfg.q_chunk,
                                 kv_mask=kv_mask, unroll=cfg.attn_unroll)
        out = out.reshape(B, S, H * hd)
    out = out @ p["wo"].astype(cd)
    return out, new_cache


def _mla_rope(x, positions, cfg: TransformerConfig):
    """MLA's rotary embedding: YaRN's frequencies and cos/sin scale when
    ``cfg.yarn_factor`` is set, else plain RoPE."""
    if not cfg.yarn_factor:
        return nn.apply_rope(x, positions, cfg.rope_theta)
    inv_freq = nn.yarn_frequencies(
        x.shape[-1], cfg.rope_theta, cfg.yarn_factor,
        cfg.yarn_original_max_position, cfg.yarn_beta_fast,
        cfg.yarn_beta_slow)
    scale = (nn.yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
             / nn.yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))
    return nn.apply_rope(x, positions, cfg.rope_theta, inv_freq=inv_freq,
                         scale=scale)


def _mla_attention(p, x, cfg: TransformerConfig, *, positions, cache=None,
                   cache_index=None, kv_mask=None):
    """Multi-head latent attention (DeepSeek-V2). Cache stores (c_kv, k_rope).

    Prefill/train: expand c_kv -> per-head K_nope/V and run standard attention.
    Decode: *absorbed* form — queries are projected into the kv_lora space so
    attention runs directly against the compressed cache (the memory win MLA
    was designed for).
    """
    B, S, D = x.shape
    H = cfg.n_heads
    dn, dr, dv, R = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim, cfg.kv_lora_rank
    cd = cfg.compute_dtype

    q = (x @ p["wq"].astype(cd)).reshape(B, S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = _mla_rope(q_rope, positions, cfg)

    dkv = x @ p["wdkv"].astype(cd)                    # (B, S, R + dr)
    c_kv = nn.rmsnorm(p["kv_norm"], dkv[..., :R], cfg.norm_eps)
    k_rope = dkv[..., R:].reshape(B, S, 1, dr)
    k_rope = _mla_rope(k_rope, positions, cfg)

    scale = (1.0 / (dn + dr) ** 0.5
             * nn.yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2)

    if cache is not None and S == 1:
        # ---- absorbed decode (attention directly in the compressed space) ----
        cc, cr = cache["ckv"], cache["krope"]
        cc = jax.lax.dynamic_update_slice(cc, c_kv.astype(cc.dtype), (0, cache_index, 0))
        cr = jax.lax.dynamic_update_slice(cr, k_rope[:, :, 0].astype(cr.dtype),
                                          (0, cache_index, 0))
        new_cache = {"ckv": cc, "krope": cr}
        T = cc.shape[1]
        valid = (jnp.arange(T)[None, :] <= cache_index)
        if kv_mask is not None:
            valid = valid & kv_mask
        wuk = p["wuk"].astype(cd).reshape(R, H, dn)
        # q' = q_nope @ wuk^T per head: (B,S,H,R)
        q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, wuk)
        # bf16 operands, f32 accumulation (no f32 cache copies — see
        # _chunked_attention)
        s = jnp.einsum("bshr,btr->bhst", q_lat, cc,
                       preferred_element_type=jnp.float32)
        s = s + jnp.einsum("bshd,btd->bhst", q_rope, cr,
                           preferred_element_type=jnp.float32)
        s = s * scale
        s = jnp.where(valid[:, None, None, :], s, -1e30)
        prob = jax.nn.softmax(s, axis=-1)
        ctx = jnp.einsum("bhst,btr->bshr", prob.astype(cd), cc.astype(cd))  # (B,S,H,R)
        wuv = p["wuv"].astype(cd).reshape(R, H, dv)
        out = jnp.einsum("bshr,rhd->bshd", ctx, wuv)
    else:
        # train / prefill: expand the compressed kv and run chunked attention.
        q_offset = jnp.asarray(0, jnp.int32)
        if cache is not None:
            cc, cr = cache["ckv"], cache["krope"]
            cc = jax.lax.dynamic_update_slice(cc, c_kv.astype(cc.dtype),
                                              (0, cache_index, 0))
            cr = jax.lax.dynamic_update_slice(cr, k_rope[:, :, 0].astype(cr.dtype),
                                              (0, cache_index, 0))
            new_cache = {"ckv": cc, "krope": cr}
            c_all, r_all = cc.astype(cd), cr.astype(cd)[:, :, None]
            T = cc.shape[1]
            valid = jnp.arange(T)[None, :] < (cache_index + S)
            kv_mask = valid if kv_mask is None else (kv_mask & valid)
            q_offset = cache_index
        else:
            new_cache = None
            c_all, r_all = c_kv, k_rope
        T = c_all.shape[1]
        k_nope = (c_all @ p["wuk"].astype(cd)).reshape(B, T, H, dn)
        vv = (c_all @ p["wuv"].astype(cd)).reshape(B, T, H, dv)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(r_all, (B, T, H, dr))], axis=-1)
        qq = jnp.concatenate([q_nope, q_rope], axis=-1)
        # MLA has H kv heads (no grouping): KV=H, G=1
        qg = qq.reshape(B, S, H, 1, dn + dr)
        out = _chunked_attention(qg, k, vv, causal=cfg.causal,
                                 q_offset=q_offset,
                                 q_chunk=cfg.q_chunk, kv_mask=kv_mask,
                                 unroll=cfg.attn_unroll, scale=scale)
        out = out.reshape(B, S, H, dv)

    out = out.reshape(B, S, H * dv) @ p["wo"].astype(cd)
    return out, new_cache


# ---------------------------------------------------------------------------
# FFN / MoE
# ---------------------------------------------------------------------------


def _dense_mlp(p, x, cfg: TransformerConfig):
    cd = cfg.compute_dtype
    if cfg.act == "swiglu":
        h = nn.silu(x @ p["w1"].astype(cd)) * (x @ p["w3"].astype(cd))
        return h @ p["w2"].astype(cd)
    h = nn.gelu(nn.linear(p["w1"], x, cd))
    return nn.linear(p["w2"], h, cd)


def _moe_dispatch(x_flat, expert_idx, gates, E: int, capacity: int):
    """Sort-based dispatch for one group.

    x_flat: (S, D); expert_idx/gates: (S, K).
    Returns (xe (E, C, D), slot_tok (E*C,), slot_gate (E*C,), slot_valid (E*C,)).
    """
    S, K = expert_idx.shape
    N = S * K
    flat_e = expert_idx.reshape(N)
    flat_g = gates.reshape(N)
    flat_tok = jnp.repeat(jnp.arange(S), K)
    order = jnp.argsort(flat_e, stable=True)
    se, sg, stok = flat_e[order], flat_g[order], flat_tok[order]
    counts = jax.ops.segment_sum(jnp.ones(N, jnp.int32), se, num_segments=E)
    starts = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(N, dtype=jnp.int32) - starts[se]
    keep = pos < capacity
    slot = jnp.where(keep, se * capacity + pos, E * capacity)  # overflow -> dropped
    slot_tok = jnp.zeros(E * capacity + 1, jnp.int32).at[slot].set(stok)[:-1]
    slot_gate = jnp.zeros(E * capacity + 1, flat_g.dtype).at[slot].set(sg)[:-1]
    slot_valid = jnp.zeros(E * capacity + 1, jnp.bool_).at[slot].set(keep)[:-1]
    xe = x_flat[slot_tok].reshape(E, capacity, -1)
    return xe, slot_tok, slot_gate, slot_valid


def _moe_block(p, x, cfg: TransformerConfig):
    """Token-choice top-k MoE with sort-based dispatch.

    x: (B, S, D) — each batch row is a routing group.
    Returns (out, aux_loss).
    """
    B, S, D = x.shape
    E, K = cfg.moe_num_experts, cfg.moe_top_k
    cd = cfg.compute_dtype
    if cfg.experts_held != E:
        raise ValueError("the capacity form computes every expert; a layer "
                         "that holds a share of them runs on the encode path")
    capacity = max(1, int(S * K / E * cfg.moe_capacity_factor))

    logits = (x.astype(jnp.float32) @ p["router"].astype(jnp.float32))  # (B,S,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, expert_idx = jax.lax.top_k(probs, K)                          # (B,S,K)
    if cfg.norm_topk_prob:
        gates = gates / jnp.clip(gates.sum(-1, keepdims=True), 1e-9)

    # load-balancing aux loss (GShard): E * sum_e f_e * p_e
    me = jnp.mean(probs, axis=(0, 1))                                    # (E,)
    one_hot_top1 = jax.nn.one_hot(expert_idx[..., 0], E, dtype=jnp.float32)
    fe = jnp.mean(one_hot_top1, axis=(0, 1))
    aux = E * jnp.sum(me * fe)

    w1, w3, w2 = (p["w1"].astype(cd), p["w3"].astype(cd), p["w2"].astype(cd))

    # spmd_axis_name tells GSPMD the vmapped batch dim stays sharded on the
    # DP axes — without it the per-group dispatch buffers (B, E, C, D) are
    # free to replicate on the batch dim (observed: TB-scale buffers).
    spmd_axis = nn.act_rule("act_batch")
    xe, slot_tok, slot_gate, slot_valid = jax.vmap(
        lambda xg, eg, gg: _moe_dispatch(xg, eg, gg.astype(cd), E, capacity),
        spmd_axis_name=spmd_axis)(x.astype(cd), expert_idx, gates)
    # expert dim sharded (EP): the dispatch gather runs EP-local — without
    # this constraint GSPMD all-gathered the (B, E, C, D) dispatch buffer
    # across the mesh (23.7 GB/layer/device on arctic-480b, §Perf iter a5).
    xe = nn.constrain(xe, ("act_batch", "act_expert", None, None))

    h = jax.nn.silu(jnp.einsum("becd,edf->becf", xe, w1))
    h = h * jnp.einsum("becd,edf->becf", xe, w3)
    oe = jnp.einsum("becf,efd->becd", h, w2)                     # (B,E,C,D)
    wgt = (slot_gate * slot_valid.astype(cd)).reshape(B, E, capacity)
    oe = oe * wgt[..., None]
    seg = jnp.where(slot_valid, slot_tok, S)                     # dropped -> S

    # combine: per-group scatter-add back to (S, D).  (A flat global
    # scatter over B*E*C was tried and REFUTED — GSPMD emitted more
    # gathers, §Perf iter a6; the per-group form + EP-sharded dispatch
    # above is the best measured layout.)
    def combine(oe_g, seg_g):
        return jax.ops.segment_sum(oe_g.reshape(E * capacity, D),
                                   seg_g.reshape(-1),
                                   num_segments=S + 1)[:S]

    out = jax.vmap(combine, spmd_axis_name=spmd_axis)(
        oe, seg.reshape(B, E, capacity))
    out = nn.constrain(out, ("act_batch", "act_seq", "act_embed"))
    if cfg.moe_num_shared:
        out = out + _dense_mlp(p["shared"], x, cfg)
    return out, aux


def _gmm_interpret() -> bool:
    """Run the grouped product's Pallas kernel in interpret mode (the CPU)."""
    return jax.default_backend() == "cpu"


def _gmm_tiling(m: int, k: int, n: int):
    """Tiles of the grouped products: all ``m`` rows (at most 512), and
    whole ``k`` and ``n`` up to 1,536, else 512 (fast memory stays under 16
    MiB at DeepSeek-V2's widths)."""
    return (m, k if k <= 1536 else 512, n if n <= 1536 else 512)


def _gmm(lhs, rhs, group_sizes, out_dtype, transpose_rhs: bool = False):
    """Grouped product ``lhs (m, k)`` x ``rhs (g, k, n)`` (``(g, n, k)``
    with ``transpose_rhs``): rows of group ``i`` (consecutive,
    ``group_sizes[i]`` of them from the top) times ``rhs[i]``; rows past
    the last group are left unwritten.  Megablox's Pallas ``gmm``, whose
    grid visits only the tiles that hold rows, under the ``experts``
    scope."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    m, k = lhs.shape
    n = rhs.shape[1 if transpose_rhs else 2]
    with jax.named_scope("experts"):
        return gmm(lhs, rhs, group_sizes, out_dtype, _gmm_tiling(m, k, n),
                   transpose_rhs=transpose_rhs, interpret=_gmm_interpret())


def _tgmm(lhs, rhs, group_sizes, out_dtype):
    """Per group, ``lhs[rows].T @ rhs[rows]`` over the group's rows of
    ``lhs (m, k)`` and ``rhs (m, n)`` -> ``(g, k, n)``: the weight gradient
    of :func:`_gmm`.  Rows outside every group are not read."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm
    m, k = lhs.shape
    with jax.named_scope("experts"):
        return tgmm(lhs.swapaxes(0, 1), rhs, group_sizes, out_dtype,
                    _gmm_tiling(m, k, rhs.shape[1]),
                    interpret=_gmm_interpret())


def _expert_block(lo, blk, tok, starts, sizes):
    """Block ``[lo, lo + blk)`` of the sorted assignments: its tokens' rows
    and each held expert's count of rows in it (the grouped products'
    group sizes)."""
    rows = jax.lax.dynamic_slice_in_dim(tok, lo, blk)
    part = jnp.maximum(jnp.minimum(starts + sizes, lo + blk)
                       - jnp.maximum(starts, lo), 0)
    return rows, part


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _held_experts(blk, xf, w1, w3, w2, gate, tok, starts, sizes, n):
    """The held experts' gated SwiGLU outputs summed into each token's row,
    (N, D) float32.

    The ``n`` live assignments, sorted by held expert (``starts``/``sizes``
    each expert's span), are computed in blocks of ``blk``, as many as
    hold live ones: gather the block's tokens ``xf[tok]``, one grouped
    product per matrix, gate by ``gate``, scatter-add.  The trip count
    follows ``n``, so the loop is a ``while`` that reverse mode cannot
    differentiate; the rule below runs the same blocks backwards by hand.
    """
    cd = xf.dtype

    def block(i, out):
        lo = i * blk
        rows, part = _expert_block(lo, blk, tok, starts, sizes)
        xb = xf[rows]
        h = nn.silu(_gmm(xb, w1, part, cd)) * _gmm(xb, w3, part, cd)
        y = _gmm(h, w2, part, jnp.float32)
        live = lo + jnp.arange(blk) < n
        g = jax.lax.dynamic_slice_in_dim(gate, lo, blk)
        y = jnp.where(live[:, None], y * g[:, None], 0.0)
        return out.at[rows].add(y)

    return jax.lax.fori_loop(0, -(-n // blk), block,
                             jnp.zeros(xf.shape, jnp.float32))


def _held_experts_fwd(blk, *args):
    return _held_experts(blk, *args), args


def _held_experts_bwd(blk, res, ct):
    """Each live block again, recomputed: the cotangents of its tokens'
    rows (scatter-added), of the three weights (accumulated in float32 by
    :func:`_tgmm`) and of its gates.  Rows past the live ones hold whatever
    the unwritten grouped-product rows held, so they are selected away,
    never multiplied by zero."""
    xf, w1, w3, w2, gate, tok, starts, sizes, n = res
    cd = xf.dtype

    def block(i, acc):
        dx, dw1, dw3, dw2, dgate = acc
        lo = i * blk
        rows, part = _expert_block(lo, blk, tok, starts, sizes)
        live = (lo + jnp.arange(blk) < n)[:, None]
        g = jax.lax.dynamic_slice_in_dim(gate, lo, blk)
        xb = xf[rows]
        a, b = _gmm(xb, w1, part, cd), _gmm(xb, w3, part, cd)
        s, silu_vjp = jax.vjp(nn.silu, a)
        h = s * b
        y = _gmm(h, w2, part, jnp.float32)
        cy = jnp.where(live, ct[rows], 0.0)
        dg = jnp.where(live[:, 0], jnp.sum(cy * y, axis=1), 0.0)
        dy = (cy * g[:, None]).astype(cd)
        dh = _gmm(dy, w2, part, cd, transpose_rhs=True)
        da, db = silu_vjp(dh * b)[0], dh * s
        dxb = (_gmm(da, w1, part, jnp.float32, transpose_rhs=True)
               + _gmm(db, w3, part, jnp.float32, transpose_rhs=True))
        return (dx.at[rows].add(jnp.where(live, dxb, 0.0)),
                dw1 + _tgmm(xb, da, part, jnp.float32),
                dw3 + _tgmm(xb, db, part, jnp.float32),
                dw2 + _tgmm(h, dy, part, jnp.float32),
                jax.lax.dynamic_update_slice_in_dim(
                    dgate, dg.astype(dgate.dtype), lo, 0))

    zeros = lambda a: jnp.zeros(a.shape, jnp.float32)
    dx, dw1, dw3, dw2, dgate = jax.lax.fori_loop(
        0, -(-n // blk), block,
        (zeros(xf), zeros(w1), zeros(w3), zeros(w2), jnp.zeros_like(gate)))
    return (dx.astype(xf.dtype), dw1.astype(w1.dtype), dw3.astype(w3.dtype),
            dw2.astype(w2.dtype), dgate, None, None, None, None)


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


#: assignments a block of the encode path's expert layer dispatches at once
MOE_BLOCK = 512


def _moe_dropless(p, x, cfg: TransformerConfig, route_mask):
    """The encode path's expert layer: routes the tokens ``route_mask``
    marks over all ``moe_num_experts`` and drops none.

    Each marked token takes the softmax of its router logits (float32) and
    its top ``moe_top_k``, gated by those probabilities (renormalised when
    ``norm_topk_prob``).  The (token, expert) assignments that fall on the
    held experts are sorted by expert and computed in blocks of
    ``MOE_BLOCK``, as many blocks as there are assignments
    (:func:`_held_experts`, differentiable by its own reverse rule): gather
    the block's tokens, one grouped product per matrix over the held
    experts' weights (:func:`_gmm`), gate, scatter-add into the output.  The
    assignment buffers are sized for the most there can be, every token on
    ``min(top_k, held)`` held experts, so nothing is dropped; padding gets
    no assignment, so an embedding does not depend on its padding.  Plus
    the shared experts, for every token.

    x: (B, S, D); route_mask: (B, S) bool.  Returns (out, number of
    assignments computed, int32).
    """
    B, S, D = x.shape
    N, K, H = B * S, cfg.moe_top_k, cfg.experts_held
    cd = cfg.compute_dtype
    with jax.named_scope("moe"):
        xf = x.reshape(N, D).astype(cd)
        logits = jnp.matmul(xf.astype(jnp.float32),
                            p["router"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        gates, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), K)
        if cfg.norm_topk_prob:
            gates = gates / jnp.clip(gates.sum(-1, keepdims=True), 1e-9)
        local = idx - cfg.moe_first_held
        held = (local >= 0) & (local < H) & route_mask.reshape(N, 1)
        key = jnp.where(held, local, H).reshape(N * K)
        sizes = jax.ops.segment_sum(jnp.ones_like(key), key,
                                    num_segments=H + 1)[:H]
        n = jnp.sum(sizes)
        starts = jnp.cumsum(sizes) - sizes
        blk = min(MOE_BLOCK, -(-N * min(K, H) // 8) * 8)
        bound = -(-N * min(K, H) // blk) * blk
        order = jnp.argsort(key, stable=True)[:bound]
        order = jnp.pad(order, (0, bound - order.shape[0]))
        tok = order // K
        gate = gates.reshape(N * K)[order]
        w1, w3, w2 = (p[w].astype(cd) for w in ("w1", "w3", "w2"))
        out = _held_experts(blk, xf, w1, w3, w2, gate, tok, starts, sizes,
                            n)
        out = out.astype(cd).reshape(B, S, D)
        if cfg.moe_num_shared:
            out = out + _dense_mlp(p["shared"], x, cfg)
    return out, n


# ---------------------------------------------------------------------------
# Layer block + full forward
# ---------------------------------------------------------------------------


def _layer(p, x, cfg: TransformerConfig, *, positions, moe: bool, cache=None,
           cache_index=None, kv_mask=None, route_mask=None):
    x = nn.constrain(x, ("act_batch", "act_seq", "act_embed"))
    attn_fn = _mla_attention if cfg.mla else _attention
    if cfg.norm_style == "post":
        a, new_cache = attn_fn(p["attn"], x, cfg, positions=positions, cache=cache,
                               cache_index=cache_index, kv_mask=kv_mask)
        x = _norm(p["attn_norm"], x + a, cfg)
        m = _dense_mlp(p["mlp"], x, cfg)
        x = _norm(p["mlp_norm"], x + m, cfg)
        return x, new_cache, jnp.zeros((), jnp.float32)
    # pre-norm
    a, new_cache = attn_fn(p["attn"], _norm(p["attn_norm"], x, cfg), cfg,
                           positions=positions, cache=cache,
                           cache_index=cache_index, kv_mask=kv_mask)
    x = x + a
    h = _norm(p["mlp_norm"], x, cfg)
    aux = jnp.zeros((), jnp.float32)
    if moe:
        if route_mask is None:
            mo, aux = _moe_block(p["moe"], h, cfg)
        else:
            mo, aux = _moe_dropless(p["moe"], h, cfg, route_mask)
        if cfg.moe_mode == "residual":
            mo = mo + _dense_mlp(p["mlp"], h, cfg)
    else:
        mo = _dense_mlp(p["mlp"], h, cfg)
    return x + mo, new_cache, aux


def _scan_stack(stack_params, x, cfg, *, moe, positions, caches=None,
                cache_index=None, kv_mask=None, route_mask=None):
    """Scan a stacked layer group. caches: pytree stacked on leading L axis."""
    def body(carry, inp):
        h = carry
        lp, lc = inp
        h, new_cache, aux = _layer(lp, h, cfg, positions=positions, moe=moe,
                                   cache=lc, cache_index=cache_index,
                                   kv_mask=kv_mask, route_mask=route_mask)
        return h, (new_cache, aux)

    fn = jax.checkpoint(body) if cfg.remat else body
    n = jax.tree_util.tree_leaves(stack_params)[0].shape[0]
    x, (new_caches, auxs) = jax.lax.scan(
        fn, x, (stack_params, caches),
        unroll=(n if cfg.layer_unroll <= 0 else min(cfg.layer_unroll, n)))
    return x, new_caches, jnp.sum(auxs)


def forward(params, cfg: TransformerConfig, tokens, *, caches=None,
            cache_index=None, kv_mask=None, positions=None, route_mask=None):
    """Run the trunk. Returns (hidden (B,S,D), new_caches, aux).

    ``aux`` is the routers' load-balancing loss.  With ``route_mask``
    ((B, S) bool; the encode path) the expert layers take the dropless form
    (:func:`_moe_dropless`) over the tokens it marks, and ``aux`` is instead
    the int32 number of (token, held expert) assignments they computed."""
    B, S = tokens.shape
    cd = cfg.compute_dtype
    x = nn.embedding(params["embed"], tokens, cd)
    x = nn.constrain(x, ("act_batch", "act_seq", "act_embed"))
    if positions is None:
        if cache_index is not None:
            positions = cache_index + jnp.arange(S)[None, :]
        else:
            positions = jnp.arange(S)[None, :] * jnp.ones((B, 1), jnp.int32)
    if cfg.max_position_embeddings:
        x = x + nn.embedding(params["pos_embed"], positions, cd)

    aux_total = jnp.zeros((), jnp.float32)
    dense_caches = caches.get("dense") if caches is not None else None
    moe_caches = caches.get("moe") if caches is not None else None
    new_caches = {}
    if "dense_layers" in params:
        x, nc, aux = _scan_stack(params["dense_layers"], x, cfg, moe=False,
                                 positions=positions, caches=dense_caches,
                                 cache_index=cache_index, kv_mask=kv_mask)
        new_caches["dense"] = nc
        aux_total += aux
    if "moe_layers" in params:
        x, nc, aux = _scan_stack(params["moe_layers"], x, cfg, moe=True,
                                 positions=positions, caches=moe_caches,
                                 cache_index=cache_index, kv_mask=kv_mask,
                                 route_mask=route_mask)
        new_caches["moe"] = nc
        aux_total = aux if route_mask is not None else aux_total + aux
    if caches is not None:
        # preserve key parity with the input cache tree (a dense model's
        # init_cache carries "moe": None; dropping the key changes the
        # pytree structure and breaks scan/jit out_shardings matching)
        for key in caches:
            new_caches.setdefault(key, caches[key])
    x = nn.constrain(x, ("act_batch", "act_seq", "act_embed"))
    x = _norm(params["final_norm"], x, cfg)
    return x, (new_caches if caches is not None else None), aux_total


def _lm_head_weight(params, cfg):
    if cfg.tie_embeddings:
        return params["embed"]["table"].T
    return params["lm_head"]["w"]


def logits(params, cfg: TransformerConfig, hidden):
    w = _lm_head_weight(params, cfg).astype(cfg.compute_dtype)
    return hidden @ w


def chunked_softmax_xent(hidden, w_lm, labels, mask, chunk: int,
                         unroll: int = 1):
    """Cross-entropy without materializing full (..., V) logits.

    hidden: (..., D) f/bf16; w_lm: (D, V); labels: (...,) int32; mask bool.
    Scans over vocab chunks keeping a running logsumexp + the label logit.
    Leading dims are PRESERVED (no token flattening) so the batch sharding
    survives GSPMD propagation — flattening (B, S, D) -> (B*S, D) merges the
    sharded batch dim into an unshardable reshape and replicates the logits.
    """
    lead = hidden.shape[:-1]
    D = hidden.shape[-1]
    V = w_lm.shape[1]
    n_chunks = -(-V // chunk)
    Vp = n_chunks * chunk
    wp = jnp.pad(w_lm, ((0, 0), (0, Vp - V)))
    wc = wp.reshape(D, n_chunks, chunk)

    def body(carry, inp):
        run_lse, lab_logit = carry
        w_i, ci = inp
        lg = (hidden @ w_i).astype(jnp.float32)                  # (..., chunk)
        lg = nn.constrain(lg, ("act_batch", "act_seq", "act_vocab"))
        base = ci * chunk
        valid = (base + jnp.arange(chunk)) < V
        lg = jnp.where(valid, lg, -jnp.inf)
        chunk_lse = jax.nn.logsumexp(lg, axis=-1)
        run_lse = jnp.logaddexp(run_lse, chunk_lse)
        local = labels - base
        inside = (local >= 0) & (local < chunk)
        got = jnp.take_along_axis(lg, jnp.clip(local, 0, chunk - 1)[..., None],
                                  axis=-1)[..., 0]
        lab_logit = jnp.where(inside, got, lab_logit)
        return (run_lse, lab_logit), None

    init = (jnp.full(lead, -jnp.inf, jnp.float32),
            jnp.full(lead, -jnp.inf, jnp.float32))
    # checkpoint: backward recomputes each chunk's logits instead of stacking
    # (..., chunk) f32 residuals across all vocab chunks (same reasoning as
    # the attention q-chunk scan).
    (lse, lab), _ = jax.lax.scan(jax.checkpoint(body), init,
                                 (jnp.moveaxis(wc, 1, 0), jnp.arange(n_chunks)),
                                 unroll=(n_chunks if unroll <= 0
                                         else min(unroll, n_chunks)))
    nll = (lse - lab) * mask
    return nll.sum() / jnp.clip(mask.sum(), 1)


def lm_loss(params, cfg: TransformerConfig, batch):
    """Causal LM loss. batch: {"tokens": (B,S) int32} (optionally "mask")."""
    tokens = batch["tokens"]
    mask = batch.get("mask", jnp.ones_like(tokens, jnp.bool_))
    hidden, _, aux = forward(params, cfg, tokens)
    tgt = tokens[:, 1:]
    h = hidden[:, :-1]
    m = mask[:, 1:] & mask[:, :-1]
    w = _lm_head_weight(params, cfg).astype(cfg.compute_dtype)
    if cfg.vocab_chunk:
        xent = chunked_softmax_xent(h, w, tgt, m,
                                    cfg.vocab_chunk, unroll=cfg.xent_unroll)
    else:
        lg = (h @ w).astype(jnp.float32)                     # (B, S1, V)
        lse = jax.nn.logsumexp(lg, axis=-1)
        lab = jnp.take_along_axis(lg, tgt[..., None], axis=-1)[..., 0]
        xent = ((lse - lab) * m).sum() / jnp.clip(m.sum(), 1)
    loss = xent + cfg.router_aux_coef * aux
    return loss, {"xent": xent, "aux": aux}


# ---------------------------------------------------------------------------
# Serving entry points
# ---------------------------------------------------------------------------


def init_cache(cfg: TransformerConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16):
    """Abstract-friendly cache pytree, stacked per layer group."""
    n_dense = cfg.first_k_dense if cfg.is_moe else cfg.n_layers
    n_moe = cfg.n_layers - n_dense if cfg.is_moe else 0

    def one(n):
        if n == 0:
            return None
        if cfg.mla:
            return {"ckv": jnp.zeros((n, batch, max_len, cfg.kv_lora_rank), dtype),
                    "krope": jnp.zeros((n, batch, max_len, cfg.qk_rope_dim), dtype)}
        return {"k": jnp.zeros((n, batch, max_len, cfg.n_kv_heads, cfg.head_dim), dtype),
                "v": jnp.zeros((n, batch, max_len, cfg.n_kv_heads, cfg.head_dim), dtype)}

    return {"dense": one(n_dense), "moe": one(n_moe)}


def prefill(params, cfg: TransformerConfig, tokens, max_len: int = 0):
    """Encode a prompt, returning (last-token logits, caches).

    max_len: cache capacity (0 -> prompt length; set larger to decode after)."""
    B, S = tokens.shape
    caches = init_cache(cfg, B, max(max_len, S), dtype=cfg.compute_dtype)
    # cache_index = 0: positions 0..S-1 are written via dynamic_update_slice
    hidden, caches, _ = forward(params, cfg, tokens, caches=caches,
                                cache_index=jnp.asarray(0, jnp.int32))
    return logits(params, cfg, hidden[:, -1:]), caches


def decode_step(params, cfg: TransformerConfig, caches, token, index):
    """One decode step. token: (B,1) int32; index: scalar position to write."""
    hidden, caches, _ = forward(params, cfg, token, caches=caches,
                                cache_index=index)
    return logits(params, cfg, hidden), caches


# ---------------------------------------------------------------------------
# Embedding/encoding entry point (dense-retriever usage)
# ---------------------------------------------------------------------------


def encode(params, cfg: TransformerConfig, tokens, mask, pooling: str = "mean",
           *, count_held: bool = False):
    """Embed token sequences -> (B, D) L2-normalized vectors.

    ``pooling``: ``cls`` (position 0), ``mean`` (over the mask) or ``last``
    (the last marked position, ``sum(mask) - 1`` for right padding; 0 for a
    row with none), the pooling of a causal trunk.  Expert layers take the
    dropless form over the marked tokens; with ``count_held`` the result is
    ``(embeddings, int32 number of (token, held expert) assignments)``."""
    hidden, _, held = forward(params, cfg, tokens, kv_mask=mask,
                              route_mask=mask)
    if pooling == "cls":
        emb = hidden[:, 0]
    elif pooling == "last":
        last = jnp.maximum(mask.sum(1) - 1, 0)
        emb = jnp.take_along_axis(hidden, last[:, None, None], axis=1)[:, 0]
    else:
        m = mask.astype(hidden.dtype)[..., None]
        emb = (hidden * m).sum(1) / jnp.clip(m.sum(1), 1e-6)
    emb = emb.astype(jnp.float32)
    emb = emb / jnp.clip(jnp.linalg.norm(emb, axis=-1, keepdims=True), 1e-6)
    return (emb, held) if count_held else emb
