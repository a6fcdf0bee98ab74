"""Plain reference of a DeepSeek-V2 trunk as a dense-retriever encoder, and
its weights.

Written from the published description (DeepSeek-AI, arXiv:2405.04434, and
the model's config.json): pre-norm blocks with RMSNorm; multi-head latent
attention with a joint key/value down-projection of rank ``kv_lora_rank``,
queries without a LoRA, a decoupled rotary part of ``qk_rope_dim`` per head
rotated with YaRN's frequencies and its softmax scale ``mscale(factor,
mscale_all_dim)**2 / sqrt(qk_nope_dim + qk_rope_dim)``; causal attention; a
dense SwiGLU in the first ``first_k_dense`` layers; then expert layers that
route each token by the softmax of its router logits to its top
``moe_top_k`` of ``moe_num_experts``, gate by those probabilities
(renormalised only with ``norm_topk_prob``), and add ``moe_num_shared``
shared experts as one SwiGLU of that many times the expert width.  The
embedding is the last real token's final hidden state, L2-normalised.  It
imports nothing of the program.

This is one chip's share of the layer: ``moe_experts_held`` experts from
``moe_first_held`` hold weights here, and each is computed densely over
every token and weighted by the gate the token gives it (zero where it did
not choose it, and for padding).  What the other experts add is left out,
as the program leaves it out.  Where the program departs from the published
model this file follows it, and the configuration file lists the departure:

* rotary pairs are (d, d + half) (rotate-half); the published code rotates
  interleaved pairs, a fixed permutation of the random weights;
* the final RMSNorm and the pooling follow the last layer kept, standing in
  for the last pipeline stage; there is no LM head.

``init`` makes the weights on the device in one jitted call, in the tree
layout the program's encoder reads, at the configuration's initializer range
(every matrix, the router too).  ``encode`` takes a ``precision``:
``"highest"`` is float32 at HIGHEST matmul precision (the reference);
``"fp8"`` is the control, the precision one step below the configuration's
bfloat16: every activation that the program holds in bfloat16, and both
operands of every matrix product, are rounded to float8 e4m3 after an absmax
scale (per row; per column for a right operand), and products accumulate in
float32 (``_fp8`` says how it rounds).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
_FP8_MAX = 240.0           # largest finite e4m3 with IEEE infinities


def init(seed_key, cfg: dict):
    """Weights for ``cfg`` (a configuration file's dict) from a PRNG key, in
    the configuration's ``param_dtype``; one jitted call on the device."""
    return _init(seed_key, _shape_key(cfg))


def _shape_key(cfg: dict) -> tuple:
    t = cfg["transformer"]
    return (t["n_layers"], t["first_k_dense"], t["d_model"], t["n_heads"],
            t["kv_lora_rank"], t["qk_nope_dim"], t["qk_rope_dim"],
            t["v_head_dim"], t["d_ff"], t["moe_num_experts"],
            _held(t), t["moe_d_ff"], t["moe_num_shared"], t["vocab_size"],
            float(cfg["initializer_range"]), cfg["param_dtype"])


def _held(t: dict) -> int:
    return t.get("moe_experts_held") or t["moe_num_experts"]


@functools.partial(jax.jit, static_argnums=1)
def _init(key, shape):
    (n_l, n_dense, d, h, r, dn, dr, dv, ff, n_exp, held, eff, n_shared,
     vocab, std, dtype) = shape
    dtype = jnp.dtype(dtype)
    keys = iter(jax.random.split(key, 32))

    def normal(*s, dt=dtype):
        return (std * jax.random.normal(next(keys), s, jnp.float32)
                ).astype(dt)

    def ones(*s):
        return jnp.ones(s, dtype)

    def block(n):
        return {"attn_norm": {"scale": ones(n, d)},
                "mlp_norm": {"scale": ones(n, d)},
                "attn": {"wq": normal(n, d, h * (dn + dr)),
                         "wdkv": normal(n, d, r + dr),
                         "kv_norm": {"scale": ones(n, r)},
                         "wuk": normal(n, r, h * dn),
                         "wuv": normal(n, r, h * dv),
                         "wo": normal(n, h * dv, d)}}

    def swiglu(lead, width):
        return {"w1": normal(*lead, d, width), "w3": normal(*lead, d, width),
                "w2": normal(*lead, width, d)}

    dense = block(n_dense)
    dense["mlp"] = swiglu((n_dense,), ff)
    n_moe = n_l - n_dense
    moe = block(n_moe)
    moe["moe"] = {"router": normal(n_moe, d, n_exp, dt=jnp.float32),
                  **swiglu((n_moe, held), eff),
                  "shared": swiglu((n_moe,), n_shared * eff)}
    return {"embed": {"table": normal(vocab, d)}, "dense_layers": dense,
            "moe_layers": moe, "final_norm": {"scale": ones(d)}}


# ---------------------------------------------------------------------------
# the two precisions
# ---------------------------------------------------------------------------


def _fp8(x, axis=-1):
    """Float8 e4m3 image of ``x`` after an absmax scale along ``axis``, in
    float32, and the scale.  The rounding is ``reduce_precision`` to 4
    exponent and 3 mantissa bits, in float32: a jitted round trip through
    ``float8_e4m3fn`` returned NaN for every row on a v5e (op by op it did
    not), and the IEEE form's largest finite value, 240, stands in for
    e4m3fn's 448: the same 3-bit precision, with one binade less range
    above and no subnormals below (values under 1/15,360 of the
    largest along ``axis`` flush to 0)."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / _FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(x / scale, -_FP8_MAX, _FP8_MAX)
    return jax.lax.reduce_precision(q, exponent_bits=4, mantissa_bits=3), \
        scale


def _act(x, precision: str):
    """An activation as the compute dtype holds it: float32 for the
    reference; float8 for the control, at every place where the program
    holds a bfloat16 activation."""
    x = x.astype(jnp.float32)
    if precision == "highest":
        return x
    q, scale = _fp8(x)
    return q * scale


def _matmul(a, b, precision: str):
    """``a (..., M, K) @ b (..., K, N)`` accumulated in float32.  The
    reference multiplies at HIGHEST; the control multiplies the float8
    images of both operands, which bfloat16 holds exactly, at DEFAULT
    precision, so each product is exact, and applies the scales after."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if precision == "highest":
        return jnp.matmul(a, b, precision=HIGHEST)
    qa, sa = _fp8(a, -1)
    qb, sb = _fp8(b, -2)
    return jnp.matmul(qa.astype(jnp.bfloat16), qb.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32) * sa * sb


# ---------------------------------------------------------------------------
# the trunk
# ---------------------------------------------------------------------------


def _rmsnorm(p, x, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(ms + eps) * p["scale"].astype(jnp.float32)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _rope_tables(t: dict, length: int):
    """cos and sin, (length, qk_rope_dim / 2), of YaRN's frequencies (plain
    RoPE without ``yarn_factor``), in float64 on the host."""
    dim, base = t["qk_rope_dim"], float(t.get("rope_theta", 10000.0))
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    factor = float(t.get("yarn_factor") or 0.0)
    scale = 1.0
    if factor:
        orig = t.get("yarn_original_max_position", 4096)

        def corr(rotations):
            return (dim * math.log(orig / (rotations * 2 * math.pi))
                    / (2 * math.log(base)))
        low = max(math.floor(corr(t.get("yarn_beta_fast", 32.0))), 0)
        high = min(math.ceil(corr(t.get("yarn_beta_slow", 1.0))), dim - 1)
        ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 0.001),
                       0.0, 1.0)
        extra = extra / factor * ramp + extra * (1.0 - ramp)
        scale = (_yarn_mscale(factor, t.get("yarn_mscale", 1.0))
                 / _yarn_mscale(factor, t.get("yarn_mscale_all_dim", 0.0)))
    angles = np.arange(length)[:, None] * extra[None, :]
    return np.cos(angles) * scale, np.sin(angles) * scale


def _rope(x, cos, sin):
    """x (B, L, heads, dim): pairs (d, d + dim/2) rotated by position."""
    half = x.shape[-1] // 2
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def _swiglu(p, x, precision):
    h = (jax.nn.silu(_act(_matmul(x, p["w1"], precision), precision))
         * _act(_matmul(x, p["w3"], precision), precision))
    return _matmul(_act(h, precision), p["w2"], precision)


def _attention(p, x, mask, cos, sin, t, precision):
    B, L, _ = x.shape
    h, r = t["n_heads"], t["kv_lora_rank"]
    dn, dr, dv = t["qk_nope_dim"], t["qk_rope_dim"], t["v_head_dim"]
    eps = float(t["norm_eps"])

    def act(a):
        return _act(a, precision)

    q = act(_matmul(x, p["wq"], precision)).reshape(B, L, h, dn + dr)
    q_nope, q_rope = q[..., :dn], act(_rope(q[..., dn:], cos, sin))
    dkv = act(_matmul(x, p["wdkv"], precision))
    c = act(_rmsnorm(p["kv_norm"], dkv[..., :r], eps))
    k_rope = act(_rope(dkv[..., r:].reshape(B, L, 1, dr), cos, sin))
    k_nope = act(_matmul(c, p["wuk"], precision)).reshape(B, L, h, dn)
    v = act(_matmul(c, p["wuv"], precision)).reshape(B, L, h, dv)
    qh = jnp.concatenate([q_nope, q_rope], -1).transpose(0, 2, 1, 3)
    kh = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, L, h, dr))],
                         -1).transpose(0, 2, 3, 1)
    scale = (_yarn_mscale(float(t.get("yarn_factor") or 0.0),
                          t.get("yarn_mscale_all_dim", 0.0)) ** 2
             / math.sqrt(dn + dr))
    s = _matmul(qh, kh, precision) * scale                  # (B, h, L, L)
    allowed = (jnp.tril(jnp.ones((L, L), bool))[None, None]
               & mask[:, None, None, :])
    s = jnp.where(allowed, s, -1e30)
    o = act(_matmul(act(jax.nn.softmax(s, axis=-1)),
                    v.transpose(0, 2, 1, 3), precision))
    o = o.transpose(0, 2, 1, 3).reshape(B, L, h * dv)
    return act(_matmul(o, p["wo"], precision))


def _gates(p, x, mask, t, precision):
    """Each token's gate for each held expert, (B, L, held): the softmax
    probability of an expert among the token's top ``moe_top_k`` (0 for the
    rest, and for padding)."""
    logits = _matmul(x, p["router"], precision)
    probs = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(probs, t["moe_top_k"])
    if t.get("norm_topk_prob", True):
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    first = t.get("moe_first_held", 0)
    chosen = jax.nn.one_hot(idx - first, _held(t), dtype=jnp.float32)
    return jnp.sum(chosen * top[..., None], axis=-2) * mask[..., None]


def _experts(p, x, mask, t, precision):
    """The held experts, each over every token, weighted by its gates; plus
    the shared experts.  Returns (output, number of (token, held expert)
    assignments)."""
    g = _gates(p, x, mask, t, precision)

    def one(out, e):
        w, ge = e
        return out + ge[..., None] * _act(_swiglu(w, x, precision),
                                          precision), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros(x.shape, jnp.float32),
        ({k: p[k] for k in ("w1", "w3", "w2")}, jnp.moveaxis(g, -1, 0)))
    out = _act(_act(routed, precision) + _act(
        _swiglu(p["shared"], x, precision), precision), precision)
    return out, jnp.sum(g > 0)


@functools.partial(jax.jit, static_argnames=("shape", "precision"))
def _encode(params, tokens, mask, cos, sin, *, shape, precision):
    t = dict(shape)
    eps = float(t["norm_eps"])

    def act(a):
        return _act(a, precision)

    x = act(params["embed"]["table"].astype(jnp.float32)[tokens])

    def attend(h, p):
        return act(h + _attention(p["attn"], act(_rmsnorm(
            p["attn_norm"], h, eps)), mask, cos, sin, t, precision))

    def dense(h, p):
        h = attend(h, p)
        return act(h + act(_swiglu(p["mlp"], act(_rmsnorm(
            p["mlp_norm"], h, eps)), precision))), None

    def expert(h, p):
        h = attend(h, p)
        out, n = _experts(p["moe"], act(_rmsnorm(p["mlp_norm"], h, eps)),
                          mask, t, precision)
        return act(h + out), n

    x, _ = jax.lax.scan(dense, x, params["dense_layers"])
    x, held = jax.lax.scan(expert, x, params["moe_layers"])
    x = act(_rmsnorm(params["final_norm"], x, eps))
    last = jnp.maximum(jnp.sum(mask, axis=1) - 1, 0)
    emb = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    emb = emb / jnp.maximum(jnp.linalg.norm(emb, axis=-1, keepdims=True),
                            1e-6)
    return emb, jnp.sum(held)


def _run(params, cfg: dict, tokens, mask, precision: str):
    if precision not in ("highest", "fp8"):
        raise ValueError(f"unknown reference precision {precision!r}")
    if cfg["pooling"] != "last":
        raise ValueError("this reference pools the last token")
    t = cfg["transformer"]
    tokens = jnp.asarray(tokens)
    cos, sin = _rope_tables(t, tokens.shape[1])
    return _encode(params, tokens, jnp.asarray(mask),
                   jnp.asarray(cos, jnp.float32), jnp.asarray(sin, jnp.float32),
                   shape=tuple(sorted(t.items())), precision=precision)


def encode(params, cfg: dict, tokens, mask, precision: str = "highest"):
    """(B, L) tokens and mask (right padding) -> (B, D) unit-norm float32
    embeddings."""
    return _run(params, cfg, tokens, mask, precision)[0]


def held_assignments(params, cfg: dict, tokens, mask) -> int:
    """The (token, held expert) assignments of the marked tokens, summed
    over the expert layers: the routed work the program counts."""
    return int(_run(params, cfg, tokens, mask, "highest")[1])


def host_copy(params):
    """The weights as host arrays (what a checkpoint hands the validator)."""
    return jax.tree_util.tree_map(np.asarray, params)
