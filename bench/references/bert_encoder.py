"""Plain reference of the BERT-style bi-encoder trunk, and its weights.

Written from the published description (Devlin et al., arXiv:1810.04805;
post-LN blocks, learned positions, GELU) in straightforward ``jax.numpy``,
with the equations of the configuration file as the system under test states
them.  It imports nothing of the program.  Where the program's encoder
departs from the published BERT, this file follows the program and the
configuration file lists the departure under ``departures``:

* no LayerNorm on the embeddings and no token-type embeddings;
* a LayerNorm on the last block's output (``final_norm``);
* GELU in its tanh form.

``init`` makes the weights on the device in one jitted call, in the tree
layout the program's encoder reads, at BERT's initializer range.  ``encode``
takes a ``precision``: ``"highest"`` is float32 at HIGHEST matmul precision
(the reference); ``"fp8"`` is the control, the precision one step below the
configuration's bfloat16: every activation that the program's encoder holds in
bfloat16, and both operands of every matrix product, are rounded to float8
e4m3 after an absmax scale (per row; per column for a right operand), and
products accumulate in float32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
_FP8_MAX = 448.0           # largest finite float8 e4m3fn


def init(seed_key, cfg: dict):
    """Weights for ``cfg`` (a configuration file's dict) from a PRNG key, in
    the configuration's ``param_dtype``; one jitted call on the device."""
    return _init(seed_key, _shape_key(cfg))


def _shape_key(cfg: dict) -> tuple:
    t = cfg["transformer"]
    return (t["n_layers"], t["d_model"], t["n_heads"], t["head_dim"],
            t["d_ff"], t["vocab_size"], t["max_position_embeddings"],
            float(cfg["initializer_range"]), cfg["param_dtype"])


@functools.partial(jax.jit, static_argnums=1)
def _init(key, shape):
    n_l, d, h, hd, ff, vocab, n_pos, std, dtype = shape
    dtype = jnp.dtype(dtype)
    keys = iter(jax.random.split(key, 16))

    def normal(*s):
        return (std * jax.random.normal(next(keys), s, jnp.float32)
                ).astype(dtype)

    def zeros(*s):
        return jnp.zeros(s, dtype)

    def ones(*s):
        return jnp.ones(s, dtype)

    def norm(*lead):
        return {"scale": ones(*lead, d), "bias": zeros(*lead, d)}

    return {
        "embed": {"table": normal(vocab, d)},
        "pos_embed": {"table": normal(n_pos, d)},
        "dense_layers": {
            "attn_norm": norm(n_l), "mlp_norm": norm(n_l),
            "attn": {"wq": normal(n_l, d, h * hd), "wk": normal(n_l, d, h * hd),
                     "wv": normal(n_l, d, h * hd), "wo": normal(n_l, h * hd, d),
                     "bq": zeros(n_l, h * hd), "bk": zeros(n_l, h * hd),
                     "bv": zeros(n_l, h * hd)},
            "mlp": {"w1": {"w": normal(n_l, d, ff), "b": zeros(n_l, ff)},
                    "w2": {"w": normal(n_l, ff, d), "b": zeros(n_l, d)}},
        },
        "final_norm": norm(),
    }


def _fp8(x, axis=-1):
    """Float8 e4m3 image of ``x`` after an absmax scale along ``axis``, in
    float32, and the scale."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / _FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32), scale


def _act(x, precision: str):
    """An activation as the compute dtype holds it: float32 for the
    reference; float8 for the control, at every place where the program's
    encoder holds a bfloat16 activation."""
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


def _layernorm(p, x, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) / jnp.sqrt(var + eps)
    return y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)


def _gelu_tanh(x):
    c = math.sqrt(2.0 / math.pi)
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x ** 3)))


def _block(x, p, mask, *, n_heads, head_dim, eps, precision):
    B, L, _ = x.shape

    def act(h):
        return _act(h, precision)

    def dense(h, w, b):
        return act(_matmul(h, w, precision) + b.astype(jnp.float32))

    a = p["attn"]
    q, k, v = (dense(x, a[w], a[b]).reshape(B, L, n_heads, head_dim)
               .transpose(0, 2, 1, 3)
               for w, b in (("wq", "bq"), ("wk", "bk"), ("wv", "bv")))
    s = _matmul(q, k.transpose(0, 1, 3, 2), precision) / math.sqrt(head_dim)
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    o = act(_matmul(act(jax.nn.softmax(s, axis=-1)), v, precision))
    o = o.transpose(0, 2, 1, 3).reshape(B, L, n_heads * head_dim)
    o = act(_matmul(o, a["wo"], precision))
    x = act(_layernorm(p["attn_norm"], act(x + o), eps))
    m = p["mlp"]
    hdn = act(_gelu_tanh(dense(x, m["w1"]["w"], m["w1"]["b"])))
    hdn = dense(hdn, m["w2"]["w"], m["w2"]["b"])
    return act(_layernorm(p["mlp_norm"], act(x + hdn), eps))


@functools.partial(jax.jit, static_argnames=("n_heads", "head_dim", "eps",
                                             "pooling", "precision"))
def _encode(params, tokens, mask, *, n_heads, head_dim, eps, pooling,
            precision):
    L = tokens.shape[1]
    x = _act(params["embed"]["table"].astype(jnp.float32)[tokens]
             + params["pos_embed"]["table"].astype(jnp.float32)[:L][None],
             precision)

    def body(h, p):
        return _block(h, p, mask, n_heads=n_heads, head_dim=head_dim, eps=eps,
                      precision=precision), None

    x, _ = jax.lax.scan(body, x, params["dense_layers"])
    x = _act(_layernorm(params["final_norm"], x, eps), precision)
    if pooling == "cls":
        emb = x[:, 0]
    else:
        m = mask.astype(jnp.float32)[..., None]
        emb = (x * m).sum(1) / jnp.maximum(m.sum(1), 1e-6)
    return emb / jnp.maximum(jnp.linalg.norm(emb, axis=-1, keepdims=True),
                             1e-6)


def encode(params, cfg: dict, tokens, mask, precision: str = "highest"):
    """(B, L) tokens and mask -> (B, D) unit-norm float32 embeddings."""
    if precision not in ("highest", "fp8"):
        raise ValueError(f"unknown reference precision {precision!r}")
    t = cfg["transformer"]
    return _encode(params, jnp.asarray(tokens), jnp.asarray(mask),
                   n_heads=t["n_heads"], head_dim=t["head_dim"],
                   eps=float(t["norm_eps"]), pooling=cfg["pooling"],
                   precision=precision)


def host_copy(params):
    """The weights as host arrays (what a checkpoint hands the validator)."""
    return jax.tree_util.tree_map(np.asarray, params)
