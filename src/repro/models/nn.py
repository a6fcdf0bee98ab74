"""Minimal functional neural-net toolkit.

No flax on this box, so models are pure functions over parameter pytrees.
Conventions:

* Parameters are nested dicts of ``jnp`` arrays.
* During ``init`` every leaf is wrapped in :class:`Param`, which carries the
  *logical axis names* of each dimension (e.g. ``("embed", "mlp")``).  The
  logical axes are pytree aux-data, so ``jax.eval_shape`` over an init
  function yields a ``ShapeDtypeStruct`` tree *with* axis metadata — this is
  how the dry-run obtains parameter shardings without allocating anything.
* ``materialize(tree)`` strips :class:`Param` wrappers -> plain array pytree.
* ``logical_axes(tree)`` extracts the parallel axes pytree.
* ``repro.distributed.sharding`` maps logical axes -> mesh ``PartitionSpec``.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import math
from typing import Any, Callable, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Param wrapper
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Param:
    """An array annotated with logical axis names (one per dim)."""

    value: Any
    axes: tuple

    def tree_flatten(self):
        return (self.value,), self.axes

    @classmethod
    def tree_unflatten(cls, axes, children):
        return cls(children[0], axes)

    @property
    def shape(self):
        return self.value.shape

    @property
    def dtype(self):
        return self.value.dtype


def is_param(x) -> bool:
    return isinstance(x, Param)


def materialize(tree):
    """Strip Param wrappers -> plain pytree of arrays (or ShapeDtypeStructs)."""
    return jax.tree_util.tree_map(lambda p: p.value, tree, is_leaf=is_param)


def logical_axes(tree):
    """Extract the logical-axes pytree parallel to ``materialize(tree)``."""
    return jax.tree_util.tree_map(lambda p: p.axes, tree, is_leaf=is_param)


def abstract_init(init_fn: Callable, *args, **kwargs):
    """eval_shape an init function; returns (ShapeDtypeStruct tree, axes tree).

    All arguments are closed over (treated as static/constant), so non-array
    args such as config dataclasses are fine.
    """
    out = jax.eval_shape(lambda: init_fn(*args, **kwargs))
    return materialize(out), logical_axes(out)


# ---------------------------------------------------------------------------
# Logical activation-sharding constraints
# ---------------------------------------------------------------------------
#
# GSPMD propagates *parameter* shardings into activations, but for large
# batches it can legally choose layouts that replicate the batch dimension
# (observed: 32 GiB/device logit chunks on a 0.5B model).  Models therefore
# pin activations at layer boundaries with *logical* names ("act_batch",
# "act_seq", ...) resolved against a context installed by the launcher —
# models never see mesh axes, and with no context installed (unit tests,
# single device) ``constrain`` is the identity.

_ACT_CTX: contextvars.ContextVar = contextvars.ContextVar(
    "activation_sharding", default=None)


@contextlib.contextmanager
def activation_sharding(mesh, rules: Mapping[str, Any]):
    """rules: logical activation axis -> mesh axis (str/tuple) or None."""
    token = _ACT_CTX.set({"mesh": mesh, "rules": dict(rules)})
    try:
        yield
    finally:
        _ACT_CTX.reset(token)


def act_rule(name: str):
    """Mesh-axis assignment for one logical activation axis (or None when no
    context / no rule).  Used e.g. as ``vmap(..., spmd_axis_name=...)`` so
    GSPMD knows a mapped dim is sharded (MoE per-group dispatch)."""
    ctx = _ACT_CTX.get()
    if ctx is None:
        return None
    return ctx["rules"].get(name)


def constrain(x, logical_axes: Sequence[Optional[str]]):
    """with_sharding_constraint by logical activation axes (no-op without
    an installed context; unknown names and non-divisible dims replicate)."""
    ctx = _ACT_CTX.get()
    if ctx is None:
        return x
    mesh, rules = ctx["mesh"], ctx["rules"]
    entries = []
    used = set()
    for dim, name in zip(x.shape, logical_axes):
        assignment = rules.get(name) if name is not None else None
        if assignment is None:
            entries.append(None)
            continue
        flat = (assignment,) if isinstance(assignment, str) else tuple(assignment)
        size = 1
        for a in flat:
            size *= mesh.shape[a]
        if any(a in flat for a in used) or dim % size != 0:
            entries.append(None)
            continue
        used.update(flat)
        entries.append(assignment)
    spec = jax.sharding.PartitionSpec(*entries)
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def normal_init(rng, shape, axes, stddev=0.02, dtype=jnp.float32) -> Param:
    return Param(jax.random.normal(rng, shape, dtype) * jnp.asarray(stddev, dtype), axes)


def fanin_init(rng, shape, axes, fan_in=None, dtype=jnp.float32) -> Param:
    """LeCun-normal style: stddev = 1/sqrt(fan_in)."""
    fan_in = fan_in if fan_in is not None else shape[0]
    std = 1.0 / math.sqrt(max(1, fan_in))
    return Param(jax.random.normal(rng, shape, dtype) * jnp.asarray(std, dtype), axes)


def zeros_init(shape, axes, dtype=jnp.float32) -> Param:
    return Param(jnp.zeros(shape, dtype), axes)


def ones_init(shape, axes, dtype=jnp.float32) -> Param:
    return Param(jnp.ones(shape, dtype), axes)


# ---------------------------------------------------------------------------
# Layers (init/apply pairs)
# ---------------------------------------------------------------------------


def linear_init(rng, in_dim, out_dim, axes, *, bias=True, dtype=jnp.float32,
                bias_axes=None):
    p = {"w": fanin_init(rng, (in_dim, out_dim), axes, dtype=dtype)}
    if bias:
        p["b"] = zeros_init((out_dim,), bias_axes if bias_axes is not None else (axes[-1],),
                            dtype=dtype)
    return p


def linear(params, x, compute_dtype=None):
    w = params["w"]
    if compute_dtype is not None:
        w = w.astype(compute_dtype)
        x = x.astype(compute_dtype)
    y = x @ w
    if "b" in params:
        y = y + params["b"].astype(y.dtype)
    return y


def embedding_init(rng, vocab, dim, axes=("vocab", "embed"), dtype=jnp.float32):
    return {"table": normal_init(rng, (vocab, dim), axes, stddev=0.02, dtype=dtype)}


def embedding(params, ids, compute_dtype=None):
    t = params["table"]
    if compute_dtype is not None:
        t = t.astype(compute_dtype)
    return jnp.take(t, ids, axis=0)


def layernorm_init(dim, axes=("embed",), dtype=jnp.float32):
    return {"scale": ones_init((dim,), axes, dtype=dtype),
            "bias": zeros_init((dim,), axes, dtype=dtype)}


def layernorm(params, x, eps=1e-6):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)
            + params["bias"].astype(jnp.float32)).astype(dt)


def rmsnorm_init(dim, axes=("embed",), dtype=jnp.float32):
    return {"scale": ones_init((dim,), axes, dtype=dtype)}


def rmsnorm(params, x, eps=1e-6):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(ms + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dt)


def gelu(x):
    return jax.nn.gelu(x, approximate=True)


def silu(x):
    return jax.nn.silu(x)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float = 10000.0) -> jnp.ndarray:
    """Inverse frequencies, shape (head_dim // 2,), float32."""
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)


def yarn_correction_range(head_dim: int, theta: float, original_max: int,
                          beta_fast: float, beta_slow: float):
    """YaRN's (low, high) frequency-pair indices (Peng et al.,
    arXiv:2309.00071): pairs below ``low`` turn more than ``beta_fast``
    times over ``original_max`` positions and keep their frequency; pairs
    from ``high`` up turn fewer than ``beta_slow`` times and are
    interpolated."""
    def corr(rotations: float) -> float:
        return (head_dim * math.log(original_max / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    return (max(math.floor(corr(beta_fast)), 0),
            min(math.ceil(corr(beta_slow)), head_dim - 1))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor ``0.1 * mscale * ln(factor) + 1``
    (1 when the context is not extended)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(head_dim: int, theta: float, factor: float,
                     original_max: int, beta_fast: float,
                     beta_slow: float) -> jnp.ndarray:
    """YaRN inverse frequencies, shape (head_dim // 2,), float32: pair ``i``
    blends ``theta_i / factor`` and ``theta_i`` by a linear ramp between the
    correction dims (DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding``)."""
    low, high = yarn_correction_range(head_dim, theta, original_max,
                                      beta_fast, beta_slow)
    extra = rope_frequencies(head_dim, theta)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    keep = 1.0 - ramp
    return extra / factor * (1.0 - keep) + extra * keep


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float = 10000.0,
               inv_freq: Optional[jnp.ndarray] = None, scale: float = 1.0):
    """Rotary embedding.

    x: (..., seq, n_heads, head_dim); positions: broadcastable to (..., seq).
    Pairs dimension d with d + head_dim//2 (the "rotate_half" convention).
    ``inv_freq`` replaces the plain frequencies (YaRN's); ``scale``
    multiplies cos and sin.
    """
    head_dim = x.shape[-1]
    if inv_freq is None:
        inv_freq = rope_frequencies(head_dim, theta)  # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., None, :]  # (..., seq, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Misc helpers
# ---------------------------------------------------------------------------


def split_rngs(rng, n):
    return list(jax.random.split(rng, n))


def count_params(params) -> int:
    leaves = jax.tree_util.tree_leaves(params)
    return int(sum(math.prod(l.shape) for l in leaves))


def cast_floating(tree, dtype):
    def _cast(x):
        if jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x
    return jax.tree_util.tree_map(_cast, tree)
