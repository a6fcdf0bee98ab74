"""Pallas kernel validation: shape/dtype sweeps + properties vs jnp oracles.

All kernels run in interpret mode on CPU (the TPU-target path is the same
kernel body); tolerances are fp32-accumulation level.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.topk_mips.kernel import (topk_mips_kernel,
                                            topk_mips_kernel_int8)
from repro.kernels.topk_mips.ops import quantize_int8, topk_mips
from repro.kernels.topk_mips.ref import topk_mips_ref

RNG = np.random.default_rng(42)


def _arr(shape, dtype):
    return jnp.asarray(RNG.normal(size=shape), dtype)


# ---------------------------------------------------------------------------
# topk_mips
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("Q,N,D,k", [
    (4, 300, 17, 10),          # ragged everything
    (128, 2048, 128, 100),     # aligned
    (7, 50, 64, 60),           # k > N (clipped)
    (1, 4096, 256, 1),         # top-1
    (33, 1000, 96, 128),       # k > default bn/8
])
def test_topk_mips_matches_ref(Q, N, D, k, dtype):
    q, c = _arr((Q, D), dtype), _arr((N, D), dtype)
    s, i = topk_mips(q, c, k=k)
    rs, ri = topk_mips_ref(q, c, k=min(k, N))
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(s), np.asarray(rs), rtol=tol,
                               atol=tol)
    # indices may legitimately differ on exact ties; compare as score sets
    agree = (np.asarray(i) == np.asarray(ri)).mean()
    assert agree > 0.95


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 40), st.integers(1, 400), st.integers(1, 80),
       st.integers(1, 50))
def test_topk_mips_property(Q, N, D, k):
    """Top-k scores are sorted desc and are the true row-wise maxima."""
    q, c = _arr((Q, D), jnp.float32), _arr((N, D), jnp.float32)
    s, i = topk_mips(q, c, k=k)
    s, i = np.asarray(s), np.asarray(i)
    assert (np.diff(s, axis=1) <= 1e-6).all()
    full = np.asarray(q) @ np.asarray(c).T
    kk = min(k, N)
    np.testing.assert_allclose(s[:, 0], full.max(axis=1), rtol=1e-5, atol=1e-5)
    gathered = np.take_along_axis(full, i, axis=1)
    np.testing.assert_allclose(gathered, s, rtol=1e-5, atol=1e-5)
    assert (np.sort(full, axis=1)[:, -kk:] >= s[:, -1:] - 1e-5).all()


def _quantized_oracle(q, c):
    """Numpy twin of the int8 scoring path: per-row symmetric quantization,
    EXACT integer accumulation (int32), then the per-row scale outer
    product — what the kernel's raw int32 scores dequantize to."""
    qv, qs = (np.asarray(a) for a in quantize_int8(jnp.asarray(q)))
    cv, cs = (np.asarray(a) for a in quantize_int8(jnp.asarray(c)))
    raw = qv.astype(np.int32) @ cv.astype(np.int32).T       # exact
    return raw.astype(np.float32) * qs * cs.T


@pytest.mark.parametrize("score_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("Q,N,D,k", [
    (4, 300, 17, 10),          # ragged everything
    (16, 1024, 128, 50),       # aligned
    (7, 50, 64, 60),           # k > N (clipped)
])
def test_topk_mips_narrow_dtype_parity(Q, N, D, k, score_dtype):
    """bf16/int8 paths: tolerance vs the f32 ref AND an exact rank-set gate
    vs the same-precision full-score oracle (quantization may legitimately
    reorder near-ties vs f32; it must NOT disagree with its own oracle)."""
    q, c = _arr((Q, D), jnp.float32), _arr((N, D), jnp.float32)
    s, i = topk_mips(q, c, k=k, score_dtype=score_dtype)
    s, i = np.asarray(s), np.asarray(i)
    kk = min(k, N)
    # tolerance gate vs f32 ref: quantization error is bounded
    rs, _ = topk_mips_ref(q, c, k=kk)
    scale = float(np.abs(np.asarray(rs)).max()) or 1.0
    assert np.abs(np.sort(s, 1) - np.sort(np.asarray(rs), 1)).max() \
        <= 0.05 * scale
    # exact rank-set gate vs the same-precision oracle
    if score_dtype == "int8":
        full = _quantized_oracle(q, c)
    else:
        full = np.asarray(jax.lax.dot_general(
            jnp.asarray(q, jnp.bfloat16), jnp.asarray(c, jnp.bfloat16),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32))
    oracle_i = np.argsort(-full, axis=1, kind="stable")[:, :kk]
    for r in range(Q):
        assert set(i[r]) == set(oracle_i[r])


@pytest.mark.parametrize("score_dtype", ["bf16", "int8"])
def test_topk_mips_n_valid_mask_narrow_dtypes(score_dtype):
    """Garbage in the corpus padding rows must be invisible at every
    precision — EXACTLY: per-row quantization means real rows' quantized
    images don't depend on the padding rows at all."""
    Q, N, D, k, n_valid = 8, 256, 32, 12, 200
    q, c = _arr((Q, D), jnp.float32), _arr((N, D), jnp.float32)
    s1, i1 = topk_mips(q, c[:n_valid], k=k, score_dtype=score_dtype)
    c2 = c.at[n_valid:].set(1e6)                 # garbage past n_valid
    s2, i2 = topk_mips(q, c2, k=k, n_valid=n_valid, score_dtype=score_dtype)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


def test_topk_mips_merge_ties_match_lax_top_k():
    """The in-kernel merge (k rounds of max-extraction) keeps lax.top_k's
    order on exact ties, across corpus tiles and the n_valid mask:
    integer-valued inputs make every dot product exact, and duplicated
    corpus rows make ties that span tiles."""
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.integers(-2, 3, size=(8, 128)), jnp.float32)
    rows = rng.integers(-2, 3, size=(64, 128))
    c = jnp.asarray(np.concatenate([rows, rows, rows[:40]]), jnp.float32)
    k, n_valid = 24, 150
    s, i = topk_mips(q, c, k=k, bn=128, n_valid=n_valid, interpret=True)
    full = np.asarray(q) @ np.asarray(c).T
    full[:, n_valid:] = -np.inf
    es, ei = jax.lax.top_k(jnp.asarray(full), k)
    np.testing.assert_array_equal(np.asarray(s), np.asarray(es))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ei))


def test_topk_mips_kernel_rejects_k_gt_bn():
    """The raw kernels assert k <= bn (a top-k wider than a corpus tile has
    no single-tile merge); the ops wrapper instead GROWS bn and succeeds."""
    q = jnp.zeros((8, 128), jnp.float32)
    c = jnp.zeros((128, 128), jnp.float32)
    with pytest.raises(AssertionError):
        topk_mips_kernel(q, c, k=256, n_valid=128, bq=8, bn=128,
                         interpret=True)
    qv, qs = quantize_int8(q)
    cv, cs = quantize_int8(c)
    with pytest.raises(AssertionError):
        topk_mips_kernel_int8(qv, cv, qs, cs.reshape(1, -1), k=256,
                              n_valid=128, bq=8, bn=128, interpret=True)
    s, i = topk_mips(q, c, k=96, bn=64)          # ops-level: bn grows
    assert np.asarray(s).shape == (8, 96)


def test_topk_mips_int8_per_tile_scales_at_boundaries():
    """Per-corpus-row scales must ride with their tiles: a corpus with a
    1000x magnitude cliff exactly at a bn-tile boundary still dequantizes
    each tile with its own rows' scales (a mixed-up tile/scale pairing
    would surface instantly as wrong winners)."""
    Q, D, bn = 4, 64, 128
    q = _arr((Q, D), jnp.float32)
    tiles = [np.asarray(_arr((bn, D), jnp.float32)) * m
             for m in (1.0, 1000.0, 0.001)]      # cliffs at rows 128, 256
    c = jnp.asarray(np.concatenate(tiles, axis=0))
    s, i = topk_mips(q, c, k=10, bn=bn, score_dtype="int8")
    s, i = np.asarray(s), np.asarray(i)
    full = _quantized_oracle(q, c)
    oracle_i = np.argsort(-full, axis=1, kind="stable")[:, :10]
    for r in range(Q):
        assert set(i[r]) == set(oracle_i[r])
    # dequantized kernel scores equal the exact-int oracle's to ~ulp (the
    # two f32 scale multiplies may reassociate between compilers)
    gathered = np.take_along_axis(full, i, axis=1)
    np.testing.assert_allclose(s, gathered, rtol=1e-6)
    # the big-magnitude tile's rows must dominate the top-k
    assert ((i >= bn) & (i < 2 * bn)).all()


def test_topk_mips_int8_matches_exact_integer_oracle():
    """The kernel's int8 x int8 accumulation is exact: its scores match the
    numpy int32 oracle to reassociation-ulp, never quantization-tolerance."""
    Q, N, D, k = 8, 512, 96, 20
    q, c = _arr((Q, D), jnp.float32), _arr((N, D), jnp.float32)
    s, i = topk_mips(q, c, k=k, score_dtype="int8")
    full = _quantized_oracle(np.asarray(q), np.asarray(c))
    gathered = np.take_along_axis(full, np.asarray(i), axis=1)
    np.testing.assert_allclose(np.asarray(s), gathered, rtol=1e-6)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KV,S,T,d,causal", [
    (2, 4, 2, 64, 64, 32, True),       # GQA causal
    (1, 8, 8, 33, 57, 64, False),      # MHA ragged bidir
    (2, 2, 1, 128, 256, 128, True),    # MQA cross-len
    (1, 14, 2, 40, 40, 64, True),      # qwen2-0.5b head config
])
def test_flash_attention_matches_ref(B, H, KV, S, T, d, causal, dtype):
    q = _arr((B, H, S, d), dtype)
    k = _arr((B, KV, T, d), dtype)
    v = _arr((B, KV, T, d), dtype)
    o = flash_attention(q, k, v, causal=causal, bq=32, bk=64)
    r = flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), rtol=tol, atol=tol)


def test_flash_attention_kv_padding_mask():
    """t_valid must make padded keys invisible."""
    B, H, S, T, d = 1, 2, 16, 64, 32
    q, k, v = _arr((B, H, S, d), jnp.float32), _arr((B, H, T, d), jnp.float32), \
        _arr((B, H, T, d), jnp.float32)
    o1 = flash_attention(q, k, v, causal=False, t_valid=40, bq=16, bk=16)
    k2 = k.at[:, :, 40:].set(1e3)          # garbage in padding
    v2 = v.at[:, :, 40:].set(-1e3)
    o2 = flash_attention(q, k2, v2, causal=False, t_valid=40, bq=16, bk=16)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-6)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 3), st.integers(1, 4), st.integers(1, 64),
       st.integers(1, 64), st.sampled_from([16, 32, 64]),
       st.booleans())
def test_flash_attention_property(B, H, S, T, d, causal):
    if causal and T < S:
        T = S
    q = _arr((B, H, S, d), jnp.float32)
    k = _arr((B, H, T, d), jnp.float32)
    v = _arr((B, H, T, d), jnp.float32)
    o = flash_attention(q, k, v, causal=causal, bq=16, bk=32)
    r = flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), rtol=3e-4,
                               atol=3e-4)


# ---------------------------------------------------------------------------
# decode_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,KV,G,T,d,L", [
    (2, 2, 4, 256, 64, 100),
    (1, 8, 1, 512, 128, 512),
    (3, 1, 7, 300, 32, 1),
    (1, 8, 8, 1024, 128, 700),     # deepseek-67b-like GQA decode
])
def test_decode_attention_matches_ref(B, KV, G, T, d, L, dtype):
    q = _arr((B, KV, G, d), dtype)
    k = _arr((B, KV, T, d), dtype)
    v = _arr((B, KV, T, d), dtype)
    o = decode_attention(q, k, v, L, bk=128)
    r = decode_attention_ref(L, q, k, v)
    tol = 2e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), rtol=tol, atol=tol)


def test_decode_attention_length_invariance():
    """Cache contents past ``length`` must not affect the output."""
    B, KV, G, T, d, L = 1, 2, 4, 256, 64, 93
    q = _arr((B, KV, G, d), jnp.float32)
    k = _arr((B, KV, T, d), jnp.float32)
    v = _arr((B, KV, T, d), jnp.float32)
    o1 = decode_attention(q, k, v, L, bk=64)
    k2 = k.at[:, :, L:].set(1e4)
    v2 = v.at[:, :, L:].set(-1e4)
    o2 = decode_attention(q, k2, v2, L, bk=64)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-6)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 2), st.integers(1, 4), st.integers(1, 8),
       st.integers(1, 200))
def test_decode_attention_property(B, KV, G, L):
    T, d = 256, 32
    q = _arr((B, KV, G, d), jnp.float32)
    k = _arr((B, KV, T, d), jnp.float32)
    v = _arr((B, KV, T, d), jnp.float32)
    o = decode_attention(q, k, v, L, bk=64)
    r = decode_attention_ref(L, q, k, v)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), rtol=3e-4,
                               atol=3e-4)
    # outputs are convex combinations of value rows -> bounded by their range
    vv = np.asarray(v[:, :, :L]).astype(np.float32)
    assert np.asarray(o).max() <= vv.max() + 1e-4
    assert np.asarray(o).min() >= vv.min() - 1e-4
