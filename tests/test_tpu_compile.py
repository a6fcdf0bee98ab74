"""Compile rehearsals for one described TPU v5e chip.

The chip's compiler is installed even where no chip is attached, so the
main path's programs are compiled here at their real widths: what Mosaic
or XLA would refuse on the chip (an unlowerable primitive, a misaligned
block, too much memory) fails here at no chip time.  Nothing runs, so
these say nothing about results or speed.

The topology is described only inside the ``topo`` fixture: one process
at a time may load the TPU library, and describing it at import would
make the test workers disagree about which tests exist.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import registry
from repro.core.engine import StreamTopKStage
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.topk_mips import ops as mips_ops
from repro.models import nn
from repro.models.biencoder import biencoder_spec

D = 768             # dr-bert-base width


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def bert(one_chip):
    """(spec, parameter shapes on the described chip) of dr-bert-base."""
    cfg = registry.get("dr-bert-base").full_config()
    spec = biencoder_spec(cfg, q_max_len=32, p_max_len=128)
    shapes = jax.eval_shape(
        lambda: nn.materialize(spec.init(jax.random.PRNGKey(0))))
    params = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        shapes)
    return spec, params


def _arg(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_passage_encoder_compiles(bert, one_chip):
    spec, params = bert
    toks = _arg((64, 128), jnp.int32, one_chip)
    mask = _arg((64, 128), jnp.bool_, one_chip)
    compiled = jax.jit(spec.encode_passage).lower(params, toks,
                                                  mask).compile()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("window,Q,k", [(1, 256, 100), (8, 256, 100),
                                        (8, 6980, 1000)],
                         ids=["1", "8", "8-k1000"])
def test_fused_streaming_step_compiles(bert, one_chip, window, Q, k):
    """encode -> score -> top-k merge, the validator's jitted step: chunk
    64, 256 queries, k 100, one chunk or a scan window of 8; and the k
    1,000 cells' window (6,980 queries), which merges its 8 chunks at
    once."""
    spec, params = bert
    chunk, L = 64, 128
    stage = StreamTopKStage(spec.encode_passage, k=k,
                            query_ids=[f"q{i}" for i in range(Q)],
                            doc_ids=[f"d{i}" for i in range(16384)],
                            window=window)
    w = () if window == 1 else (window,)
    args = (params, _arg((Q, D), jnp.float32, one_chip),
            _arg((Q, k), jnp.float32, one_chip),
            _arg((Q, k), jnp.int32, one_chip),
            _arg(w + (chunk, L), jnp.int32, one_chip),
            _arg(w + (chunk, L), jnp.bool_, one_chip),
            _arg(w, jnp.int32, one_chip), _arg(w, jnp.int32, one_chip))
    fn = stage._fused if window == 1 else stage._fused_window
    fn.lower(*args).compile()


def test_flash_attention_compiles_at_bert_heads(one_chip):
    """The flash kernel at BERT-base heads: 12 x 64, 128 tokens."""
    q = _arg((8, 12, 128, 64), jnp.bfloat16, one_chip)
    f = jax.jit(lambda q, k, v: flash_attention(q, k, v, interpret=False))
    compiled = f.lower(q, q, q).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k", [10, 100])
@pytest.mark.parametrize("score_dtype", ["f32", "bf16", "int8"])
def test_topk_mips_compiles(one_chip, score_dtype, k):
    q = _arg((256, D), jnp.float32, one_chip)
    c = _arg((4096, D), jnp.float32, one_chip)
    f = jax.jit(lambda q, c: mips_ops.topk_mips(
        q, c, k=k, interpret=False, score_dtype=score_dtype))
    compiled = f.lower(q, c).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_dropless_expert_layer_compiles(one_chip, monkeypatch):
    """The encode path's expert layer at DeepSeek-V2-Lite's widths and the
    cell's chunk (64 passages x 128 tokens): router over 64 experts, top 6,
    8 held, each block's three grouped products as Pallas kernels."""
    import dataclasses

    from repro.models import transformer as tfm
    monkeypatch.setattr(tfm, "_gmm_interpret", lambda: False)
    cfg = dataclasses.replace(
        registry.get("deepseek-v2-lite-16b").full_config(),
        moe_experts_held=8)
    shapes = jax.eval_shape(lambda: nn.materialize(
        tfm._moe_init(jax.random.PRNGKey(0), cfg)))
    params = jax.tree_util.tree_map(
        lambda x: _arg(x.shape, x.dtype, one_chip), shapes)
    x = _arg((64, 128, cfg.d_model), jnp.bfloat16, one_chip)
    mask = _arg((64, 128), jnp.bool_, one_chip)
    f = jax.jit(lambda p, x, m: tfm._moe_dropless(p, x, cfg, m))
    text = f.lower(params, x, mask).compile().as_text()
    assert text.count("tpu_custom_call") == 3


def test_dropless_expert_layer_gradient_compiles(one_chip, monkeypatch):
    """The same layer's reverse rule compiles for the chip at those widths:
    the forward's three grouped products, their recomputation, the three
    transposed products of the input's cotangent and the three per-expert
    weight products (``tgmm``), all Pallas kernels."""
    import dataclasses

    from repro.models import transformer as tfm
    monkeypatch.setattr(tfm, "_gmm_interpret", lambda: False)
    cfg = dataclasses.replace(
        registry.get("deepseek-v2-lite-16b").full_config(),
        moe_experts_held=8)
    shapes = jax.eval_shape(lambda: nn.materialize(
        tfm._moe_init(jax.random.PRNGKey(0), cfg)))
    params = jax.tree_util.tree_map(
        lambda x: _arg(x.shape, x.dtype, one_chip), shapes)
    x = _arg((8, 128, cfg.d_model), jnp.bfloat16, one_chip)
    mask = _arg((8, 128), jnp.bool_, one_chip)

    def loss(p, x, m):
        out, _ = tfm._moe_dropless(p, x, cfg, m)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    f = jax.jit(jax.grad(loss, argnums=(0, 1)))
    text = f.lower(params, x, mask).compile().as_text()
    assert text.count("tpu_custom_call") == 12
