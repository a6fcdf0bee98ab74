"""Sharding rules engine + fault-tolerance primitives."""

import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed import fault
from repro.distributed import sharding as shd

# ---------------------------------------------------------------------------
# spec_for: divisibility fallback + conflict dedup + stacked layers
# ---------------------------------------------------------------------------


class FakeMesh:
    """Duck-typed mesh: only ``.shape`` (dict) and ``.axis_names`` used."""
    def __init__(self, **axes):
        self.shape = dict(axes)
        self.axis_names = tuple(axes)


MESH = FakeMesh(data=16, model=16)


def test_spec_for_basic_tp_fsdp():
    rules = shd.lm_train_rules()
    assert shd.spec_for((8192, 1024), ("embed", "kv_heads"), rules, MESH) \
        == P("data", "model")
    assert shd.spec_for((8192, 22016), ("embed", "mlp"), rules, MESH) \
        == P("data", "model")


def test_spec_for_divisibility_fallback():
    rules = shd.lm_train_rules()
    # BERT vocab 30522 is not divisible by 16 -> falls through model AND
    # data (30522 = 2 * 3 * 5087) -> replicated
    assert shd.spec_for((30522, 768), ("vocab", "embed"), rules, MESH) \
        == P(None, "data")
    # qwen2 vocab divides 16 -> model
    assert shd.spec_for((151936, 896), ("vocab", "embed"), rules, MESH) \
        == P("model", "data")


def test_spec_for_conflict_dedup():
    rules = shd.lm_train_rules()
    # MoE (expert, embed, mlp): expert wins "model"; mlp falls to replicated
    assert shd.spec_for((128, 7168, 4864), ("expert", "embed", "mlp"),
                        rules, MESH) == P("model", "data")


def test_spec_for_stacked_leading_dims():
    rules = shd.lm_train_rules()
    # 3-D array with 2 logical axes -> leading scan-stack dim unsharded
    assert shd.spec_for((95, 8192, 1024), ("embed", "kv_heads"), rules, MESH) \
        == P(None, "data", "model")


def test_spec_for_joint_axes():
    rules = shd.fsdp_only_rules()
    assert shd.spec_for((1024, 64), ("table_rows", "embed"), rules, MESH) \
        == P(("data", "model"))              # trailing None trimmed
    # second dim can't reuse consumed axes -> replicated
    assert shd.spec_for((256, 256), ("a", "b"), rules, MESH) \
        == P(("data", "model"))


def test_opt_state_shardings_adam_and_adafactor():
    from repro.train import optim
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    params_abs = {"w": jax.ShapeDtypeStruct((64, 32), jnp.float32),
                  "b": jax.ShapeDtypeStruct((32,), jnp.float32)}
    param_sh = {"w": jax.NamedSharding(mesh, P("data", "model")),
                "b": jax.NamedSharding(mesh, P("model"))}
    adam_abs = jax.eval_shape(optim.adamw(1e-3).init, params_abs)
    sh = shd.opt_state_shardings(adam_abs, params_abs, param_sh, mesh)
    assert sh["m"]["w"].spec == P("data", "model")     # same-shape slot
    assert sh["v"]["b"].spec == P("model")
    assert sh["step"].spec == P()                      # scalar replicated
    af_abs = jax.eval_shape(optim.adafactor(1e-3).init, params_abs)
    sh = shd.opt_state_shardings(af_abs, params_abs, param_sh, mesh)
    assert sh["slots"]["w"]["vr"].spec == P("data")    # (64,) = w minus dim 1
    assert sh["slots"]["w"]["vc"].spec == P("model")   # (32,) = w minus dim 0


def test_cache_spec_layouts():
    # batch shardable -> batch on data, seq on model
    assert shd.cache_spec(MESH, (95, 128, 32768, 8, 128), 128) \
        == P(None, ("data",), "model")
    # batch=1 -> sequence takes the whole mesh
    assert shd.cache_spec(MESH, (95, 1, 524288, 8, 128), 1) \
        == P(None, None, ("data", "model"))


def test_lm_batch_spec():
    assert shd.lm_batch_spec(MESH, 256) == P(("data",))
    assert shd.lm_batch_spec(MESH, 7) == P()           # unshardable
    multi = FakeMesh(pod=2, data=16, model=16)
    assert shd.lm_batch_spec(multi, 256) == P(("pod", "data"))


# ---------------------------------------------------------------------------
# WorkQueue / straggler / fault injection
# ---------------------------------------------------------------------------

def test_make_chunks_over_decomposition():
    chunks = fault.make_chunks(list(range(100)), n_workers=4, over_factor=4)
    assert 13 <= len(chunks) <= 16
    flat = [x for c in chunks for x in c.payload]
    assert flat == list(range(100))


def test_run_chunked_basic_order():
    out = fault.run_chunked(list(range(50)), lambda xs: [x * 2 for x in xs],
                            n_workers=3)
    assert [x for c in out for x in c] == [x * 2 for x in range(50)]


def test_run_chunked_with_straggler():
    """One consistently slow worker must not serialize the job: speculation
    re-executes its chunks elsewhere; results stay exact."""
    delays = {"w0": 0.05, "w1": 0.0, "w2": 0.0, "w3": 0.0}
    out = fault.run_chunked(list(range(40)), lambda xs: [x + 1 for x in xs],
                            n_workers=4, worker_delay=lambda w: delays[w])
    assert [x for c in out for x in c] == [x + 1 for x in range(40)]


def test_run_chunked_with_injected_failures():
    """Chunks that fail once are retried and complete."""
    out = fault.run_chunked(list(range(30)), lambda xs: list(xs),
                            n_workers=3, fail_once=(0, 2))
    assert [x for c in out for x in c] == list(range(30))


def test_workqueue_first_result_wins():
    chunks = fault.make_chunks([1, 2, 3, 4], n_workers=1, over_factor=1)
    q = fault.WorkQueue(chunks)
    c = q.acquire("a")
    # b speculates on the same chunk once the queue drains
    c2 = q.acquire("b")
    assert c2 is not None and c2.chunk_id == c.chunk_id
    assert q.complete("a", c.chunk_id, "A") is True
    assert q.complete("b", c.chunk_id, "B") is False   # loser discarded
    assert q.results()[0].value == "A"
    assert q.finished


def test_workqueue_permanent_failure_surfaces():
    chunks = fault.make_chunks([1], n_workers=1, over_factor=1)
    q = fault.WorkQueue(chunks, max_attempts=2)
    for _ in range(2):
        c = q.acquire("w")
        q.fail("w", c.chunk_id)
    assert q.failed_chunks == [0]
    with pytest.raises(RuntimeError):
        fault.run_chunked([1], lambda x: x, n_workers=1,
                          fail_once=())  # sanity: no failure -> fine
        raise RuntimeError("unreachable-guard")


def test_elastic_workers_join_mid_run():
    """Workers joining after the queue is half-drained still help."""
    chunks = fault.make_chunks(list(range(20)), n_workers=2, over_factor=2)
    q = fault.WorkQueue(chunks)
    # worker 1 processes half
    for _ in range(2):
        c = q.acquire("w1")
        q.complete("w1", c.chunk_id, sum(c.payload))
    # new worker joins (elasticity: acquire needs no registration)
    while not q.finished:
        c = q.acquire("w2")
        if c is None:
            break
        q.complete("w2", c.chunk_id, sum(c.payload))
    assert q.finished


# ---------------------------------------------------------------------------
# Sharded streaming rerank on a real multi-device mesh (forced host devices,
# subprocess — mirrors the sharded retrieval test in tests/test_engine.py)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_sharded_rerank_multidevice_subprocess():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import engine as E
        from repro.core import retrieval as R

        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        rng = np.random.default_rng(0)
        N, Q, D, chunk, k = 96, 6, 16, 24, 50     # chunk % 8 shards == 0
        # integer-valued table/queries: exact float32 dot products, so the
        # 8-shard run must equal the references bit for bit, not approx.
        params = {"table": jnp.asarray(rng.integers(-4, 5, size=(64, D)),
                                       jnp.float32)}
        doc_texts = [[int(i % 64)] for i in range(N)]
        c_emb = jnp.take(params["table"],
                         jnp.asarray([t[0] for t in doc_texts]), axis=0)
        q_emb = jnp.asarray(rng.integers(-4, 5, size=(Q, D)), jnp.float32)

        def enc(params, tokens, mask):
            return jnp.take(params["table"], tokens[:, 0], axis=0)

        qids = [f"q{i}" for i in range(Q)]
        dids = [f"d{i}" for i in range(N)]
        per_query = {
            qids[0]: ["d3", "d3", "d40", "d95"],           # duplicates
            qids[1]: [],                                   # empty
            qids[2]: [f"d{j}" for j in range(30)],         # ragged, 2 chunks
            qids[3]: ["d95"],                              # final chunk only
            qids[4]: ["d0", "d24", "d48", "d72"],          # one per chunk
            qids[5]: ["d7", "d7", "d9", "bogus"],          # dup + unknown
        }
        ref = R.rerank_run(qids, q_emb, dids, c_emb, per_query, k=k)

        store = E.TokenStore.build(doc_texts, max_len=2, chunk=chunk)
        stage = E.ShardedStreamRerankStage(enc, mesh, k=k, query_ids=qids,
                                           doc_ids=dids, per_query=per_query,
                                           store=store)
        carry = stage.init(q_emb)
        skipped = 0
        for toks, mask, base, n_valid in store.chunks():
            if not stage.wants_chunk(base // store.chunk):
                skipped += 1
                continue
            carry = stage.step(params, q_emb, carry, toks, mask, base,
                               n_valid)
        assert stage.finalize(carry) == ref, "sharded != materialized"

        # end to end: make_stage routes (mode=rerank, mesh=...) to the
        # sharded stage and the full engine (pre-sharded staging included)
        # scores identically to the single-device pipeline.
        from repro.core.pipeline import ValidationConfig, ValidationPipeline
        from repro.core.samplers import RerankTopK
        from repro.data import corpus as corpus_lib
        from repro.models.biencoder import EncoderSpec
        ds = corpus_lib.synthetic_retrieval_dataset(0, n_passages=200,
                                                    n_queries=20)
        def enc2(params, tokens, mask):
            emb = jnp.take(params["t"], tokens, axis=0)
            m = mask.astype(emb.dtype)[..., None]
            v = (emb * m).sum(1) / jnp.clip(m.sum(1), 1e-6)
            return v / jnp.clip(jnp.linalg.norm(v, axis=-1, keepdims=True),
                                1e-6)
        spec = EncoderSpec(
            name="toy", dim=16, encode_query=enc2, encode_passage=enc2,
            init=lambda rng: {"t": 0.1 * jax.random.normal(rng, (503, 16))},
            q_max_len=8, p_max_len=20)
        params2 = spec.init(jax.random.PRNGKey(0))
        base_run = corpus_lib.lexical_baseline_run(ds, k=30)
        kw = dict(metrics=("MRR@10",), mode="rerank", k=100, batch_size=40)
        on_mesh = ValidationPipeline(
            spec, ds.corpus, ds.queries, ds.qrels,
            ValidationConfig(mesh=mesh, chunk_size=40, **kw),
            sampler=RerankTopK(depth=10), baseline_run=base_run)
        assert on_mesh.engine.stage.name == "rerank_sharded"
        single = ValidationPipeline(
            spec, ds.corpus, ds.queries, ds.qrels,
            ValidationConfig(chunk_size=40, **kw),
            sampler=RerankTopK(depth=10), baseline_run=base_run)
        rm = on_mesh.validate_params(params2)
        rs = single.validate_params(params2)
        assert rm.metrics == rs.metrics, (rm.metrics, rs.metrics)
        print("SHARDED_RERANK_OK skipped=%d" % skipped)
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300)
    assert "SHARDED_RERANK_OK" in out.stdout, out.stdout + out.stderr
