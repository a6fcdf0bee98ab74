"""Integration tests: pallas attn inside the model, end-to-end async
train+validate, and the dry-run machinery on a small simulated mesh."""

import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.models import nn
from repro.models import transformer as tfm


def test_pallas_attention_path_matches_xla():
    for arch in ("qwen2-0.5b", "dr-bert-base"):
        cfg = registry.get(arch).smoke_config()
        cfg = dataclasses.replace(cfg, compute_dtype=jnp.float32)
        params = nn.materialize(tfm.init(jax.random.PRNGKey(0), cfg))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 1,
                                  cfg.vocab_size)
        h1, _, _ = tfm.forward(params, cfg, toks)
        cfgp = dataclasses.replace(cfg, attn_impl="pallas")
        h2, _, _ = tfm.forward(params, cfgp, toks)
        np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                                   rtol=3e-4, atol=3e-4)


def test_end_to_end_async_training_and_validation(tmp_path):
    """The launch/train.py deployment: async validator beats checkpoints out
    of a live training run, MRR improves, ledger is written."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from repro.launch.train import run

    class Args:
        arch = "dr-bert-base"
        workdir = str(tmp_path / "run")
        steps = 24
        ckpt_every = 8
        batch_size = 8
        corpus_size = 150
        n_queries = 25
        q_max_len = 10
        p_max_len = 26
        depth = 15
        lr = 2e-3
        seed = 0
        subset = True
        sync = False
        full = False

    res = run(Args())
    assert res["mode"] == "async"
    assert res["validated_steps"] == [8, 16, 24]
    assert not res["errors"]
    mrrs = [res["metrics"][s]["MRR@10"] for s in (8, 24)]
    assert mrrs[1] >= mrrs[0] - 0.05          # training not diverging


@pytest.mark.slow
def test_dryrun_machinery_small_mesh():
    """build_step + jit(lower/compile) + analysis on an 4x2 simulated mesh
    with the paper's own arch (subprocess so device count never leaks)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        from jax.sharding import Mesh
        import numpy as np
        from repro.launch import analysis
        from repro.launch.steps import build_step

        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        spec = build_step("dr-bert-base", "encode_corpus", mesh,
                          variant="cost")
        jitted = jax.jit(spec.fn, in_shardings=spec.in_shardings,
                         out_shardings=spec.out_shardings)
        lowered = jitted.lower(*spec.abstract_args)
        compiled = lowered.compile()
        m = analysis.measure(compiled, 8)
        assert m.flops > 0
        assert m.bytes_accessed > 0
        ma = compiled.memory_analysis()
        assert ma.temp_size_in_bytes >= 0
        r = analysis.roofline(m, spec.meta["model_flops"] / 8)
        assert r["bottleneck"] in ("compute", "memory", "collective")
        assert 0 <= r["roofline_frac"]
        print("DRYRUN_OK", r["bottleneck"])
    """)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900, env=env,
                         cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert "DRYRUN_OK" in out.stdout, out.stdout + out.stderr


def test_collective_parser():
    from repro.launch.analysis import parse_collectives
    hlo = """
      %ag = bf16[64,128]{1,0} all-gather(%x), replica_groups=[16,16]<=[256]
      %ar = f32[32]{0} all-reduce(%y), replica_groups={{0,1,2,3}}, to_apply=%add
      %rs = f32[8,8]{1,0} reduce-scatter(%z), replica_groups=[2,128]<=[256]
      %cp = bf16[4,4]{1,0} collective-permute(%w), source_target_pairs={{0,1}}
      %notacoll = f32[9]{0} add(%a, %b)
    """
    ops = parse_collectives(hlo, 256)
    kinds = sorted(o.kind for o in ops)
    assert kinds == ["all-gather", "all-reduce", "collective-permute",
                     "reduce-scatter"]
    ag = next(o for o in ops if o.kind == "all-gather")
    assert ag.group == 16
    assert ag.result_bytes == 64 * 128 * 2
    assert ag.wire_bytes == (15 / 16) * 64 * 128 * 2
    ar = next(o for o in ops if o.kind == "all-reduce")
    assert ar.group == 4
    assert ar.wire_bytes == 2 * (3 / 4) * 32 * 4
    rs = next(o for o in ops if o.kind == "reduce-scatter")
    assert rs.group == 128
    assert rs.wire_bytes == 127 * 64 * 4


def test_extrapolation_math():
    from repro.launch.analysis import Measurement, extrapolate
    q1 = Measurement(flops=10.0, bytes_accessed=100.0, coll_wire_bytes=4.0,
                     coll_ops=[], hbm_bytes_est=50.0)
    q2 = Measurement(flops=13.0, bytes_accessed=130.0, coll_wire_bytes=5.0,
                     coll_ops=[], hbm_bytes_est=60.0)
    full = extrapolate(q1, q2, n_scaled=10)
    assert full.flops == pytest.approx(10.0 + 9 * 3.0)
    assert full.bytes_accessed == pytest.approx(100.0 + 9 * 30.0)
    assert full.coll_wire_bytes == pytest.approx(4.0 + 9 * 1.0)
    assert full.hbm_bytes_est == pytest.approx(50.0 + 9 * 10.0)
    # no second measurement -> exact single measurement
    assert extrapolate(q1, None, 5).flops == 10.0
