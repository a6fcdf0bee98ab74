"""Distributed validation internals: sharded exact MIPS + straggler-tolerant
chunked corpus encoding — the pieces that turn the paper's single-GPU
validator into a pod-scale one.

Runs on 8 simulated host devices (re-execs itself with XLA_FLAGS).

    PYTHONPATH=src python examples/distributed_validation.py
"""

import os
import sys

if os.environ.get("XLA_FLAGS", "").find("host_platform_device_count") < 0:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.retrieval import topk_exact, topk_sharded
from repro.distributed.fault import run_chunked


def main():
    assert len(jax.devices()) == 8, "expected 8 simulated devices"
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rng = np.random.default_rng(0)
    Q, N, D, k = 16, 40_000, 64, 100
    q = jnp.asarray(rng.normal(size=(Q, D)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)

    # -- sharded exact MIPS: row-sharded corpus, hierarchical top-k merge --
    s_ref, i_ref = topk_exact(q, c, k=k)
    s_sh, i_sh = topk_sharded(mesh, q, c, k=k)
    agree = float((np.asarray(i_sh) == np.asarray(i_ref)).mean())
    print(f"[distributed] sharded top-{k} over {N} rows x 8 devices: "
          f"index agreement with single-device = {agree:.4f}")
    assert agree > 0.99

    # -- straggler-tolerant chunked encode ---------------------------------
    # one worker is 10x slower; speculation hides it.
    def encode_chunk(idxs):
        return np.asarray(c)[idxs].sum(axis=1)        # stand-in for encode

    items = list(range(N))
    chunks = [items[i:i + 2500] for i in range(0, N, 2500)]

    delays = {"w0": 0.02}                              # w0 is the straggler
    t0 = time.time()
    out = run_chunked(items, encode_chunk, n_workers=4, over_factor=4,
                      worker_delay=lambda w: delays.get(w, 0.0))
    dt = time.time() - t0
    total = sum(len(o) for o in out)
    print(f"[distributed] chunked encode of {total} items with a 1-in-4 "
          f"straggler + speculation: {dt:.2f}s, results exact = "
          f"{total == N}")
    assert total == N


if __name__ == "__main__":
    main()
