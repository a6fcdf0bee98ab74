"""The CPU-sized test cell of ``bench/tests/data``, run through the harness
without its look for a chip."""

import os
import time

from bench import harness
from bench.cells import load_cell

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELL = "tiny-bert.tiny-k20"


def cell():
    return load_cell(CELL, benchmark=os.path.join(DATA, "BENCHMARK.json"),
                     data_dir=DATA)


def run(seed: int, *, seconds: float = 0.5, trace: bool = False):
    harness.prepare_jax()
    return harness.run(cell(), seed=seed, seconds=seconds, trace=trace,
                       t_start=time.perf_counter(), platform=None)
