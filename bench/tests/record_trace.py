"""Record a traced run on the chip and keep what it shows.

    python3 bench/tests/record_trace.py --workload <name> --seed <n> \\
        --seconds <s> --out <dir> [--tiny]

Writes ``<dir>/describe.json`` (planes, lines and the first events of each
device line with all their stats: for reading the trace by hand),
``<dir>/ops.json`` (device time by module and operation) and, with
``--tiny`` (the CPU-sized test cell of ``bench/tests/data``), the reduced
trace itself as ``<dir>/trace.json``: the fixture of ``test_trace.py``.
Prints the run's result line.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    from bench import harness
    from bench import trace as trace_lib
    from bench.cells import load_cell
    harness.prepare_jax()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    data = os.path.join(HERE, "data")
    cell = (load_cell(args.workload,
                      benchmark=os.path.join(data, "BENCHMARK.json"),
                      data_dir=data)
            if args.tiny else load_cell(args.workload))
    os.makedirs(args.out, exist_ok=True)

    def keep(trace_dir, tr):
        with open(os.path.join(args.out, "describe.json"), "w") as f:
            json.dump(trace_lib.describe(trace_dir), f, indent=1)
        lo, hi = tr.window()
        by = {}
        for o in tr.ops:
            key = f"{o.module} | {o.opcode} | {o.text}"
            by[key] = by.get(key, 0) + (min(o.end, hi) - max(o.start, lo))
        with open(os.path.join(args.out, "ops.json"), "w") as f:
            json.dump(sorted(([k, v / 1e9] for k, v in by.items()),
                             key=lambda kv: -kv[1])[:300], f, indent=1)
        if args.tiny:
            trace_lib.save(tr, os.path.join(args.out, "trace.json"))

    out = harness.run(cell, seed=args.seed, seconds=args.seconds, trace=True,
                      t_start=T_START, on_trace=keep,
                      log=lambda m: print(m, file=sys.stderr, flush=True))
    print(json.dumps(out.line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
