"""Benchmark entry point: one run of one cell.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell named in ``BENCHMARK.json`` from its files under ``bench/``,
sets up (weights on the device from the seed, traffic from the seed, the
token store, the compile of every program the window runs), measures
``--seconds`` of checkpoint verdicts through the validator's per-checkpoint
entry, checks the verdicts against the float32 HIGHEST reference, and prints
one JSON line as the last line of standard output.  With ``--trace 1`` the
window runs under the profiler and the line carries the per-layer metrics.
Without a TPU, or with fewer chips than the cell asks for, it exits 1 and
prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python3 bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    def log(msg: str) -> None:
        print(f"[bench] {msg}", file=sys.stderr, flush=True)

    try:
        from bench import harness
        from bench.cells import load_cell
        harness.prepare_jax()
        cell = load_cell(args.workload)
        from repro.compile_cache import enable_compile_cache
        enable_compile_cache()
        out = harness.run(cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), t_start=T_START, log=log)
    except (RuntimeError, KeyError, FileNotFoundError, ImportError) as e:
        log(f"no result: {e!r}")
        return 1
    for name, c in out.checks.items():
        print(f"[check] {name} = {c['value']!r} (limit {c['limit']!r})"
              f"{'' if c['ok'] else '  FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out.line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
