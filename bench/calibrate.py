"""Readings that the limits of ``correct`` are set from.

    python3 bench/calibrate.py --workloads <name> [<name> ...] \\
        --seeds <n> [<n> ...] --out <file.jsonl>

For each seed, in one process: each cell's program drives one verdict
through its timed path (``ValidatorWorker.run_step`` after the harness's
own set-up and warm-up), at the cell's own size, and the verdict's numbers
are read against the float32 HIGHEST reference; then the control, the
reference computed in float8 and put in the program's place, is read the
same way.  Cells of one configuration on the same traffic share the
reference and control embeddings of a seed.  One JSON line per (seed, cell):
``{"seed", "workload", "program": {...}, "control": {...}}``.

The benchmark's own runs never run the control.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

# mix keys that do not change the generated traffic
_NOT_TRAFFIC = ("name", "source", "mode", "sampler", "k", "metrics",
                "check_queries", "assumed")


def traffic_key(cell) -> str:
    mix = {k: v for k, v in cell.mix.items() if k not in _NOT_TRAFFIC}
    return json.dumps([cell.config["name"], cell.sizes["corpus"], mix],
                      sort_keys=True)


def calibrate(cells, seeds, out, log=print, platform="tpu"):
    from bench import check, harness
    for seed in seeds:
        shared = {}
        for cell in cells:
            harness.device_info(platform, cell.chips)
            t0 = time.perf_counter()
            prog = harness.build(cell, seed, telemetry=False,
                                 annotate=harness.annotator(False))
            harness.warm_up(prog)
            prog.recorder.keep = True
            res = prog.worker.run_step(1)
            ckpt = prog.source.which(1)
            tr, kept = prog.traffic, prog.recorder.kept
            answers = harness.program_answers(
                cell, tr, kept, [res.tasks["default"].metrics], seed)
            shutil.rmtree(prog.workdir, ignore_errors=True)
            del prog
            key = traffic_key(cell)
            if key not in shared:
                params = cell.reference.init(harness.seed_key(seed, ckpt + 1),
                                             cell.config)
                shared[key] = {p: harness.reference_embeddings(cell, tr,
                                                               params, p)
                               for p in ("highest", "fp8")}
            emb = shared[key]
            ref = harness.reference_for(cell, tr, ckpt, seed,
                                        embeddings=emb["highest"])
            names = list(cell.mix["metrics"])
            control = check.control_answers(
                *emb["fp8"], harness.check_sample(cell.mix, len(tr.q_lens),
                                                  seed), int(cell.mix["k"]))
            # metrics against the reference's exact ranking: recorded, not
            # compared (see PERF.md)
            ranks = {p: check.gold_ranks(*emb[p], tr.gold) for p in emb}
            exact = check.metrics_from_ranks(ranks["highest"], names)
            low = check.metrics_from_ranks(ranks["fp8"], names)
            got = res.tasks["default"].metrics
            row = {"seed": seed, "workload": cell.name,
                   "program": {**check.readings(answers, ref),
                               **{f"gap.{m}": abs(got[m] - exact[m])
                                  for m in names}},
                   "control": {**check.readings([control], ref),
                               **{f"gap.{m}": abs(low[m] - exact[m])
                                  for m in names}},
                   "seconds": time.perf_counter() - t0}
            log(json.dumps(row))
            with open(out, "a") as f:
                f.write(json.dumps(row) + "\n")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workloads", nargs="+", required=True)
    ap.add_argument("--seeds", nargs="+", type=int, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    from bench import harness
    from bench.cells import load_cell
    harness.prepare_jax()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    calibrate([load_cell(n) for n in args.workloads], args.seeds, args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
