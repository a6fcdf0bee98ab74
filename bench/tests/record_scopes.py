"""Record a traced run with the program's own span and scope names.

    python3 bench/tests/record_scopes.py --workload <name> --seed <n> \\
        --seconds <s> --out <dir> [--tiny]

Runs the cell as ``bench/run.py --trace 1`` does and keeps, besides the
result line, what the program names in the window
(``bench/program_trace.py``): the longest idle gaps cut at the ``repro.*``
and ``bench.*`` spans, the device operations with their scopes, device time
per scope and the share of the fused step's device time that maps to no
scope.  Writes ``<dir>/<workload>.scopes.json`` and prints the result line.
With ``--tiny`` (the CPU-sized test cell of ``bench/tests/data``, with the
metrics of ``BENCHMARK_scoped.json``) it also writes the reduced trace of
the window's first ``--verdicts`` verdicts, their ``repro.*`` spans and the
scopes of their instructions as ``<dir>/trace.json``: the fixture of
``test_program_trace.py``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def summary(tr, sc) -> dict:
    """What the program's names say about the traced window ``tr``."""
    from bench import program_trace
    from bench import trace as trace_lib
    out = {"idle_gaps": program_trace.split_gaps(tr),
           "window_s": trace_lib.window_s(tr), "busy_s": trace_lib.busy_s(tr)}
    if sc is not None:
        modules = sorted(sc.programs)
        out["device_ops"] = program_trace.scoped_ops(tr, sc, n=20)
        out["scope_s"] = {s: program_trace.scope_seconds(tr, sc, (s,))
                          for s in ("encode", "score", "merge")}
        out["scoped_modules"] = modules
        out["unscoped_share"] = program_trace.unscoped_share(tr, sc, modules)
        unscoped = {}
        lo, hi = tr.window()
        for o in tr.leaves():
            if o.module in modules and sc.of(o) is None:
                d = max(0, min(o.end, hi) - max(o.start, lo)) / 1e9
                unscoped[trace_lib.op_label(o)] = \
                    unscoped.get(trace_lib.op_label(o), 0.0) + d
        out["unscoped_ops"] = sorted(unscoped.items(),
                                     key=lambda kv: -kv[1])[:20]
    return out


def first_verdicts(tr, n: int):
    """``tr`` cut to its window's first ``n`` verdicts: the window ends
    with the ``n``-th ``bench.verdict`` span, and what starts later goes."""
    from bench.trace import Span, Trace
    lo, hi = tr.window()
    ends = sorted(s.end for s in tr.spans if s.name == "bench.verdict"
                  and lo <= s.start < hi)
    if len(ends) <= n:
        return tr
    cut = ends[n - 1]
    spans = [Span(s.name, s.start, min(s.end, cut)) for s in tr.spans
             if s.start < cut and s.name != "bench.window"]
    return Trace(ops=[o for o in tr.ops if o.start < cut],
                 spans=[Span("bench.window", lo, cut)] + spans,
                 n_devices=tr.n_devices)


def fixture(tr, sc, meta: dict, verdicts: int) -> dict:
    """The test fixture: the cut trace, with the scopes of its ops."""
    small = first_verdicts(tr, verdicts)
    d = small.to_json()
    texts = {o.text for o in small.ops}
    d["scopes"] = {t: s for t, s in (sc.by_text if sc else {}).items()
                   if t in texts}
    d["programs"] = sorted(sc.programs) if sc else []
    d.update(meta)
    return d


def main(argv=None, platform: str = "tpu") -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--verdicts", type=int, default=4)
    args = ap.parse_args(argv)
    from bench import harness, program_trace
    from bench.cells import load_cell
    from bench.trace import Trace
    harness.prepare_jax()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    data = os.path.join(HERE, "data")
    cell = (load_cell(args.workload,
                      benchmark=os.path.join(data, "BENCHMARK_scoped.json"),
                      data_dir=data)
            if args.tiny else load_cell(args.workload))
    os.makedirs(args.out, exist_ok=True)
    kept = {}

    def keep(trace_dir, tr):
        full = Trace(ops=tr.ops,
                     spans=tr.spans + program_trace.program_spans(trace_dir),
                     n_devices=tr.n_devices)
        sc = program_trace.scopes()
        kept.update(full=full, scopes=sc, summary=summary(full, sc))

    out = harness.run(cell, seed=args.seed, seconds=args.seconds, trace=True,
                      t_start=T_START, on_trace=keep, platform=platform,
                      log=lambda m: print(m, file=sys.stderr, flush=True))
    if args.tiny:
        meta = {"seed": args.seed, "device": out.line["device"]["kind"]}
        with open(os.path.join(args.out, "trace.json"), "w") as f:
            json.dump(fixture(kept["full"], kept["scopes"], meta,
                              args.verdicts), f, separators=(",", ":"))
    with open(os.path.join(args.out, f"{args.workload}.scopes.json"),
              "w") as f:
        json.dump({"summary": kept["summary"], "line": out.line}, f,
                  indent=1)
    print(json.dumps(out.line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
