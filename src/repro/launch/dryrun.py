"""Multi-pod dry-run: lower + compile every (arch x shape) on the production
meshes and extract the roofline terms (no device allocation — all inputs are
ShapeDtypeStructs).

Per cell:
  1. FULL variant (scan-over-layers, remat) on the single-pod 16x16 mesh
     AND the 2x16x16 multi-pod mesh -> compile proof + memory analysis.
  2. COST variants (reduced depth, fully unrolled scans) on the single-pod
     mesh -> per-layer FLOPs/bytes/collective-wire-bytes, extrapolated to
     full depth (XLA counts scan bodies once — DESIGN.md §2.7).
  3. Roofline terms + bottleneck -> JSON under experiments/dryrun/.

Usage:
  python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k
  python -m repro.launch.dryrun --all [--jobs 4]     # every cell, subprocesses
  python -m repro.launch.dryrun --report             # aggregate JSON -> table

A CPU-only tool: run as a program it pins JAX to the CPU with 512 host
devices.  Nothing on the chip path imports it.
"""

import argparse
import json
import os
import subprocess
import sys
import time
import traceback


def _run_cell(arch: str, shape: str, out_dir: str, *, skip_multipod: bool,
              mesh_override=None, knobs=None, tag: str = "") -> dict:
    # imports deferred: jax must init after XLA_FLAGS (512 host devices)
    import jax
    from repro.launch import analysis
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import build_step

    rec = {"arch": arch, "shape": shape, "ok": False, "tag": tag,
           "knobs": knobs or {}, "timings": {}}

    def lower_compile(mesh, variant, cost_layers=1):
        t0 = time.time()
        kw = dict(knobs or {})
        # config-field overrides (everything not a builder kwarg)
        builder_keys = {"sp", "serve_layout"}
        cfg_ov = {k: v for k, v in kw.items() if k not in builder_keys}
        kw = {k: v for k, v in kw.items() if k in builder_keys}
        if cfg_ov:
            kw["cfg_overrides"] = cfg_ov
        spec = build_step(arch, shape, mesh, variant=variant,
                          cost_layers=cost_layers, **kw)
        jitted = jax.jit(spec.fn,
                         in_shardings=spec.in_shardings,
                         out_shardings=spec.out_shardings,
                         donate_argnums=spec.donate_argnums)
        lowered = jitted.lower(*spec.abstract_args)
        compiled = lowered.compile()
        dt = time.time() - t0
        return spec, compiled, dt

    world = 256
    single = make_production_mesh(multi_pod=False)

    # -- 1. FULL compile proof + memory analysis (single pod) --------------
    spec, compiled, dt = lower_compile(single, "full")
    rec["timings"]["full_single_s"] = dt
    rec["meta"] = {k: v for k, v in spec.meta.items()}
    ma = compiled.memory_analysis()
    mem = {attr: float(getattr(ma, attr)) for attr in
           ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes",
            "alias_size_in_bytes") if hasattr(ma, attr)}
    mem["per_device_total"] = (mem.get("argument_size_in_bytes", 0)
                               + mem.get("output_size_in_bytes", 0)
                               + mem.get("temp_size_in_bytes", 0)
                               - mem.get("alias_size_in_bytes", 0))
    rec["memory"] = mem
    full_meas = analysis.measure(compiled, world)
    rec["full_raw"] = {"flops": full_meas.flops,
                       "bytes": full_meas.bytes_accessed,
                       "coll_wire_bytes": full_meas.coll_wire_bytes}
    del compiled

    # -- 2. multi-pod compile proof (the "pod" axis shards) -----------------
    if not skip_multipod:
        multi = make_production_mesh(multi_pod=True)
        _, compiled_mp, dt = lower_compile(multi, "full")
        rec["timings"]["full_multipod_s"] = dt
        ma = compiled_mp.memory_analysis()
        rec["memory_multipod_per_device"] = float(
            getattr(ma, "argument_size_in_bytes", 0.0)
            + getattr(ma, "output_size_in_bytes", 0.0)
            + getattr(ma, "temp_size_in_bytes", 0.0)
            - getattr(ma, "alias_size_in_bytes", 0.0))
        del compiled_mp

    # -- 3. cost extraction (single pod) ------------------------------------
    n_scaled = _scaled_layers(arch, spec.meta)
    spec1, c1, dt1 = lower_compile(single, "cost", cost_layers=1)
    rec["timings"]["cost1_s"] = dt1
    q1 = analysis.measure(c1, world)
    del c1
    q2 = None
    if n_scaled > 1:
        _, c2, dt2 = lower_compile(single, "cost", cost_layers=2)
        rec["timings"]["cost2_s"] = dt2
        q2 = analysis.measure(c2, world)
        del c2
    full = analysis.extrapolate(q1, q2, n_scaled)
    rec["per_device"] = {"flops": full.flops, "bytes": full.bytes_accessed,
                         "coll_wire_bytes": full.coll_wire_bytes,
                         "n_scaled_layers": n_scaled}
    mf_per_dev = spec.meta["model_flops"] / world
    rec["roofline"] = analysis.roofline(full, mf_per_dev)
    # collective op histogram (from the 1-layer cost variant)
    hist = {}
    for op in q1.coll_ops:
        key = op["kind"]
        hist.setdefault(key, {"count": 0, "wire_bytes": 0.0})
        hist[key]["count"] += 1
        hist[key]["wire_bytes"] += op["wire_bytes"]
    rec["collectives_1layer"] = hist
    rec["ok"] = True
    return rec


def _scaled_layers(arch: str, meta: dict) -> int:
    """Size of the homogeneous layer stack the cost variant extrapolates."""
    from repro.configs import registry
    spec = registry.get(arch)
    cfg = spec.full_config()
    if spec.family in ("lm", "biencoder"):
        if getattr(cfg, "moe_num_experts", 0) > 0 and cfg.first_k_dense > 0:
            return cfg.n_layers - cfg.first_k_dense
        return cfg.n_layers
    if spec.family == "gnn":
        return cfg.n_layers
    if spec.family == "recsys":
        return 1          # cost variant keeps real depth, fully unrolled
    return 1


def run_one(args) -> int:
    out_dir = args.out
    os.makedirs(out_dir, exist_ok=True)
    tag = args.tag or "baseline"
    path = os.path.join(out_dir,
                        f"{args.arch}__{args.shape}__{tag}.json")
    knobs = {}
    for kv in (args.knobs.split(",") if args.knobs else []):
        k, v = kv.split("=", 1)
        for cast in (int, float):
            try:
                v = cast(v)
                break
            except ValueError:
                continue
        if v in ("true", "false"):
            v = v == "true"
        knobs[k] = v
    try:
        rec = _run_cell(args.arch, args.shape, out_dir,
                        skip_multipod=args.skip_multipod, tag=tag,
                        knobs=knobs or None)
    except Exception as e:
        rec = {"arch": args.arch, "shape": args.shape, "ok": False,
               "tag": tag, "error": repr(e),
               "traceback": traceback.format_exc()}
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    if rec["ok"]:
        r = rec["roofline"]
        print(f"[dryrun] {args.arch}/{args.shape}: OK  "
              f"mem/dev={rec['memory']['per_device_total']/2**30:.2f}GiB  "
              f"compute={r['compute_s']*1e3:.2f}ms mem={r['memory_s']*1e3:.2f}ms "
              f"coll={r['collective_s']*1e3:.2f}ms -> {r['bottleneck']}  "
              f"roofline_frac={r['roofline_frac']:.3f}")
        return 0
    print(f"[dryrun] {args.arch}/{args.shape}: FAIL {rec['error']}")
    print(rec.get("traceback", ""))
    return 1


def run_all(args) -> int:
    """Run every cell in its own subprocess (isolation + parallelism)."""
    from repro.launch.steps import all_cells
    cells = all_cells(include_paper_arch=not args.assigned_only)
    if args.filter:
        cells = [c for c in cells if args.filter in f"{c[0]}/{c[1]}"]
    if args.skip_existing:
        tag = args.tag or "baseline"

        def done(a, s):
            p = os.path.join(args.out, f"{a}__{s}__{tag}.json")
            if not os.path.exists(p):
                return False
            with open(p) as f:
                return json.load(f).get("ok", False)

        cells = [c for c in cells if not done(*c)]
        print(f"[dryrun --all] {len(cells)} cells remaining")
    procs, pending, failures = [], list(cells), []
    results = []
    while pending or procs:
        while pending and len(procs) < args.jobs:
            arch, shape = pending.pop(0)
            tagpart = ["--tag", args.tag] if args.tag else []
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--out", args.out] \
                + (["--skip-multipod"] if args.skip_multipod else []) + tagpart
            p = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                 stderr=subprocess.STDOUT, text=True)
            procs.append((arch, shape, p))
        for item in list(procs):
            arch, shape, p = item
            if p.poll() is not None:
                procs.remove(item)
                out = p.stdout.read()
                print(out.strip())
                results.append((arch, shape, p.returncode))
                if p.returncode != 0:
                    failures.append((arch, shape))
        time.sleep(0.5)
    print(f"\n[dryrun --all] {len(results) - len(failures)}/{len(results)} OK")
    for a, s in failures:
        print(f"  FAILED: {a}/{s}")
    return 1 if failures else 0


def report(args) -> int:
    import glob
    rows = []
    for path in sorted(glob.glob(os.path.join(args.out, "*.json"))):
        with open(path) as f:
            rec = json.load(f)
        if not rec.get("ok"):
            rows.append((rec["arch"], rec["shape"], rec.get("tag", ""),
                         "FAIL", "", "", "", "", "", "", "", ""))
            continue
        r = rec["roofline"]
        mp = rec.get("memory_multipod_per_device")
        rows.append((rec["arch"], rec["shape"], rec.get("tag", ""),
                     r["bottleneck"],
                     f"{r['compute_s']*1e3:.2f}",
                     f"{r['memory_s']*1e3:.2f}",
                     f"{r.get('memory_raw_s', 0)*1e3:.2f}",
                     f"{r['collective_s']*1e3:.2f}",
                     f"{rec['memory']['per_device_total']/2**30:.2f}",
                     f"{mp/2**30:.2f}" if mp else "-",
                     f"{r['useful_flops_frac']:.2f}",
                     f"{r['roofline_frac']:.3f}"))
    hdr = ("arch", "shape", "tag", "bound", "comp_ms", "mem_ms", "memraw_ms",
           "coll_ms", "GiB/dev", "GiB/dev@512", "useful", "roofline")
    if getattr(args, "md", False):
        print("| " + " | ".join(hdr) + " |")
        print("|" + "---|" * len(hdr))
        for r in rows:
            print("| " + " | ".join(str(x) for x in r) + " |")
    else:
        widths = [max(len(str(r[i])) for r in rows + [hdr])
                  for i in range(len(hdr))]
        for r in [hdr] + rows:
            print("  ".join(str(x).ljust(w) for x, w in zip(r, widths)))
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--report", action="store_true")
    ap.add_argument("--assigned-only", action="store_true")
    ap.add_argument("--filter", default="")
    ap.add_argument("--jobs", type=int, default=4)
    ap.add_argument("--tag", default="")
    ap.add_argument("--skip-multipod", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--md", action="store_true")
    ap.add_argument("--knobs", default="",
                    help="k=v[,k=v...] builder/config overrides "
                         "(sp=1, serve_layout=tp, param_dtype=bf16, ...)")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()
    if args.report:
        return report(args)
    if args.all:
        return run_all(args)
    assert args.arch and args.shape, "--arch and --shape (or --all)"
    return run_one(args)


if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    import jax
    jax.config.update("jax_platforms", "cpu")
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
