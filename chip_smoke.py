"""Chip smoke test: Asyncval's train -> async-validate loop on a TPU.

    python chip_smoke.py                # one chip (the default)
    python chip_smoke.py --four-chips   # four chips: sharded validation only

One chip, in one process, at the full width of ``dr-bert-base`` (12
layers, d=768, vocab 30,522; q_len 32, p_len 128) with weights drawn from
``--seed`` and a synthetic corpus made from the same seed:

  1. device     -- JAX must see a TPU; there is no CPU fallback.
  2. train      -- ``repro.launch.train.run``: contrastive steps, a
                   checkpoint every ``ckpt_every`` steps, the asynchronous
                   validator scoring each one; every saved step validated,
                   no errors, finite loss and metrics.
  3. cli        -- ``repro.core.cli.main`` over those checkpoints with the
                   XLA top-k and with the Pallas ``topk_mips`` kernel.
  4. reference  -- the last checkpoint re-encoded in float32 at
                   ``highest`` matmul precision, exact scores and top-k on
                   the host in float64; the validator's TREC run must match
                   it within ``SCORE_TOL``, and the same validator engine
                   run at ``highest`` precision within ``SCORE_TOL_HIGHEST``.
  5. serve      -- ``IndexBuilder`` -> ``QueryService`` answers queries from
                   the last checkpoint, checked against the same reference.

``--four-chips`` runs one phase instead: a checkpoint validated on
``make_validator_mesh(4)`` (the sharded streaming stage) and on device 0
(the single-chip stage), compared with each other at DEFAULT and at
HIGHEST matmul precision, plus where
``repro.launch.train.run`` places the trainer's params and the
validator's carry.

Each phase prints one JSON line with its wall time, compile time, peak
device memory and sizes.  They describe a smoke run, not a benchmark.  The
last line is ``{"ok": true, "device": {...}}``, printed only when every
phase passed; any failure exits non-zero.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import os
import shutil
import sys
import time
from typing import Dict, List, Sequence

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
WORKDIR = os.path.join(ROOT, ".smoke")

# Scores are cosines (``tfm.encode`` L2-normalizes), so the tolerances are
# absolute.  At DEFAULT matmul precision a TPU rounds every float32 operand
# to bfloat16 (unit roundoff 2^-9).  dr-bert-base chains about 6 matmuls per
# layer over 12 layers; 72 roundings adding in quadrature leave about
# sqrt(72) x 2^-9 = 1.7% error on a unit embedding and twice that on a
# cosine.  2^-4 leaves about 2x over that.
SCORE_TOL = 2.0 ** -4
# The same validator engine at HIGHEST precision: the reference's own
# arithmetic, so only float32 rounding and summation order differ.
SCORE_TOL_HIGHEST = 2.0 ** -10
# MRR@10 against the reference, absolute: with scores inside the tolerance
# only near-tied documents swap, and 0.01 is about 2.5 of 256 queries
# moving their gold document between ranks 1 and 2.
MRR_ATOL = 0.01


class SmokeFailure(Exception):
    """A phase ran but its output is wrong."""


@dataclasses.dataclass(frozen=True)
class Sizes:
    full: bool = True           # dr-bert-base full_config(); else smoke
    steps: int = 20
    ckpt_every: int = 10
    batch: int = 64             # train batch and validator chunk rows
    corpus: int = 16384
    queries: int = 256
    q_len: int = 32
    p_len: int = 128
    k: int = 100                # validator top-k (train.run's cut-off)
    serve_queries: int = 32
    serve_k: int = 10
    lr: float = 1e-4


CHIP = Sizes()
FOUR_CHIPS = dataclasses.replace(CHIP, steps=2, ckpt_every=2, corpus=4096)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# reporting: one JSON line per phase
# ---------------------------------------------------------------------------


class CompileClock:
    """Seconds XLA spent compiling (or loading from the persistent cache)
    in this process, summed from JAX's compile events."""

    def __init__(self):
        from jax._src import dispatch
        self._event = dispatch.BACKEND_COMPILE_EVENT
        self.total = 0.0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == self._event:
            self.total += duration

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self)


def _peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


def run_phase(name: str, device: Dict, clock: CompileClock, fn, *args):
    """Run one phase; print its line; return what it returned."""
    t0, c0 = time.perf_counter(), clock.total
    out, report = fn(*args)
    line = {"phase": name, "device": device["kind"],
            "wall_s": time.perf_counter() - t0,
            "compile_s": clock.total - c0,
            "peak_bytes_in_use": _peak_bytes(), **report}
    print(json.dumps(line), flush=True)
    return out


# ---------------------------------------------------------------------------
# phase 1: device
# ---------------------------------------------------------------------------


def device_info(platform: str = "tpu") -> Dict:
    """The device as JAX reports it; fails unless it is ``platform``."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    check(info["platform"] == platform,
          f"JAX found no {platform} device: {info}")
    return info


# ---------------------------------------------------------------------------
# data shared by the phases
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Setup:
    sizes: Sizes
    seed: int
    workdir: str
    cfg: object                 # TransformerConfig, float32 compute
    spec: object                # the EncoderSpec train.run builds from it
    ds: object                  # synthetic RetrievalDataset

    @property
    def ckpt_dir(self) -> str:
        return os.path.join(self.workdir, "ckpts")


def make_setup(sizes: Sizes, seed: int, workdir: str) -> Setup:
    """The encoder spec and dataset ``repro.launch.train.run`` builds for
    these arguments (same config, same seed, same sizes)."""
    import jax.numpy as jnp

    from repro.configs import registry
    from repro.data import corpus as corpus_lib
    from repro.models.biencoder import biencoder_spec

    arch = registry.get("dr-bert-base")
    cfg = arch.full_config() if sizes.full else arch.smoke_config()
    cfg = dataclasses.replace(cfg, compute_dtype=jnp.float32)
    spec = biencoder_spec(cfg, q_max_len=sizes.q_len, p_max_len=sizes.p_len)
    ds = corpus_lib.synthetic_retrieval_dataset(
        seed, n_passages=sizes.corpus, n_queries=sizes.queries,
        vocab=cfg.vocab_size)
    return Setup(sizes=sizes, seed=seed, workdir=workdir, cfg=cfg,
                 spec=spec, ds=ds)


def _train_argv(s: Setup) -> List[str]:
    z = s.sizes
    return (["--arch", "dr-bert-base", "--workdir", s.workdir,
             "--steps", str(z.steps), "--ckpt-every", str(z.ckpt_every),
             "--batch-size", str(z.batch), "--corpus-size", str(z.corpus),
             "--n-queries", str(z.queries), "--q-max-len", str(z.q_len),
             "--p-max-len", str(z.p_len), "--lr", str(z.lr),
             "--seed", str(s.seed), "--write-run"]
            + (["--full"] if z.full else []))


def _finite(xs) -> bool:
    return all(math.isfinite(float(x)) for x in xs)


def _last_params(s: Setup):
    from repro.ckpt import checkpoint as ckpt
    from repro.core.suite import params_from_checkpoint
    step = ckpt.latest_step(s.ckpt_dir)
    state, _ = ckpt.restore(s.ckpt_dir, step)
    return step, params_from_checkpoint(state)


# ---------------------------------------------------------------------------
# phase 2: train -> async-validate through repro.launch.train.run
# ---------------------------------------------------------------------------


def phase_train(s: Setup):
    from repro.ckpt import checkpoint as ckpt
    from repro.launch import train

    results = train.run(train.parse_args(_train_argv(s)))
    saved = ckpt.list_steps(s.ckpt_dir)
    z = s.sizes
    want = list(range(z.ckpt_every, z.steps + 1, z.ckpt_every))
    check(saved == want, f"saved steps {saved}, expected {want}")
    check(not results["errors"], f"validator errors: {results['errors']}")
    validated = sorted(results["validated_steps"])
    check(set(saved) <= set(validated),
          f"saved {saved} but validated only {validated}")
    for step in saved:
        m = results["metrics"][step]
        check(_finite(m.values()), f"step {step} metrics not finite: {m}")
    with open(os.path.join(s.workdir, "train.jsonl")) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    losses = {r["step"]: r["loss"] for r in rows}
    check(losses and _finite(losses.values()), f"train losses {losses}")
    report = {"sizes": dataclasses.asdict(z), "saved_steps": saved,
              "validated_steps": validated, "errors": len(results["errors"]),
              "loss": losses,
              "metrics": {str(k): v for k, v in results["metrics"].items()},
              "train_wall_s": results["wall_time_s"]}
    return results, report


# ---------------------------------------------------------------------------
# phase 3: the validator CLI, XLA and Pallas top-k
# ---------------------------------------------------------------------------


def write_cli_inputs(s: Setup) -> Dict[str, str]:
    """The dataset in the CLI's formats: JSONL texts and TREC qrels."""
    from repro.data.corpus import write_jsonl
    d = os.path.join(s.workdir, "cli_data")
    os.makedirs(os.path.join(d, "corpus"), exist_ok=True)
    paths = {"corpus": os.path.join(d, "corpus"),
             "queries": os.path.join(d, "queries.jsonl"),
             "qrels": os.path.join(d, "qrels.txt")}
    write_jsonl(os.path.join(paths["corpus"], "corpus.jsonl"), s.ds.corpus)
    write_jsonl(paths["queries"], s.ds.queries)
    with open(paths["qrels"], "w") as f:
        for qid, rels in s.ds.qrels.items():
            for did, gain in rels.items():
                f.write(f"{qid} 0 {did} {gain}\n")
    return paths


def phase_cli(s: Setup):
    import jax

    from repro.ckpt import checkpoint as ckpt
    from repro.core import cli

    paths = write_cli_inputs(s)
    z = s.sizes
    saved = ckpt.list_steps(s.ckpt_dir)
    report = {"backend": jax.default_backend(),
              "pallas_interpreted": jax.default_backend() == "cpu"}
    for impl in ("xla", "pallas"):
        out = os.path.join(s.workdir, f"cli_{impl}")
        argv = ["--query_file", paths["queries"],
                "--candidate_dir", paths["corpus"],
                "--ckpts_dir", s.ckpt_dir, "--qrel_file", paths["qrels"],
                "--q_max_len", str(z.q_len), "--p_max_len", str(z.p_len),
                "--arch", "dr-bert-base", "--impl", impl,
                "--batch_size", str(z.batch), "--retrieve_k", str(z.k),
                "--metrics", "MRR@10", "Recall@100",
                "--report_to", "jsonl", "--run_name", impl,
                "--output_dir", out] + ([] if z.full else ["--smoke"])
        rc = cli.main(argv)
        check(rc == 0, f"cli --impl {impl} returned {rc}")
        with open(os.path.join(out, f"{impl}_metrics.jsonl")) as f:
            rows = [json.loads(line) for line in f if line.strip()]
        got = {r["step"]: {m: r[m] for m in ("MRR@10", "Recall@100")}
               for r in rows}
        check(sorted(got) == saved,
              f"cli --impl {impl} validated {sorted(got)}, saved {saved}")
        for step, m in got.items():
            check(_finite(m.values()),
                  f"cli --impl {impl} step {step} metrics {m}")
        report[impl] = {str(k): v for k, v in got.items()}
    for step in saved:
        a, b = report["xla"][str(step)], report["pallas"][str(step)]
        check(abs(a["MRR@10"] - b["MRR@10"]) <= MRR_ATOL,
              f"step {step}: MRR@10 xla {a} vs pallas {b}")
    return None, report


# ---------------------------------------------------------------------------
# phase 4: float32 reference, exact top-k on the host
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Reference:
    step: int
    query_ids: List[str]
    doc_ids: List[str]
    scores: np.ndarray          # (Q, N) float64, all pairs


def _encode_highest(cfg, params, texts: Sequence, max_len: int,
                    rows: int) -> np.ndarray:
    import jax

    from repro.data.corpus import pad_batch
    from repro.models import transformer as tfm

    # a fresh jit of the trunk, independent of the validator's cached
    # encoders: float32 compute at the highest matmul precision
    enc = jax.jit(lambda p, t, m: tfm.encode(p, cfg, t, m, "cls"))
    out = []
    with jax.default_matmul_precision("highest"):
        for lo in range(0, len(texts), rows):
            part = list(texts[lo:lo + rows])
            real = len(part)
            toks, mask = pad_batch(part + [[0]] * (rows - real), max_len)
            out.append(np.asarray(enc(params, toks, mask),
                                  np.float64)[:real])
    return np.concatenate(out)


def reference_scores(s: Setup, step: int, params) -> Reference:
    z = s.sizes
    qids, dids = list(s.ds.queries), list(s.ds.corpus)
    q = _encode_highest(s.cfg, params, [s.ds.queries[i] for i in qids],
                        z.q_len, z.batch)
    p = _encode_highest(s.cfg, params, [s.ds.corpus[i] for i in dids],
                        z.p_len, 4 * z.batch)
    return Reference(step=step, query_ids=qids, doc_ids=dids,
                     scores=q @ p.T)


def compare_topk(ids: np.ndarray, scores: np.ndarray, ref: np.ndarray,
                 tol: float) -> Dict:
    """Check a system's top-k against exact reference scores.

    ``ids`` (Q, k) corpus rows and ``scores`` (Q, k) as the system ranked
    them; ``ref`` (Q, N) reference scores of every pair; ``tol`` the
    absolute score tolerance.  Passes when (a) every returned score is within
    ``tol`` of the reference score of the same document, (b) no returned
    document is worse than the reference's k-th best by more than 2 tol,
    and (c) no document better than the worst returned one by more than
    2 tol is missing.  Order inside a band of 2 tol is free (near-ties).
    Returns the measured margins; raises SmokeFailure on a violation.
    """
    Q, k = ids.shape
    check(all(len(set(row)) == k for row in ids.tolist()),
          "a query returned the same document twice")
    got_ref = np.take_along_axis(ref, ids, axis=1)
    err = np.abs(scores - got_ref) / tol
    kth = -np.partition(-ref, k - 1, axis=1)[:, k - 1]
    worse = got_ref < (kth - 2 * tol)[:, None]
    floor = got_ref.min(axis=1) + 2 * tol
    must = (ref > floor[:, None]).sum(axis=1)
    have = (got_ref > floor[:, None]).sum(axis=1)
    ref_ids = np.argsort(-ref, axis=1, kind="stable")[:, :k]
    stats = {"queries": Q, "k": k,
             "max_score_err_over_tol": float(err.max()),
             "max_abs_score_err": float(np.abs(scores - got_ref).max()),
             "tol": tol,
             "rank_exact_frac": float((ids == ref_ids).mean()),
             "set_overlap_frac": float(np.mean(
                 [len(set(a) & set(b)) / k
                  for a, b in zip(ids.tolist(), ref_ids.tolist())])),
             "topk_spread_over_tol": float(np.median(
                 (got_ref.max(axis=1) - got_ref.min(axis=1)) / tol))}
    check(err.max() <= 1.0, f"scores off the reference by up to "
          f"{err.max():.3f} x tol: {stats}")
    check(not worse.any(), f"{int(worse.sum())} returned documents rank "
          f"below the reference's k-th by more than 2 tol: {stats}")
    check((have >= must).all(), f"{int((must - have).clip(0).sum())} "
          f"clearly better documents missing: {stats}")
    return stats


def _run_arrays(run: Dict[str, Sequence[str]],
                scores: Dict[str, Sequence[float]], query_ids, doc_index):
    ids = np.asarray([[doc_index[d] for d in run[q]] for q in query_ids])
    sc = np.asarray([scores[q] for q in query_ids], np.float64)
    return ids, sc


def _mrr(ids: np.ndarray, query_ids, doc_ids, qrels) -> float:
    from repro.core import metrics as metrics_lib
    run = {q: [doc_ids[j] for j in row[:10]]
           for q, row in zip(query_ids, ids.tolist())}
    return metrics_lib.compute_metrics(run, qrels, ["MRR@10"])["MRR@10"]


def _validation_suite(s: Setup, mesh=None):
    """The validation suite ``repro.launch.train.run`` builds."""
    from repro.core.suite import (ValidationConfig, ValidationSuite,
                                  ValidationTask)
    vcfg = ValidationConfig(metrics=("MRR@10", "Recall@100"), k=s.sizes.k,
                            batch_size=s.sizes.batch, mesh=mesh)
    return ValidationSuite(s.spec, [ValidationTask(
        "default", s.ds.corpus, s.ds.queries, s.ds.qrels)], vcfg)


def phase_reference(s: Setup):
    import jax

    from repro.core.metrics import read_trec_run

    step, params = _last_params(s)
    ref = reference_scores(s, step, params)
    trec = read_trec_run(os.path.join(s.workdir, "runs",
                                      f"asyncval_step{step}.trec"))
    doc_index = {d: i for i, d in enumerate(ref.doc_ids)}
    run = {q: [d for d, _ in trec[q]] for q in ref.query_ids}
    sc = {q: [x for _, x in trec[q]] for q in ref.query_ids}
    ids, scores = _run_arrays(run, sc, ref.query_ids, doc_index)
    stats = compare_topk(ids, scores, ref.scores, SCORE_TOL)
    # the same engine at the reference's precision isolates the data path
    # (staging, fused encode -> score -> merge) from bfloat16 rounding
    with jax.default_matmul_precision("highest"):
        run_h, sc_h, _ = _validation_suite(s).engine("default").run(params)
    ids_h, scores_h = _run_arrays(run_h, sc_h, ref.query_ids, doc_index)
    stats_h = compare_topk(ids_h, scores_h, ref.scores, SCORE_TOL_HIGHEST)
    k = ids.shape[1]
    ref_ids = np.argsort(-ref.scores, axis=1, kind="stable")[:, :k]
    mrr_sys = _mrr(ids, ref.query_ids, ref.doc_ids, s.ds.qrels)
    mrr_ref = _mrr(ref_ids, ref.query_ids, ref.doc_ids, s.ds.qrels)
    check(abs(mrr_sys - mrr_ref) <= MRR_ATOL,
          f"MRR@10 validator {mrr_sys} vs reference {mrr_ref}")
    report = {"step": step, "score_tol": SCORE_TOL,
              "score_tol_highest": SCORE_TOL_HIGHEST, "mrr_atol": MRR_ATOL,
              "validator_vs_reference": stats,
              "validator_highest_vs_reference": stats_h,
              "mrr10_validator": mrr_sys, "mrr10_reference": mrr_ref}
    return (ref, params), report


# ---------------------------------------------------------------------------
# phase 5: serve the last checkpoint
# ---------------------------------------------------------------------------


def phase_serve(s: Setup, ref: Reference, params):
    from repro.core.suite import ValidationConfig
    from repro.serve import IndexBuilder, QueryService, ServeConfig

    z = s.sizes
    cfg = ServeConfig(scoring=ValidationConfig(k=z.serve_k,
                                               batch_size=z.batch))
    index = IndexBuilder(s.spec, s.ds.corpus, cfg).build(params, ref.step)
    service = QueryService(s.spec, k=z.serve_k, max_batch=cfg.max_batch)
    service.install(index)
    qids = ref.query_ids[:z.serve_queries]
    resp = service.answer([(q, s.ds.queries[q]) for q in qids])
    check([r.qid for r in resp] == qids and
          all(r.step == ref.step for r in resp),
          "responses do not match the queries or the checkpoint")
    doc_index = {d: i for i, d in enumerate(ref.doc_ids)}
    ids, scores = _run_arrays({r.qid: r.doc_ids for r in resp},
                              {r.qid: r.scores for r in resp}, qids,
                              doc_index)
    rows = [ref.query_ids.index(q) for q in qids]
    stats = compare_topk(ids, scores, ref.scores[rows], SCORE_TOL)
    lat = sorted(r.latency_s for r in resp)
    report = {"step": ref.step, "answered": len(resp),
              "server_vs_reference": stats,
              "latency_p50_s": lat[len(lat) // 2]}
    return None, report


# ---------------------------------------------------------------------------
# --four-chips: the sharded streaming stage against the single-chip stage
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def placement_probe():
    """Record the devices of the trainer's params after ``Trainer.run``
    and of each carry a retrieval stage starts (observation only)."""
    from repro.core import engine
    from repro.train import trainer

    def devices(tree) -> List[int]:
        import jax
        return sorted({d.id for x in jax.tree_util.tree_leaves(tree)
                       for d in x.devices()})

    seen: Dict[str, List[int]] = {}
    run0, init0 = trainer.Trainer.run, engine.StreamTopKStage.init

    def run(self, *a, **kw):
        out = run0(self, *a, **kw)
        seen["trainer_params"] = devices(self.params)
        return out

    def init(self, q_emb):
        carry = init0(self, q_emb)
        seen["validator_carry"] = devices(carry)
        return carry

    trainer.Trainer.run, engine.StreamTopKStage.init = run, init
    try:
        yield seen
    finally:
        trainer.Trainer.run, engine.StreamTopKStage.init = run0, init0


def _pairwise(run_a, sc_a, run_b, sc_b, query_ids, tol: float) -> Dict:
    """Two systems' top-k: same ids at every rank except inside a band of
    near-tied scores, and scores within ``tol`` rank by rank."""
    mism, worst = 0, 0.0
    for q in query_ids:
        a, b = np.asarray(sc_a[q]), np.asarray(sc_b[q])
        worst = max(worst, float(np.abs(a - b).max() / tol))
        check(np.abs(a - b).max() <= tol, f"query {q}: scores differ")
        for j, (da, db) in enumerate(zip(run_a[q], run_b[q])):
            if da == db:
                continue
            mism += 1
            pos = run_b[q].index(da) if da in run_b[q] else None
            near = (b[pos] if pos is not None else b[-1])
            check(abs(a[j] - near) <= 2 * tol,
                  f"query {q} rank {j}: {da} vs {db} not a near-tie")
    return {"rank_mismatches": mism, "max_score_err_over_tol": worst,
            "tol": tol}


def phase_four_chips(s: Setup, n_devices: int = 4):
    import jax

    from repro.core.engine import (ShardedStreamTopKStage, encode_store,
                                   plan_schedule, staged_batches)
    from repro.launch import train
    from repro.launch.mesh import make_validator_mesh

    check(jax.device_count() >= n_devices,
          f"{jax.device_count()} devices, need {n_devices}")
    with placement_probe() as placement:
        results = train.run(train.parse_args(_train_argv(s)))
    check(not results["errors"], f"validator errors: {results['errors']}")
    step, params = _last_params(s)

    mesh = make_validator_mesh(n_devices)
    engines = {"sharded": _validation_suite(s, mesh).engine("default"),
               "single": _validation_suite(s).engine("default")}
    eng = engines["sharded"]
    check(isinstance(eng.stage, ShardedStreamTopKStage),
          f"mesh validation used {type(eng.stage).__name__}")
    toks, _ = next(staged_batches(
        eng.doc_store, plan_schedule(eng.doc_store.n_chunks, 1),
        sharding=eng.stage.input_sharding))
    q_emb = encode_store(s.spec.encode_query, params, eng.query_store,
                         mesh=mesh, axis_names=mesh.axis_names)
    spans = {"staged_chunk": len(toks.sharding.device_set),
             "query_embeddings": len(q_emb.sharding.device_set)}
    check(all(v == n_devices for v in spans.values()),
          f"sharded arrays span {spans}, expected {n_devices}")
    # at DEFAULT precision the untrained encoder's top-100 spans less than
    # 2 SCORE_TOL, so only HIGHEST, with its 2^-10 band, pins the ids
    stats = {}
    for prec, tol in (("default", SCORE_TOL),
                      ("highest", SCORE_TOL_HIGHEST)):
        with (contextlib.nullcontext() if prec == "default"
              else jax.default_matmul_precision(prec)):
            runs = {n: e.run(params)[:2] for n, e in engines.items()}
        stats[prec] = _pairwise(*runs["sharded"], *runs["single"],
                                list(s.ds.queries), tol)
    report = {"sizes": dataclasses.asdict(s.sizes), "step": step,
              "mesh": dict(mesh.shape), "device_span": spans,
              "sharded_vs_single": stats, "placement": placement}
    return None, report


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python chip_smoke.py")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded-validation phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        device = device_info("tpu")
    except (SmokeFailure, RuntimeError) as e:
        print(f"[chip_smoke] {e}", file=sys.stderr)
        return 1
    print(json.dumps({"phase": "device", **device}), flush=True)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"[chip_smoke] the repro package is not beside this script: "
              f"{e}", file=sys.stderr)
        return 1
    print(json.dumps({"phase": "compile_cache",
                      "dir": enable_compile_cache()}), flush=True)
    shutil.rmtree(WORKDIR, ignore_errors=True)
    try:
        with CompileClock() as clock:
            if args.four_chips:
                s = make_setup(FOUR_CHIPS, args.seed, WORKDIR)
                run_phase("four_chips", device, clock, phase_four_chips, s)
            else:
                s = make_setup(CHIP, args.seed, WORKDIR)
                run_phase("train", device, clock, phase_train, s)
                run_phase("cli", device, clock, phase_cli, s)
                ref, params = run_phase("reference", device, clock,
                                        phase_reference, s)
                run_phase("serve", device, clock, phase_serve, s, ref,
                          params)
    except SmokeFailure as e:
        print(f"[chip_smoke] FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(WORKDIR, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
