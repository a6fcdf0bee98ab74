"""The comparison that decides ``correct``.

A verdict is a top-k list with scores for every query and the metrics
computed from those lists.  The reference encodes every query and every
passage of the same traffic with the configuration's plain reference in
float32 at HIGHEST matmul precision, on the device, in blocks of rows of one
length bucket, and scores exactly.  Scores are cosines (both encoders
L2-normalize), so every gap below is absolute.

Numbers compared, each against the limit that ``bench/cells/<cell>.json``
gives it:

* ``score_err``   -- the largest gap between a returned score and the
  reference score of the passage returned with it, over the checked queries
  and all k ranks.  Covers the query and passage encode, the scoring, and
  the id mapping of finalize: a wrong id carries another passage's score.
* ``topk_gap``    -- the widest gap by which a returned passage's reference
  score lies below the reference's own k-th best score.  Covers the merge:
  a dropped chunk or a stale carry returns passages the reference ranks
  lower.
* ``metric_err``  -- the gap between the verdict's metric and the same metric
  recomputed here from the verdict's own top-k lists, over all queries
  (exact: limit 0).  The lists themselves are held to the reference by the
  two numbers above.
* ``bad_answers`` -- checked queries whose list is not k distinct passages
  of the corpus (exact: limit 0).
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
_METRIC = re.compile(r"^(MRR|Recall)@(\d+)$")


# ---------------------------------------------------------------------------
# reference embeddings, in length buckets
# ---------------------------------------------------------------------------


def _buckets(lens: np.ndarray, max_len: int, step: int = 32):
    """Padded length of each text: its length rounded up to ``step``."""
    return np.minimum(max_len, -(-lens // step) * step)


def encode_all(ref, cfg: dict, params, texts: Sequence[List[int]],
               lens: np.ndarray, max_len: int, precision: str,
               tokens_per_block: int = 32768) -> jnp.ndarray:
    """Embeddings (n, D) on the device, in the order of ``texts``.

    Texts are sorted by length and encoded in blocks of one padded length
    (a multiple of 32), ``tokens_per_block`` tokens a block; the last block
    of a length is padded with empty rows so that each length compiles
    once."""
    lens = np.asarray(lens)
    padded = _buckets(lens, max_len)
    order = np.argsort(padded, kind="stable")
    outs = []
    for L in np.unique(padded):
        rows = order[padded[order] == L]
        per = max(1, tokens_per_block // int(L))
        for lo in range(0, len(rows), per):
            part = rows[lo:lo + per]
            tok = np.zeros((per, int(L)), np.int32)
            for j, r in enumerate(part):
                t = texts[r][:int(L)]
                tok[j, :len(t)] = t
            mask = np.arange(int(L))[None, :] < np.minimum(
                np.pad(lens[part], (0, per - len(part)), constant_values=1),
                int(L))[:, None]
            outs.append(ref.encode(params, cfg, tok, mask, precision)
                        [:len(part)])
    emb = jnp.concatenate(outs, axis=0)
    inverse = np.empty_like(order)
    inverse[order] = np.arange(len(order))
    return jnp.take(emb, jnp.asarray(inverse), axis=0)


@jax.jit
def _scores(q, p):
    return jnp.matmul(q, p.T, precision=HIGHEST)


def gold_ranks(q_emb, p_emb, gold: np.ndarray, block: int = 1024) -> np.ndarray:
    """Rank (0 = best) of each query's gold passage among all passages."""
    out = []
    for lo in range(0, q_emb.shape[0], block):
        s = _scores(q_emb[lo:lo + block], p_emb)
        g = jnp.asarray(gold[lo:lo + block])
        gs = jnp.take_along_axis(s, g[:, None], axis=1)
        out.append(np.asarray(jnp.sum(s > gs, axis=1)))
    return np.concatenate(out)


def metrics_from_ranks(ranks: np.ndarray, names: Sequence[str]
                       ) -> Dict[str, float]:
    """MRR@n and Recall@n of one gold passage per query, from its rank."""
    out = {}
    for name in names:
        m = _METRIC.match(name)
        if not m:
            raise ValueError(f"the reference computes MRR@n and Recall@n, "
                             f"not {name!r}")
        n = int(m.group(2))
        hit = ranks < n
        out[name] = float(np.mean(np.where(hit, 1.0 / (ranks + 1.0), 0.0))
                          if m.group(1) == "MRR" else np.mean(hit))
    return out


def own_metrics(run: Dict[str, List[str]], qrels: Dict[str, Dict[str, int]],
                names: Sequence[str]) -> Dict[str, float]:
    """A verdict's metrics recomputed from its own top-k lists."""
    out = {}
    for name in names:
        kind, n = _METRIC.match(name).groups()
        n = int(n)
        total, count = 0.0, 0
        for qid, docs in run.items():
            rel = {d for d, g in qrels.get(qid, {}).items() if g > 0}
            if not rel:
                continue
            count += 1
            if kind == "MRR":
                for rank, d in enumerate(docs[:n], start=1):
                    if d in rel:
                        total += 1.0 / rank
                        break
            else:
                total += len(rel.intersection(docs[:n])) / len(rel)
        out[name] = total / max(count, 1)
    return out


# ---------------------------------------------------------------------------
# readings
# ---------------------------------------------------------------------------


class Reference:
    """Exact scores of the checked queries against the whole corpus, and
    the reference's k-th best score for each."""

    def __init__(self, q_emb, p_emb, check_rows: np.ndarray, k: int):
        self.scores = _scores(q_emb[jnp.asarray(np.asarray(check_rows))],
                              p_emb)
        top = jax.lax.top_k(self.scores, k)[0]
        self.kth = np.asarray(top[:, -1], np.float64)

    def pair_scores(self, ids: np.ndarray) -> np.ndarray:
        """Reference score of each returned passage, (S, k)."""
        return np.asarray(jnp.take_along_axis(
            self.scores, jnp.asarray(ids, jnp.int32), axis=1), np.float64)


def control_answers(q_emb, p_emb, check_rows, k: int) -> dict:
    """A verdict made by embeddings in the program's place: the top-k lists
    of the checked queries and their scores (its metrics are those of its
    own lists)."""
    s = _scores(q_emb[jnp.asarray(np.asarray(check_rows))], p_emb)
    top_s, top_i = jax.lax.top_k(s, k)
    return {"ids": np.asarray(top_i, np.int64),
            "scores": np.asarray(top_s, np.float64), "metrics": {},
            "own_metrics": {}, "bad": 0}


def answers_of(run, scores, metrics, check_qids: Sequence[str],
               doc_row: Dict[str, int], n_docs: int, k: int, qrels,
               metric_names) -> dict:
    """The checked part of one verdict, from its top-k lists and scores."""
    ids = np.zeros((len(check_qids), k), np.int64)
    sc = np.zeros((len(check_qids), k), np.float64)
    bad = 0
    for i, q in enumerate(check_qids):
        docs, s = run.get(q, []), scores.get(q, [])
        rows = [doc_row.get(d, -1) for d in docs]
        if (len(rows) != k or len(s) != k or len(set(rows)) != k
                or min(rows, default=-1) < 0 or max(rows, default=n_docs)
                >= n_docs):
            bad += 1
            continue
        ids[i], sc[i] = rows, s
    return {"ids": ids, "scores": sc, "metrics": dict(metrics),
            "own_metrics": own_metrics(run, qrels, metric_names), "bad": bad}


def readings(verdicts: Sequence[dict], ref: Reference) -> Dict[str, float]:
    """The compared numbers over ``verdicts`` (each from ``answers_of`` or
    ``control_answers``), largest over the verdicts."""
    out = {"score_err": 0.0, "topk_gap": 0.0, "metric_err": 0.0,
           "bad_answers": 0.0}
    for v in verdicts:
        pair = ref.pair_scores(v["ids"])
        out["score_err"] = max(out["score_err"],
                               float(np.abs(v["scores"] - pair).max()))
        out["topk_gap"] = max(out["topk_gap"], float(
            np.max(ref.kth - pair.min(axis=1)).clip(0.0)))
        out["bad_answers"] += v["bad"]
        for m in v["metrics"]:
            out["metric_err"] = max(out["metric_err"], abs(
                v["metrics"][m] - v["own_metrics"][m]))
    return out


def judge(values: Dict[str, float], limits: Optional[Dict[str, float]]
          ) -> Dict[str, dict]:
    """Each number beside its limit; a number without a limit fails."""
    limits = limits or {}
    return {name: {"value": v, "limit": limits.get(name),
                   "ok": name in limits and v <= limits[name]}
            for name, v in values.items()}
