"""IR evaluation metrics for checkpoint validation (paper §3 ``--metrics``).

A *run* is ``{qid: [docid, ...]}`` (rank order); *qrels* is
``{qid: {docid: gain}}`` (TREC format, gain >= 1 means relevant).  A run is a
plain dict, or a :class:`RankedRun`: the streaming engine's ``(Q, k)`` row
array read as that dict, whose metrics :func:`compute_metrics` takes from the
array itself.

Supported metric strings (paper default is MRR@10 on MS MARCO):
  MRR@k, Recall@k, nDCG@k, Success@k, AverageRank (the DPR §2 strategy:
  mean rank of the first gold within the candidate pool; lower = better).
"""

from __future__ import annotations

import bisect
import math
import re
from collections.abc import Mapping
from functools import cached_property
from typing import Dict, List, Optional, Sequence

import numpy as np

Run = Mapping[str, List[str]]
Qrels = Dict[str, Dict[str, int]]

_METRIC_RE = re.compile(r"^(MRR|Recall|nDCG|Success)@(\d+)$|^(AverageRank)$")


def parse_metric(name: str):
    m = _METRIC_RE.match(name)
    if not m:
        raise ValueError(f"unknown metric {name!r}")
    if m.group(3):
        return ("AverageRank", None)
    return (m.group(1), int(m.group(2)))


def _relevant(qrels: Qrels, qid: str) -> set:
    return {d for d, g in qrels.get(qid, {}).items() if g > 0}


def mrr_at_k(run: Run, qrels: Qrels, k: int) -> float:
    total, n = 0.0, 0
    for qid, docs in run.items():
        rel = _relevant(qrels, qid)
        if not rel:
            continue
        n += 1
        for rank, d in enumerate(docs[:k], start=1):
            if d in rel:
                total += 1.0 / rank
                break
    return total / max(n, 1)


def recall_at_k(run: Run, qrels: Qrels, k: int) -> float:
    total, n = 0.0, 0
    for qid, docs in run.items():
        rel = _relevant(qrels, qid)
        if not rel:
            continue
        n += 1
        total += len(rel.intersection(docs[:k])) / len(rel)
    return total / max(n, 1)


def success_at_k(run: Run, qrels: Qrels, k: int) -> float:
    total, n = 0.0, 0
    for qid, docs in run.items():
        rel = _relevant(qrels, qid)
        if not rel:
            continue
        n += 1
        total += 1.0 if rel.intersection(docs[:k]) else 0.0
    return total / max(n, 1)


def ndcg_at_k(run: Run, qrels: Qrels, k: int) -> float:
    total, n = 0.0, 0
    for qid, docs in run.items():
        gains = qrels.get(qid, {})
        if not any(g > 0 for g in gains.values()):
            continue
        n += 1
        dcg = sum((2 ** gains.get(d, 0) - 1) / math.log2(r + 1)
                  for r, d in enumerate(docs[:k], start=1))
        ideal = sorted((g for g in gains.values() if g > 0), reverse=True)[:k]
        idcg = sum((2 ** g - 1) / math.log2(r + 1)
                   for r, g in enumerate(ideal, start=1))
        total += dcg / idcg if idcg > 0 else 0.0
    return total / max(n, 1)


def average_rank(run: Run, qrels: Qrels) -> float:
    """DPR-style: mean rank (1-based) of the first relevant doc; queries whose
    gold is absent from the candidate list count as rank len(list)+1."""
    total, n = 0.0, 0
    for qid, docs in run.items():
        rel = _relevant(qrels, qid)
        if not rel:
            continue
        n += 1
        rank = len(docs) + 1
        for r, d in enumerate(docs, start=1):
            if d in rel:
                rank = r
                break
        total += rank
    return total / max(n, 1)


# ---------------------------------------------------------------------------
# runs held as (Q, k) row arrays
# ---------------------------------------------------------------------------


class RunIds:
    """The query and passage ids behind a stage's ``(Q, k)`` rows, with the
    lookups its views and the array metrics need.  Each is built on first
    use and kept as long as this object is (one per stage)."""

    def __init__(self, query_ids: Sequence[str], doc_ids: Sequence[str]):
        self.query_ids = query_ids
        self.doc_ids = doc_ids
        self._relevance = None

    @cached_property
    def row_of(self) -> Dict[str, int]:
        """qid -> its row, in query order.  A repeated qid keeps its first
        place and its last row, as filling a dict query by query does."""
        return {q: i for i, q in enumerate(self.query_ids)}

    @cached_property
    def doc_row(self) -> Optional[Dict[str, int]]:
        """doc id -> its row; None where an id repeats."""
        rows = {d: i for i, d in enumerate(self.doc_ids)}
        return rows if len(rows) == len(self.doc_ids) else None

    def relevance(self, qrels: Qrels) -> "_Relevance":
        """The relevance index of ``qrels`` over these ids (unique doc ids
        only), built once per qrels object: qrels are not edited between
        the verdicts that share them."""
        if self._relevance is None or self._relevance[0] is not qrels:
            self._relevance = (qrels, _Relevance(self, qrels))
        return self._relevance[1]


class _Rows(Mapping):
    """``{qid: list}`` over the rows of a ``(Q, k)`` host array.  A query's
    list is built when it is first read and kept, so it is one object on
    every read, as a dict's value is."""

    def __init__(self, array: np.ndarray, ids: RunIds):
        self.array = array
        self.ids = ids
        self.built: Dict[str, list] = {}

    def _list(self, i: int) -> list:
        return self.array[i].tolist()

    def __getitem__(self, qid: str) -> list:
        got = self.built.get(qid)
        if got is None:
            got = self.built[qid] = self._list(self.ids.row_of[qid])
        return got

    def __contains__(self, qid) -> bool:
        return qid in self.ids.row_of

    def __iter__(self):
        return iter(self.ids.row_of)

    def __len__(self) -> int:
        return len(self.ids.row_of)

    def __repr__(self) -> str:
        return f"{type(self).__name__}({dict(self)!r})"


class RankedRun(_Rows):
    """A run as the ``(Q, k)`` passage rows of a top-k carry: ``run[qid]``
    is the query's doc ids in rank order, as a run dict holds them."""

    def _list(self, i: int) -> List[str]:
        doc_ids = self.ids.doc_ids
        return [doc_ids[j] for j in self.array[i].tolist()]

    def rows_index_corpus(self) -> bool:
        """Whether the metrics can be read from the rows alone: no list has
        been handed out (and perhaps edited), every doc id is unique and
        every row names a passage."""
        a = self.array
        return not self.built and self.ids.doc_row is not None and (
            a.size == 0 or (int(a.min()) >= 0
                            and int(a.max()) < len(self.ids.doc_ids)))


class RankedScores(_Rows):
    """The scores beside a :class:`RankedRun`: ``scores[qid]`` is the
    query's float32 row as Python floats."""


class _Relevance:
    """Where the judged passages of one qrels lie among a stage's rows.

    ``judged`` holds, in run order, each query that has a relevant id (gain
    > 0, in the corpus or not): its row, its count of relevant ids and its
    gains.  A *pair* is one (query row, passage row) of a judged passage of
    the corpus with a non-zero gain; passages with gain 0 add nothing to
    any metric."""

    def __init__(self, ids: RunIds, qrels: Qrels):
        doc_row = ids.doc_row
        self.judged = []
        pair_q, pair_row, self.gains = [], [], []
        for qid, i in ids.row_of.items():
            gains = qrels.get(qid, {})
            n_rel = sum(1 for g in gains.values() if g > 0)
            if not n_rel:
                continue
            self.judged.append((i, n_rel, gains))
            for d, g in gains.items():
                if g != 0 and d in doc_row:
                    pair_q.append(i)
                    pair_row.append(doc_row[d])
                    self.gains.append(g)
        self.pair_q = np.asarray(pair_q, np.int64)
        self.pair_row = np.asarray(pair_row, np.int64)
        self.relevant = np.asarray([g > 0 for g in self.gains], bool)

    def places(self, rows: np.ndarray):
        """``(pair, position)`` of every place a pair's passage takes in its
        query's row of ``rows``, ordered by pair, then rank."""
        eq = rows[self.pair_q] == self.pair_row.astype(rows.dtype)[:, None]
        return np.divmod(np.flatnonzero(eq), max(rows.shape[1], 1))


def _by_query(q: np.ndarray, at: np.ndarray, *cols) -> Dict[int, list]:
    """query row -> its entries in rank order: ``at``, or ``(at, *cols)``."""
    order = np.lexsort((at, q))
    out: Dict[int, list] = {}
    rows = zip(q[order].tolist(), at[order].tolist(),
               *(c[order].tolist() for c in cols))
    for i, a, *rest in rows:
        out.setdefault(i, []).append((a, *rest) if rest else a)
    return out


def _array_metrics(run: RankedRun, qrels: Qrels,
                   names: List[str]) -> Dict[str, float]:
    """:func:`compute_metrics` from the run's rows: the same per-query
    values, added in the same order, so every metric equals the list
    path's bit for bit."""
    rel = run.ids.relevance(qrels)
    depth = run.array.shape[1]
    pair, at = rel.places(run.array)
    # each relevant passage's first place, the places a list's set counts
    first = np.ones(len(pair), bool)
    first[1:] = pair[1:] != pair[:-1]
    first &= rel.relevant[pair]
    places = _by_query(rel.pair_q[pair[first]], at[first])
    out = {}
    for name in names:
        kind, k = parse_metric(name)
        total = 0.0
        if kind == "nDCG":
            gained = _by_query(rel.pair_q[pair], at, pair)
        for i, n_rel, gains in rel.judged:
            got = places.get(i, ())
            if kind == "MRR":
                if got and got[0] < k:
                    total += 1.0 / (got[0] + 1)
            elif kind == "Recall":
                total += bisect.bisect_left(got, k) / n_rel
            elif kind == "Success":
                total += 1.0 if got and got[0] < k else 0.0
            elif kind == "nDCG":
                # the list path's zero terms (passages without a gain)
                # leave sum()'s running total and compensation unchanged
                dcg = sum((2 ** rel.gains[p] - 1) / math.log2(a + 2)
                          for a, p in gained.get(i, ()) if a < k)
                ideal = sorted((g for g in gains.values() if g > 0),
                               reverse=True)[:k]
                idcg = sum((2 ** g - 1) / math.log2(r + 1)
                           for r, g in enumerate(ideal, start=1))
                total += dcg / idcg if idcg > 0 else 0.0
            else:
                total += got[0] + 1 if got else depth + 1
        out[name] = total / max(len(rel.judged), 1)
    return out


def compute_metrics(run: Run, qrels: Qrels, names: List[str],
                    telemetry=None) -> Dict[str, float]:
    """Each metric of ``names`` over ``run``.  A :class:`RankedRun` over
    unique doc ids is read from its rows; any other run, query by query
    from its lists.  With ``telemetry``, the path taken is counted once
    per call (``metrics.array_path``, ``metrics.list_path``)."""
    array = isinstance(run, RankedRun) and run.rows_index_corpus()
    if telemetry is not None:
        telemetry.metrics.counter("metrics.array_path" if array
                                  else "metrics.list_path").inc()
    if array:
        return _array_metrics(run, qrels, names)
    out = {}
    for name in names:
        kind, k = parse_metric(name)
        if kind == "MRR":
            out[name] = mrr_at_k(run, qrels, k)
        elif kind == "Recall":
            out[name] = recall_at_k(run, qrels, k)
        elif kind == "nDCG":
            out[name] = ndcg_at_k(run, qrels, k)
        elif kind == "Success":
            out[name] = success_at_k(run, qrels, k)
        else:
            out[name] = average_rank(run, qrels)
    return out


def write_trec_run(path: str, run: Run, scores=None, tag: str = "asyncval"):
    """TREC 6-column run file (paper's --write_run)."""
    with open(path, "w") as f:
        for qid, docs in run.items():
            for rank, d in enumerate(docs, start=1):
                s = scores[qid][rank - 1] if scores else 1.0 / rank
                f.write(f"{qid} Q0 {d} {rank} {s:.6f} {tag}\n")


def read_trec_run(path: str) -> Dict[str, List[tuple]]:
    """Returns {qid: [(docid, score) ...]} sorted by score desc."""
    runs: Dict[str, list] = {}
    with open(path) as f:
        for line in f:
            parts = line.split()
            if len(parts) < 6:
                continue
            qid, _, did, _, score = parts[:5]
            runs.setdefault(qid, []).append((did, float(score)))
    return {q: sorted(v, key=lambda x: -x[1]) for q, v in runs.items()}


def read_trec_qrels(path: str) -> Qrels:
    qrels: Qrels = {}
    with open(path) as f:
        for line in f:
            parts = line.split()
            if len(parts) < 4:
                continue
            qid, _, did, gain = parts[:4]
            qrels.setdefault(qid, {})[did] = int(gain)
    return qrels
