"""IR metrics + fidelity statistics: hand-computed cases and properties."""

import math

import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core import fidelity
from repro.core import metrics as M

QRELS = {"q1": {"d1": 1}, "q2": {"d9": 1, "d5": 2}, "q3": {"d7": 1}}
RUN = {"q1": ["d3", "d1", "d2"],          # gold at rank 2
       "q2": ["d5", "d2", "d9"],          # golds at ranks 1 and 3
       "q3": ["d2", "d3", "d4"]}          # gold missing


def test_mrr():
    # (1/2 + 1/1 + 0) / 3
    assert M.mrr_at_k(RUN, QRELS, 10) == pytest.approx((0.5 + 1.0) / 3)
    assert M.mrr_at_k(RUN, QRELS, 1) == pytest.approx(1.0 / 3)


def test_recall():
    # q1: 1/1, q2: 2/2, q3: 0/1
    assert M.recall_at_k(RUN, QRELS, 10) == pytest.approx(2 / 3)
    assert M.recall_at_k(RUN, QRELS, 1) == pytest.approx((0 + 0.5 + 0) / 3)


def test_success():
    assert M.success_at_k(RUN, QRELS, 1) == pytest.approx(1 / 3)
    assert M.success_at_k(RUN, QRELS, 3) == pytest.approx(2 / 3)


def test_ndcg():
    # q1: dcg = 1/log2(3), idcg = 1 -> 0.6309...
    q1 = (2 ** 1 - 1) / math.log2(3)
    # q2: dcg = (2^2-1)/log2(2) + (2^1-1)/log2(4) = 3 + 0.5
    #     idcg = 3/log2(2) + 1/log2(3)
    q2 = 3.5 / (3 + 1 / math.log2(3))
    assert M.ndcg_at_k(RUN, QRELS, 10) == pytest.approx((q1 + q2 + 0) / 3)


def test_average_rank():
    # q1 -> 2, q2 -> 1, q3 -> missing = len+1 = 4
    assert M.average_rank(RUN, QRELS) == pytest.approx((2 + 1 + 4) / 3)


def test_parse_metric_and_compute_all():
    out = M.compute_metrics(RUN, QRELS,
                            ["MRR@10", "Recall@3", "nDCG@10", "Success@1",
                             "AverageRank"])
    assert set(out) == {"MRR@10", "Recall@3", "nDCG@10", "Success@1",
                        "AverageRank"}
    with pytest.raises(ValueError):
        M.parse_metric("BogusMetric@5")


def test_trec_run_roundtrip(tmp_path):
    path = str(tmp_path / "run.trec")
    scores = {q: [10.0 - i for i in range(len(docs))]
              for q, docs in RUN.items()}
    M.write_trec_run(path, RUN, scores, tag="test")
    back = M.read_trec_run(path)
    for q, docs in RUN.items():
        assert [d for d, _ in back[q]] == docs


def test_trec_qrels_io(tmp_path):
    path = str(tmp_path / "qrels.txt")
    with open(path, "w") as f:
        for q, docs in QRELS.items():
            for d, g in docs.items():
                f.write(f"{q} 0 {d} {g}\n")
    assert M.read_trec_qrels(path) == QRELS


@settings(max_examples=50, deadline=None)
@given(st.lists(st.integers(0, 30), min_size=2, max_size=20, unique=True))
def test_mrr_bounded_and_monotone_in_k(ranks):
    """MRR in [0,1] and non-decreasing in k."""
    run = {"q": [f"d{i}" for i in ranks]}
    qrels = {"q": {f"d{ranks[0]}": 1}}
    vals = [M.mrr_at_k(run, qrels, k) for k in (1, 3, 5, 100)]
    assert all(0.0 <= v <= 1.0 for v in vals)
    assert vals == sorted(vals)


# ---------------------------------------------------------------------------
# runs held as (Q, k) row arrays
# ---------------------------------------------------------------------------

ALL_METRICS = ["MRR@1", "MRR@10", "Recall@1", "Recall@5", "Recall@8",
               "Recall@100", "Success@1", "Success@8", "Success@50",
               "nDCG@3", "nDCG@8", "nDCG@20", "AverageRank"]


def _tel():
    from types import SimpleNamespace

    from repro.obs.metrics import MetricsRegistry
    return SimpleNamespace(metrics=MetricsRegistry())


def _counts(tel):
    out = {}
    for path in ("array_path", "list_path"):
        counter = tel.metrics.get(f"metrics.{path}")
        out[path] = 0 if counter is None else counter.value
    return out


def _ranked_case(seed, *, unique_docs=True, repeat_rows=False):
    """A run view of 40 queries at depth 8 over 60 passages, and qrels with
    queries absent from the run and from the qrels, several relevant
    passages a query (every result judged for some), relevant ids outside
    the corpus, zero and negative gains."""
    rng = np.random.default_rng(seed)
    Q, k, N = 40, 8, 60
    qids = [f"q{i}" for i in range(Q)]
    dids = [f"d{i % 45}" if not unique_docs else f"d{i}" for i in range(N)]
    rows = np.stack([rng.choice(N, size=k, replace=repeat_rows)
                     for _ in range(Q)]).astype(np.int32)
    qrels = {}
    for i, q in enumerate(qids):
        if i % 7 == 3:
            continue                               # absent from the qrels
        judged = {}
        for d in rng.choice(N, size=int(rng.integers(1, 5)), replace=False):
            judged[dids[d]] = int(rng.choice([-1, 0, 1, 1, 2, 3]))
        if rng.random() < 0.5:                     # one of its own results
            judged[dids[rows[i, rng.integers(k)]]] = int(rng.integers(1, 4))
        if i % 5 == 1:                             # judged all through
            for j in rows[i]:
                judged[dids[j]] = int(rng.integers(-1, 4))
        if rng.random() < 0.3:
            judged[f"outside{i}"] = 1              # not in the corpus
        qrels[q] = judged
    qrels["q_not_run"] = {"d0": 1}
    ids = M.RunIds(qids, dids)
    return M.RankedRun(rows, ids), qrels


@pytest.mark.parametrize("seed", range(8))
def test_ranked_run_metrics_equal_the_list_path_bitwise(seed):
    run, qrels = _ranked_case(seed, repeat_rows=seed % 4 == 3)
    tel = _tel()
    got = M.compute_metrics(run, qrels, ALL_METRICS, telemetry=tel)
    want = M.compute_metrics(dict(run), qrels, ALL_METRICS, telemetry=tel)
    assert got == want
    assert _counts(tel) == {"array_path": 1, "list_path": 1}


def test_ranked_run_over_repeated_doc_ids_takes_the_list_path():
    run, qrels = _ranked_case(5, unique_docs=False)
    assert run.ids.doc_row is None
    tel = _tel()
    got = M.compute_metrics(run, qrels, ALL_METRICS, telemetry=tel)
    assert got == M.compute_metrics(dict(run), qrels, ALL_METRICS)
    assert _counts(tel) == {"array_path": 0, "list_path": 1}


def test_ranked_run_reads_as_the_dict_of_its_rows():
    ids = M.RunIds(["q0", "q1", "q0"], ["a", "b", "c"])
    rows = np.asarray([[2, 0], [1, 2], [0, 1]], np.int32)
    scores = np.asarray([[0.5, 0.25], [0.1, -1.0], [0.3, 0.2]], np.float32)
    run, sc = M.RankedRun(rows, ids), M.RankedScores(scores, ids)
    plain_run, plain_sc = {}, {}                  # filled query by query
    for qi, qid in enumerate(ids.query_ids):
        plain_run[qid] = [ids.doc_ids[j] for j in rows[qi]]
        plain_sc[qid] = [float(s) for s in scores[qi]]
    assert run == plain_run and plain_run == run
    assert sc == plain_sc and list(sc) == list(plain_sc) == ["q0", "q1"]
    assert len(run) == 2 and run["q0"] == ["a", "b"]
    assert run.get("zz") is None and "q1" in run and "zz" not in run
    assert list(run.items()) == list(plain_run.items())
    assert all(type(v) is float for v in sc["q1"])
    with pytest.raises(KeyError):
        run["zz"]
    assert M.compute_metrics(run, {"q0": {"a": 1}}, ["MRR@2"]) == \
        M.compute_metrics(plain_run, {"q0": {"a": 1}}, ["MRR@2"])


def test_ranked_run_once_read_takes_the_list_path():
    run, qrels = _ranked_case(1)
    first = next(iter(run))
    run[first].reverse()                 # a consumer's edit, as on a dict
    assert run[first] is run[first]
    tel = _tel()
    got = M.compute_metrics(run, qrels, ALL_METRICS, telemetry=tel)
    assert got == M.compute_metrics(dict(run), qrels, ALL_METRICS)
    assert _counts(tel) == {"array_path": 0, "list_path": 1}


def test_ranked_run_with_rows_outside_the_corpus_takes_the_list_path():
    ids = M.RunIds(["q0"], ["a", "b", "c"])
    run = M.RankedRun(np.asarray([[-1, 0]], np.int32), ids)
    assert not run.rows_index_corpus()
    tel = _tel()
    # a row of -1 names the last passage, as indexing the id list does
    assert M.compute_metrics(run, {"q0": {"c": 1}}, ["MRR@10"],
                             telemetry=tel) == {"MRR@10": 1.0}
    assert _counts(tel) == {"array_path": 0, "list_path": 1}


def test_ranked_run_relevance_index_is_built_once_per_qrels():
    run, qrels = _ranked_case(2)
    M.compute_metrics(run, qrels, ["MRR@10"])
    index = run.ids.relevance(qrels)
    M.compute_metrics(M.RankedRun(run.array[::-1].copy(), run.ids), qrels,
                      ["MRR@10"])
    assert run.ids.relevance(qrels) is index
    assert run.ids.relevance(dict(qrels)) is not index


# ---------------------------------------------------------------------------
# fidelity statistics
# ---------------------------------------------------------------------------

def test_correlations_perfect_and_inverted():
    a = [0.1, 0.2, 0.3, 0.4]
    assert fidelity.spearman(a, a) == pytest.approx(1.0)
    assert fidelity.spearman(a, a[::-1]) == pytest.approx(-1.0)
    assert fidelity.kendall_tau(a, a) == pytest.approx(1.0)
    assert fidelity.kendall_tau(a, a[::-1]) == pytest.approx(-1.0)
    assert fidelity.pearson(a, [2 * x + 1 for x in a]) == pytest.approx(1.0)


def test_best_checkpoint_agreement():
    ref = [0.1, 0.3, 0.2]
    assert fidelity.best_checkpoint_agreement(ref, [0.5, 0.9, 0.6])
    assert not fidelity.best_checkpoint_agreement(ref, [0.9, 0.5, 0.6])
    # lower-is-better (AverageRank)
    assert fidelity.best_checkpoint_agreement([3, 1, 2], [30, 10, 20],
                                              higher_is_better=False)


def test_overestimation_report():
    rep = fidelity.overestimation([0.1, 0.2], [0.15, 0.3])
    assert rep["always_overestimates"] == 1.0
    assert rep["mean_delta"] == pytest.approx(0.075)


def test_fidelity_report_keys():
    rep = fidelity.fidelity_report([0.1, 0.2, 0.3], [0.2, 0.25, 0.4])
    for k in ("pearson", "spearman", "kendall_tau", "best_ckpt_agreement",
              "mean_delta"):
        assert k in rep


@settings(max_examples=30, deadline=None)
@given(st.lists(st.floats(-100, 100), min_size=3, max_size=15),
       st.lists(st.floats(-100, 100), min_size=3, max_size=15))
def test_correlation_bounds(a, b):
    n = min(len(a), len(b))
    a, b = a[:n], b[:n]
    for fn in (fidelity.pearson, fidelity.spearman, fidelity.kendall_tau):
        v = fn(a, b)
        assert -1.0 - 1e-9 <= v <= 1.0 + 1e-9
