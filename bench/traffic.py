"""Traffic generator: one synthetic retrieval validation set per seed.

A mix file (``bench/mixes/<name>.json``) gives the parameters; the cell file
(``bench/cells/<name>.json``) gives the corpus size.  Everything is drawn from
``--seed`` with numpy, in bulk.

The model is the topic model of ``repro.data.corpus.synthetic_retrieval_dataset``
(topic blocks of the vocabulary, plus a common range), adapted to
MS MARCO-like shapes: lengths drawn log-normal and clipped, WordPiece-range
ids, ``[CLS] ... [SEP]`` framing, and queries that take part of their tokens
from their one gold passage (MS MARCO queries share words with their answer
passage).  It is a copy, not an import: the benchmark's inputs do not change
when the program's data module does.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np


@dataclasses.dataclass
class Traffic:
    """One validation set: ``corpus``/``queries`` are id -> token list, and
    ``qrels`` maps each query to its one gold passage."""

    corpus: Dict[str, List[int]]
    queries: Dict[str, List[int]]
    qrels: Dict[str, Dict[str, int]]
    p_lens: np.ndarray          # (N,) real tokens per passage, framing included
    q_lens: np.ndarray          # (Q,) real tokens per query
    gold: np.ndarray            # (Q,) corpus row of each query's gold passage


def _lengths(rng, n: int, spec: dict) -> np.ndarray:
    """``n`` lengths, log-normal with the given mean, clipped to [min, max]."""
    sigma = float(spec["sigma"])
    mu = np.log(float(spec["mean"])) - 0.5 * sigma ** 2
    raw = np.rint(rng.lognormal(mu, sigma, n))
    return np.clip(raw, spec["min"], spec["max"]).astype(np.int64)


def generate(mix: dict, n_passages: int, seed: int) -> Traffic:
    """The validation set of ``mix`` with ``n_passages`` passages."""
    rng = np.random.default_rng(seed)
    v = mix["vocab"]
    n_topics, block = int(v["topics"]), int(v["topic_block"])
    common_lo = int(v["lo"])
    topic_lo = int(v["hi"]) - n_topics * block
    if topic_lo <= common_lo:
        raise ValueError("vocab range too small for its topic blocks")
    n_q = int(mix["queries"])
    p_len = _lengths(rng, n_passages, mix["passage_tokens"])
    q_len = _lengths(rng, n_q, mix["query_tokens"])
    cls, sep = int(v["cls"]), int(v["sep"])

    def bodies(topic, lens, share):
        """Token rows (n, max(lens)-2) for texts of ``topic``: each body token
        is from the topic's block with probability ``share``, else common."""
        width = int(lens.max()) - 2
        n = len(lens)
        from_topic = rng.random((n, width)) < share
        tok_topic = topic_lo + topic[:, None] * block + rng.integers(
            0, block, (n, width))
        tok_common = rng.integers(common_lo, topic_lo, (n, width))
        return np.where(from_topic, tok_topic, tok_common)

    p_topic = rng.integers(0, n_topics, n_passages)
    p_body = bodies(p_topic, p_len, float(v["topic_share_passage"]))

    # every query's gold passage is a random corpus row; the query is about
    # the gold passage's topic and copies some of the gold passage's tokens
    gold = rng.integers(0, n_passages, n_q)
    q_topic = p_topic[gold]
    q_body = bodies(q_topic, q_len, float(v["topic_share_query"]))
    copy = rng.random(q_body.shape) < float(v["gold_share_query"])
    src = rng.integers(0, 1 << 30, q_body.shape) % (p_len[gold] - 2)[:, None]
    q_body = np.where(copy, p_body[gold[:, None], src], q_body)

    def texts(prefix, body, lens):
        return {f"{prefix}{i}": [cls] + body[i, :lens[i] - 2].tolist() + [sep]
                for i in range(len(lens))}

    corpus = texts("d", p_body, p_len)
    queries = texts("q", q_body, q_len)
    qrels = {f"q{i}": {f"d{int(g)}": 1} for i, g in enumerate(gold)}
    return Traffic(corpus=corpus, queries=queries, qrels=qrels, p_lens=p_len,
                   q_lens=q_len, gold=gold.astype(np.int64))
