"""A run with the timed path broken underneath comes out as not correct.
The harness's look for a chip is skipped; everything else runs as on the
chip, at the test cell's CPU size.  One test per fault a one-chip retrieval
cell can have (there is no exchange between chips to leave out)."""

import jax.numpy as jnp

from bench.tests import tiny
from repro.core import engine, suite


def _failed(out, number):
    assert not out.line["correct"], out.checks
    assert not out.checks[number]["ok"], out.checks


def test_step_returns_its_state_unchanged(monkeypatch):
    """Every other dispatch of the fused step keeps the carry as it was."""
    real = engine.StreamTopKStage.step_window
    calls = {"n": 0}

    def stale(self, params, q_emb, carry, *a):
        calls["n"] += 1
        return carry if calls["n"] % 2 else real(self, params, q_emb,
                                                 carry, *a)
    monkeypatch.setattr(engine.StreamTopKStage, "step_window", stale)
    _failed(tiny.run(31), "topk_gap")


def test_half_of_each_chunk_left_out(monkeypatch):
    """The fused step scores only the first half of each chunk's rows."""
    real = engine.StreamTopKStage.step_window

    def half(self, params, q_emb, carry, toks_w, mask_w, bases, n_valids):
        return real(self, params, q_emb, carry, toks_w, mask_w, bases,
                    jnp.asarray(n_valids) // 2)
    monkeypatch.setattr(engine.StreamTopKStage, "step_window", half)
    _failed(tiny.run(32), "topk_gap")


def test_an_answer_altered_where_it_is_produced(monkeypatch):
    """finalize maps one rank of every query to the next passage's id."""
    real = engine.StreamTopKStage.finalize

    def shifted(self, carry):
        run, scores = real(self, carry)
        for q, docs in run.items():
            j = self.doc_ids.index(docs[-1])
            docs[-1] = self.doc_ids[(j + 1) % len(self.doc_ids)]
        return run, scores
    monkeypatch.setattr(engine.StreamTopKStage, "finalize", shifted)
    _failed(tiny.run(33), "score_err")


def test_a_score_altered_where_it_is_produced(monkeypatch):
    real = engine.StreamTopKStage.finalize

    def nudged(self, carry):
        run, scores = real(self, carry)
        for q in scores:
            scores[q][0] += 0.05
        return run, scores
    monkeypatch.setattr(engine.StreamTopKStage, "finalize", nudged)
    _failed(tiny.run(34), "score_err")


def test_a_metric_altered_where_it_is_produced(monkeypatch):
    real = suite.metrics_lib.compute_metrics

    def off(run, qrels, names):
        return {m: v + 1e-9 for m, v in real(run, qrels, names).items()}
    monkeypatch.setattr(suite.metrics_lib, "compute_metrics", off)
    _failed(tiny.run(35), "metric_err")
