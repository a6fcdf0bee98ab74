"""The control, the reference computed in float8 and put in the program's
place, comes out as not correct; the program itself comes out correct.  At
the CPU size of the test cell, whose limits were set from readings at that
size (PERF.md)."""

import pytest

from bench import check, harness
from bench.tests import tiny


@pytest.mark.parametrize("seed", [21, 2 ** 31 + 5])
def test_program_correct_control_not(seed):
    out = tiny.run(seed)
    assert out.line["correct"], out.checks
    cell = tiny.cell()
    tr = harness.traffic.generate(cell.mix, cell.sizes["corpus"], seed)
    params = cell.reference.init(harness.seed_key(seed, 1), cell.config)
    ref = harness.reference_for(cell, tr, 0, seed)
    q, p = harness.reference_embeddings(cell, tr, params, "fp8")
    control = check.control_answers(
        q, p, harness.check_sample(cell.mix, len(tr.q_lens), seed),
        cell.mix["k"])
    verdict = check.judge(check.readings([control], ref),
                          cell.sizes["limits"])
    assert not all(c["ok"] for c in verdict.values()), verdict
    assert not verdict["score_err"]["ok"]
