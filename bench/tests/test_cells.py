"""A configuration, a mix, a cell and a per-layer metric are each added by
adding files and entries alone; the harness finds them by name."""

import json
import os
import shutil
import time

import pytest

from bench import harness
from bench.cells import BENCH, ROOT, load_cell
from bench.tests import tiny


def test_every_committed_cell_loads():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.mix["name"] == w["traffic"]
        assert cell.sizes["corpus"] > 0
        assert cell.reference.encode
        assert set(cell.sizes["limits"]) == {"score_err", "topk_gap",
                                             "metric_err", "bad_answers"}
        for m in cell.per_layer:
            assert callable(cell.metric_reader(m["name"]))


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError):
        load_cell("no-such-cell")


@pytest.fixture
def added(tmp_path):
    """A benchmark with one more mix, cell and metric, as new files."""
    data, code = tmp_path / "data", tmp_path / "code"
    shutil.copytree(tiny.DATA, data)
    shutil.copytree(os.path.join(BENCH, "references"), code / "references")
    shutil.copytree(os.path.join(BENCH, "metrics"), code / "metrics")
    with open(data / "mixes" / "tiny-k20.json") as f:
        mix = json.load(f)
    mix.update(name="tiny-k5", k=5, metrics=["MRR@5"])
    (data / "mixes" / "tiny-k5.json").write_text(json.dumps(mix))
    (data / "cells" / "tiny-bert.tiny-k5.json").write_text(json.dumps(
        {"corpus": 300, "limits": {"score_err": 0.006, "topk_gap": 0.004,
                                   "metric_err": 0.0, "bad_answers": 0.0}}))
    (code / "metrics" / "verdicts_traced.py").write_text(
        "def read(ctx):\n    return float(ctx.verdicts)\n")
    with open(data / "BENCHMARK.json") as f:
        bench = json.load(f)
    bench["workloads"].append({"name": "tiny-bert.tiny-k5",
                               "config": "tiny-bert", "traffic": "tiny-k5",
                               "chips": 1, "why": "added by files"})
    bench["per_layer"].append({"name": "verdicts_traced", "unit": "count",
                               "better": "higher", "source": "host_clock",
                               "layer": "test", "moves": "passages_per_s",
                               "workloads": ["tiny-bert.tiny-k5"]})
    (data / "BENCHMARK.json").write_text(json.dumps(bench))
    return data, code


def test_added_cell_runs_from_files_alone(added):
    data, code = added
    cell = load_cell("tiny-bert.tiny-k5", benchmark=str(data /
                     "BENCHMARK.json"), data_dir=str(data),
                     code_dir=str(code))
    assert cell.mix["k"] == 5 and cell.sizes["corpus"] == 300
    assert [m["name"] for m in cell.per_layer][-1] == "verdicts_traced"
    harness.prepare_jax()
    out = harness.run(cell, seed=4, seconds=0.5, trace=True,
                      t_start=time.perf_counter(), platform=None)
    assert out.line["correct"], out.checks
    assert out.line["metrics"]["verdicts_traced"]["value"] >= 1
    # the tiny cell's own metric list does not get the new metric
    other = load_cell(tiny.CELL, benchmark=str(data / "BENCHMARK.json"),
                      data_dir=str(data), code_dir=str(code))
    assert "verdicts_traced" not in [m["name"] for m in other.per_layer]
