"""chip_smoke.py rehearsed on the CPU: the same phase functions at
``smoke_config()`` and tiny sizes, the top-k comparator the phases rely
on, and the script's refusal to report success without a TPU."""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)

import chip_smoke as cs  # noqa: E402

TINY = cs.Sizes(full=False, steps=4, ckpt_every=2, batch=8, corpus=96,
                queries=12, q_len=12, p_len=28, k=20, serve_queries=4,
                serve_k=5, lr=2e-3)


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    s = cs.make_setup(TINY, 0, str(tmp_path_factory.mktemp("smoke")))
    results, report = cs.phase_train(s)
    return s, results, report


@pytest.fixture(scope="module")
def referenced(trained):
    s = trained[0]
    (ref, params), report = cs.phase_reference(s)
    return s, ref, params, report


def test_train_phase_validates_every_checkpoint(trained):
    _, results, report = trained
    assert report["saved_steps"] == [2, 4]
    assert report["validated_steps"] == [2, 4]
    assert report["errors"] == 0 and not results["errors"]
    assert set(report["loss"]) == {2, 4}


def test_cli_phase_runs_xla_and_pallas(trained):
    _, report = cs.phase_cli(trained[0])
    assert report["pallas_interpreted"]          # CPU: interpret mode
    assert set(report["xla"]) == set(report["pallas"]) == {"2", "4"}


def test_reference_phase_matches_validator(referenced):
    report = referenced[3]
    stats = report["validator_vs_reference"]
    assert report["step"] == 4
    # on the CPU float32 matmuls are exact to rounding: far inside tol
    assert stats["max_score_err_over_tol"] < 1e-2
    assert stats["set_overlap_frac"] == 1.0
    highest = report["validator_highest_vs_reference"]
    assert highest["max_score_err_over_tol"] < 1.0
    assert highest["set_overlap_frac"] == 1.0
    assert report["mrr10_validator"] == report["mrr10_reference"]


def test_serve_phase_matches_reference(referenced):
    s, ref, params, _ = referenced
    _, report = cs.phase_serve(s, ref, params)
    assert report["answered"] == TINY.serve_queries
    assert report["server_vs_reference"]["max_score_err_over_tol"] < 1e-2


def _ref_case():
    rng = np.random.default_rng(0)
    ref = rng.normal(size=(3, 50)) * 10.0
    ids = np.argsort(-ref, axis=1, kind="stable")[:, :5]
    scores = np.take_along_axis(ref, ids, axis=1)
    return ids, scores, ref, 0.1


def test_compare_topk_accepts_exact_and_near_tie_swaps():
    ids, scores, ref, tol = _ref_case()
    cs.compare_topk(ids, scores, ref, tol)
    # two near-tied documents (within 2 tol) may trade places
    ref = ref.copy()
    ref[0, ids[0, 1]] = ref[0, ids[0, 0]] - 0.05
    swapped = ids.copy()
    swapped[0, [0, 1]] = ids[0, [1, 0]]
    cs.compare_topk(swapped, np.take_along_axis(ref, swapped, axis=1),
                    ref, tol)


@pytest.mark.parametrize("fault", ["score", "worse_doc", "duplicate"])
def test_compare_topk_rejects_faults(fault):
    ids, scores, ref, tol = _ref_case()
    ids, scores = ids.copy(), scores.copy()
    if fault == "score":
        scores[1, 2] += 0.5                       # 5 tol off
    elif fault == "worse_doc":
        worst = np.argsort(ref[2])[0]             # the corpus's worst doc
        ids[2, 4] = worst
        scores[2, 4] = ref[2, worst]
    else:
        ids[0, 4] = ids[0, 0]
        scores[0, 4] = scores[0, 0]
    with pytest.raises(cs.SmokeFailure):
        cs.compare_topk(ids, scores, ref, tol)


def test_main_refuses_without_a_tpu(capsys):
    assert cs.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok": true' not in out


def test_script_exits_nonzero_on_cpu():
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                          capture_output=True, text=True, timeout=300,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


_FOUR_CHIP = """
import dataclasses, json, sys
sys.path.insert(0, {root!r})
import chip_smoke as cs
sizes = cs.Sizes(**json.loads({sizes!r}))
s = cs.make_setup(sizes, 0, {workdir!r})
_, report = cs.phase_four_chips(s)
print(json.dumps(report))
"""


def test_four_chip_phase_on_virtual_devices(tmp_path):
    """The --four-chips phase on four CPU devices: sharded and single-device
    validation agree, the staged chunks and query embeddings span the mesh,
    and the trainer and the validator both sit on device 0."""
    sizes = dataclasses.replace(TINY, steps=2, ckpt_every=2)
    code = _FOUR_CHIP.format(root=ROOT,
                             sizes=json.dumps(dataclasses.asdict(sizes)),
                             workdir=str(tmp_path))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert report["device_span"] == {"staged_chunk": 4,
                                     "query_embeddings": 4}
    for stats in report["sharded_vs_single"].values():
        assert stats["max_score_err_over_tol"] < 1e-2
    assert report["placement"] == {"trainer_params": [0],
                                   "validator_carry": [0]}
