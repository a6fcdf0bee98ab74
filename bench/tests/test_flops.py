"""bench/flops.py against counts made by hand."""

import json
import os

import pytest

from bench import flops

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def _cfg(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)


# per token and layer: Q, K, V and output projections (4 d^2), the two MLP
# products (2 d ff), each a multiply and an add; plus QK^T and PV over L keys
@pytest.mark.parametrize("name,layers,d,ff", [
    ("dr-bert-base", 12, 768, 3072),
    ("msmarco-minilm-l6", 6, 384, 1536),
])
def test_encoder_flops_by_hand(name, layers, d, ff):
    cfg = _cfg(name)
    L = 75
    by_hand = layers * (L * (2 * 4 * d * d + 2 * 2 * d * ff)
                        + 2 * 2 * d * L * L)
    assert flops.encoder_flops(cfg, [L]) == by_hand
    # texts add up; padding is not counted
    assert flops.encoder_flops(cfg, [L, 10]) == (
        flops.encoder_flops(cfg, [L]) + flops.encoder_flops(cfg, [10]))


def test_published_per_token_costs():
    # BERT-base: about 1.7e8 FLOPs per token at short lengths; MiniLM-L6
    # about 2.1e7 (2 x non-embedding parameters per token)
    assert flops.encoder_flops(_cfg("dr-bert-base"), [1]) == 169_906_176
    assert flops.encoder_flops(_cfg("msmarco-minilm-l6"), [1]) == 21_242_880


def test_scoring_and_weights():
    assert flops.scoring_flops(6980, 32768, 768) == 2 * 6980 * 32768 * 768
    # 85M trunk parameters in float32
    assert flops.weight_bytes(_cfg("dr-bert-base")) == 4 * 12 * (
        4 * 768 * 768 + 2 * 768 * 3072)


def test_peaks_by_device_kind():
    p = flops.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peaks("cpu")
