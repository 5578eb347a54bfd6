import json
from pathlib import Path

import pytest

from chipbench import flops

CFG = json.loads((Path(__file__).parents[1] / "configs"
                  / "mistral-7b-v0.2.serve-d16.json").read_text())


def test_against_a_hand_count_for_depth_8():
    cfg = dict(CFG, num_hidden_layers=8)
    # per layer: qkv 4096 x 6144, o 4096 x 4096, gate/up/down 3 x 4096 x 14336
    per_layer = 25_165_824 + 16_777_216 + 176_160_768
    assert per_layer == 218_103_808
    assert flops.matmul_params(cfg) == 8 * per_layer + 4096 * 32000
    # causal half of 4096: q.k and p.v over 2048 keys, 32 heads of 128
    attn = 8 * 2 * 2 * 2048 * 32 * 128
    assert flops.train_flops_per_token(cfg, 4096) == 3 * (
        2 * 1_875_902_464 + attn) == 12_060_721_152


def test_unknown_device_kind_is_an_error():
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        flops.peaks("_source")
