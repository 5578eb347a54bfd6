"""``chipbench/looped.py`` against counts made by hand for Ouro-2.6B."""
import json
from pathlib import Path

from chipbench import flops, looped

BENCH = Path(__file__).parents[1]
OURO = json.loads((BENCH / "configs" / "ouro-2.6b.serve.json").read_text())
MISTRAL = json.loads((BENCH / "configs"
                      / "mistral-7b-v0.2.serve-d16.json").read_text())


def test_ouro_against_a_hand_count():
    # a layer: q, k, v, o 4 x 2048^2; gate, up, down 3 x 2048 x 5632
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert per_layer == looped.layer_matmul_params(OURO) == 51_380_224
    assert 48 * per_layer == 2_466_250_752
    head = 2048 * 49152
    assert head == 100_663_296
    # four passes over the 48 layers and the head once, 2 bytes each
    assert looped.matmul_weight_bytes_per_tick(OURO) == \
        (4 * 2_466_250_752 + 100_663_296) * 2 == 19_931_332_608
    # K and V, 16 heads of 128, 2 bytes
    assert looped.kv_bytes_per_token_layer(OURO) == 8_192
    assert looped.cache_layers(OURO) == 192
    assert looped.cache_layers(OURO) * looped.kv_bytes_per_token_layer(OURO) \
        == 1_572_864                                   # 1.5 MB a token


def test_flops_count_the_passes():
    # at 200 keys: q.k and p.v 2 x 2 x 200 x 16 x 128 a layer and pass
    attn = 2 * 2 * 200 * 16 * 128
    assert attn == 1_638_400
    want = 192 * (2 * 51_380_224 + attn) + 2 * 100_663_296
    assert looped.forward_flops_per_token(OURO, 200) == want == 20_245_905_408


def test_a_one_pass_model_reads_as_flops_py_reads_it():
    assert looped.passes(MISTRAL) == 1
    assert looped.cache_layers(MISTRAL) == 16
    assert looped.kv_bytes_per_token_layer(MISTRAL) == 4_096      # 8 K/V heads
    assert looped.forward_flops_per_token(MISTRAL, 512) == \
        flops.forward_flops_per_token(MISTRAL, 512)
    assert looped.matmul_weight_bytes_per_tick(MISTRAL) == \
        2 * flops.matmul_params(MISTRAL)
