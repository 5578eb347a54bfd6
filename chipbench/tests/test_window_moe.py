"""What the Trinity-Mini cell adds to the harness, by hand on the CPU:
``window_moe.py``'s counts against the configuration's own ``counts`` and
arithmetic written out here, the traffic file's means, its seven readers on
a span list written by hand, and a toy cell through ``run_cell`` with the
new driver, sound and with each control planted."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

from chipbench import control_trinity as control
from chipbench import run as harness
from chipbench import window_moe as wm
from chipbench.traffic import sessions

CELLS = Path(__file__).parent / "cells"
BENCH = Path(__file__).parents[1]
MINI = json.loads((BENCH / "configs"
                   / "trinity-mini.serve-d5.json").read_text())
CELL = json.loads((BENCH / "workloads"
                   / "trinitymini.serve.mixedlen.json").read_text())
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_the_cut_configuration_counts_what_its_file_says():
    # q, k, v fused 2048 x 5120; the gate and o 2048 x 4096 each; two gains
    assert wm.attention_params(MINI) == 2048 * 5120 + 2 * 2048 * 4096 + 256 \
        == 27_263_232
    assert wm.expert_params(MINI) == 3 * 2048 * 1024 == 6_291_456
    assert wm.dense_layer_params(MINI) == 27_263_232 + 4 * 2048 \
        + 3 * 2048 * 6144 == 65_020_160
    # 128 experts and the shared one, the router and its bias
    assert wm.expert_layer_params(MINI) == 27_263_232 + 4 * 2048 \
        + 129 * 6_291_456 + 2048 * 128 + 128 == 839_131_520
    assert wm.head_params(MINI) == 200_192 * 2048 == 409_993_216
    assert wm.model_params(MINI) == 65_020_160 + 4 * 839_131_520 + 2048 \
        + 2 * 409_993_216 == 4_241_534_720
    # 8.48 GB in bf16 and the four routers' float32 matrices and biases
    assert wm.weight_bytes(MINI) == 2 * 4_241_534_720 + 2 * 4 * (
        2048 * 128 + 128) == 8_485_167_616
    # K and V of 4 heads of 128 in bf16: 2 KB a token a layer; four window
    # layers and one full
    assert wm.kv_bytes_per_token_layer(MINI) == 2_048
    assert wm.kv_bytes_per_token(MINI, wm.WINDOW) == 8_192
    assert wm.kv_bytes_per_token(MINI, wm.FULL) == 2_048
    got = {"attention_params": wm.attention_params(MINI),
           "dense_layer_params": wm.dense_layer_params(MINI),
           "expert_params": wm.expert_params(MINI),
           "expert_layer_params": wm.expert_layer_params(MINI),
           "head_params": wm.head_params(MINI),
           "model_params": wm.model_params(MINI),
           "weight_bytes": wm.weight_bytes(MINI),
           "kv_bytes_per_token_window_space": 8_192,
           "kv_bytes_per_token_full_space": 2_048}
    assert {k: v for k, v in MINI["counts"].items() if k != "_from"} == got
    # the whole model by the same count: 26B parameters, 3B of them active
    whole = dict(MINI, num_hidden_layers=32, num_dense_layers=2,
                 layer_types=[wm.FULL if (i + 1) % 4 == 0 else wm.WINDOW
                              for i in range(32)])
    assert round(wm.model_params(whole) / 1e9, 1) == 26.1
    assert wm.layers(whole, wm.FULL) == 8
    # the engine's two spaces: 32 rows of max_seq_len in the full one, a
    # window, a chunk and two edge blocks a row in the other
    eng = CELL["engine"]
    assert eng["num_blocks"] * 16 == 32 * eng["max_seq_len"]
    assert eng["num_window_blocks"] == 32 * ((2048 + 2048) // 16 + 2)
    assert eng["num_blocks"] * 16 * 2_048 == 1_107_296_256
    assert eng["num_window_blocks"] * 16 * 8_192 == 1_082_130_432


def test_the_published_keys_are_the_catalogs():
    assert MINI["reduced"] == ["num_hidden_layers", "num_dense_layers",
                               "layer_types"]
    assert (MINI["hidden_size"], MINI["head_dim"], MINI["num_experts"],
            MINI["num_experts_per_tok"], MINI["moe_intermediate_size"],
            MINI["intermediate_size"], MINI["vocab_size"],
            MINI["sliding_window"]) == (2048, 128, 128, 8, 1024, 6144,
                                        200192, 2048)
    assert MINI["layer_types"].count(wm.FULL) == 1
    assert MINI["published"]["num_hidden_layers"] == 32
    assert set(MINI["assumed"]) >= {"initializer_range", "expert_bias",
                                    "window_edge", "rope_pairing",
                                    "router_precision"}


def test_the_flops_count_keys_to_the_window_and_to_the_causal_edge():
    # a chunk of 2,048 at offset 8,192: every query of a window layer
    # reads 2,048 keys; of the full layer, its position + 1
    assert wm.window_keys(8192, 2048, 2048) == 2048 * 2048
    assert wm.causal_keys(8192, 2048) == 2048 * 8192 + 2048 * 2049 / 2
    # from offset 0 the window is the causal edge; across it, a ramp
    assert wm.window_keys(0, 2048, 2048) == wm.causal_keys(0, 2048)
    assert wm.window_keys(2000, 100, 2048) == sum(
        min(t + 1, 2048) for t in range(2000, 2100))
    assert wm.attention_flops_per_key(MINI) == 4 * 32 * 128
    flat = wm.forward_flops(MINI, 1, 1, 0, 0, 0)
    assert flat == 2.0 * (5 * (2048 * 5120 + 2 * 2048 * 4096)
                          + 3 * 2048 * 6144
                          + 4 * (6_291_456 + 2048 * 128) + 409_993_216)
    assert wm.forward_flops(MINI, 1, 1, 10, 30, 32) - flat == \
        16_384 * (4 * 10 + 1 * 30) + 32 * 2 * 6_291_456


def test_the_traffic_file_is_the_issues():
    mix = json.loads((BENCH / "traffic"
                      / "sessions_mixedlen_backlog.json").read_text())
    reqs = sessions.requests(1, mix["params"], MINI["vocab_size"])
    prompt = np.array([len(q["prompt"]) for q in reqs])
    assert len(reqs) == 384 and round(prompt.mean()) == 5099
    assert round(100 * (prompt > 2048).mean()) == 84
    assert (prompt.min(), prompt.max()) == (512, 16384)
    assert round(np.mean([q["max_new_tokens"] for q in reqs[32:]])) == 221
    assert all(q["shared"] == -1 for q in reqs)
    assert max(len(q["prompt"]) + q["max_new_tokens"] for q in reqs) \
        <= CELL["engine"]["max_seq_len"]


# ------------------------------------------------------------ the readers
def ev(id, parent, name, ts, dur, **args):
    return {"name": name, "ph": "X", "cat": "host", "ts": ts, "dur": dur,
            "pid": 1, "tid": 1, "id": id, "parent": parent, "args": args}


@pytest.fixture
def spans(monkeypatch):
    mod = harness.reader("_spans")
    monkeypatch.setitem(sys.modules, "_spans", mod)
    monkeypatch.setitem(sys.modules, "_lib", harness.reader("_lib"))

    def give(events):
        monkeypatch.setattr(mod, "program_events", lambda: list(events))
    give([])
    return give


def read(name, run):
    return harness.reader(name).read(run)


ARGS = dict(ut_steps=1, cache_layers=5)
# two ticks that only decode and one that sends a chunk at offset 8,192
TICKS = [
    ev(1, None, "serving.step", 0, 11_000, tick=1),
    ev(2, 1, "serving.decode", 10, 10_500, slots=32, kv_blocks=10_600,
       kv_blocks_full=10_600, kv_blocks_window=4_000, routed_pairs=1024,
       experts_hit=440, **ARGS),
    ev(3, None, "serving.step", 20_000, 12_000, tick=2),
    ev(4, 3, "serving.decode", 20_010, 11_500, slots=30, kv_blocks=9_000,
       kv_blocks_full=9_000, kv_blocks_window=3_700, routed_pairs=960,
       experts_hit=430, **ARGS),
    ev(5, None, "serving.step", 50_000, 120_000, tick=3),
    ev(6, 5, "exe.prefill_chunk", 50_010, 100, rows=2048, useful=2048,
       kv_blocks=640, kv_blocks_full=640, kv_blocks_window=256,
       ctx_tokens=10_240, **ARGS),
    ev(7, 5, "serving.decode", 150_000, 12_000, slots=32, kv_blocks=10_000,
       kv_blocks_full=10_000, kv_blocks_window=4_100, routed_pairs=1024,
       experts_hit=450, **ARGS),
    ev(8, 7, "exe.routed", 160_000, 5, program="chunk", seq=9,
       routed_pairs=65_536, experts_hit=512),
    ev(9, 1, "serving.gauges", 500, 20, shadow=True, full_held=10_000,
       full_free=23_792, window_held=4_200, window_free=4_056),
    ev(10, 3, "serving.gauges", 20_500, 20, shadow=True, full_held=9_000,
       full_free=24_792, window_held=3_900, window_free=4_356),
]
TRACE = dict(busy_s=0.12, window_s=0.15, device_ops=[
    ["%fusion", 0.05], ["%grouped_matmul", 0.03],
    ["%paged_chunk_attention", 0.012], ["%paged_decode_attention", 0.006]])


def record(cfg=MINI, block=16, **trace):
    return {"config": cfg, "device_kind": "TPU v5 lite",
            "cell": {"engine": {"block_size": block}},
            **({"trace": trace} if trace else {})}


def test_mfu_counts_every_token_its_keys_in_each_kind_and_its_pairs(spans):
    spans(TICKS)
    value, n = read("serve_mfu_window_moe.backlog", record(**TRACE))
    tokens, head_rows = 2048 + 32 + 30 + 32, 1 + 32 + 30 + 32
    # a window layer's decode keys: the blocks walked, at most slots x 2,048
    keys_w = 2048 * 2048 + min(4_000 * 16, 32 * 2048) \
        + min(3_700 * 16, 30 * 2048) + min(4_100 * 16, 32 * 2048)
    keys_f = 2048 * 8192 + 2048 * 2049 / 2 + 16 * (10_600 + 9_000 + 10_000)
    pairs = 1024 + 960 + 1024 + 65_536
    flops = (tokens * wm.forward_flops(MINI, 1, 0, 0, 0, 0)
             + head_rows * 2 * 409_993_216
             + 16_384 * (4 * keys_w + keys_f) + pairs * 2 * 6_291_456)
    assert n == 4
    assert value == pytest.approx(100 * flops / 0.15 / 197e12)
    assert 5 < value < 20


def test_grouped_roofline_is_each_calls_floor_over_the_kernels_seconds(
        spans):
    spans(TICKS)
    value, n = read("grouped_matmul_roofline_window_moe.backlog",
                    record(**TRACE))
    # the ticks are bound by the experts' weights (12.6 MB each); the chunk
    # call by its FLOPs: 65,536 pairs are 0.82 TFLOP (4.2 ms), its 512
    # experts 6.4 GB (7.9 ms): by its bytes still
    tick_s = (440 + 430 + 450) * 12_582_912 / 819e9
    chunk_s = max(65_536 * 2 * 6_291_456 / 197e12, 512 * 12_582_912 / 819e9)
    assert n == 4
    assert value == pytest.approx(100 * (tick_s + chunk_s) / 0.03)
    assert wm.grouped_floor_seconds(MINI, 300_000, 512, PEAK) == \
        pytest.approx(300_000 * 2 * 6_291_456 / 197e12)


def test_decode_attention_roofline_is_both_spaces_bytes_over_the_kernel(
        spans):
    spans(TICKS)
    value, n = read("decode_attention_roofline_window.backlog",
                    record(**TRACE))
    # four window layers over their blocks, one full layer over its own
    blocks = 4 * (4_000 + 3_700 + 4_100) + (10_600 + 9_000 + 10_000)
    assert n == 3
    assert value == pytest.approx(100 * blocks * 16 * 2_048 / 0.006 / 819e9)
    assert 40 < value < 60


def test_chunk_attention_roofline_is_the_calls_floor_over_the_kernel(spans):
    spans(TICKS)
    value, n = read("chunk_attention_roofline_window.backlog",
                    record(**TRACE))
    flops = 16_384 * (4 * 2048 * 2048 + 2048 * 8192 + 2048 * 2049 / 2)
    bytes_ = (4 * 256 + 640) * 16 * 2_048
    assert flops / 197e12 > bytes_ / 819e9          # bound by its FLOPs
    assert n == 1
    assert value == pytest.approx(100 * flops / 197e12 / 0.012)


def test_decode_stream_roofline_is_a_ticks_floor_over_its_median(spans):
    spans(TICKS)
    value, n = read("decode_stream_roofline_window_moe.backlog", record())
    # what a tick always streams: all the chip holds less the embedding
    # and the 512 routed experts; the median tick touched 440 of them
    always = 8_485_167_616 - 409_993_216 * 2 - 512 * 12_582_912
    assert always == pytest.approx(1.22e9, rel=0.01)
    moved = sorted(always + hit * 12_582_912 + (4 * bw + bf) * 16 * 2_048
                   for hit, bw, bf in ((440, 4_000, 10_600),
                                       (430, 3_700, 9_000),
                                       (450, 4_100, 10_000)))[1]
    assert n == 2                      # two ticks that only decode
    assert value == pytest.approx(100 * moved / 819e9 / 0.0115)
    assert 70 < value < 90


def test_the_shares_are_medians_of_what_the_spans_count(spans):
    spans(TICKS)
    value, n = read("experts_hit_share_window_moe.backlog", record())
    assert n == 3 and value == pytest.approx(100 * 440 / 512)
    value, n = read("window_blocks_share.backlog", record())
    assert n == 2 and value == pytest.approx(
        100 * (4_200 / 10_000 + 3_900 / 9_000) / 2)


NEW = ("serve_mfu_window_moe.backlog",
       "decode_stream_roofline_window_moe.backlog",
       "decode_attention_roofline_window.backlog",
       "chunk_attention_roofline_window.backlog",
       "grouped_matmul_roofline_window_moe.backlog",
       "experts_hit_share_window_moe.backlog", "window_blocks_share.backlog")


def test_the_readers_return_none_where_there_is_nothing_to_read(spans):
    # a program without two block spaces (the parent's), with and without
    # a trace: its spans count neither space, and its sweeps no blocks
    mine = ("kv_blocks_window", "kv_blocks_full", "routed_pairs",
            "experts_hit", "full_held", "full_free", "window_held",
            "window_free")
    plain = [dict(e, args={k: v for k, v in e["args"].items()
                           if k not in mine})
             for e in TICKS if e["name"] != "exe.routed"]
    spans(plain)
    other = dict(busy_s=0.2, window_s=0.3, device_ops=[["%fusion", 0.2]])
    for name in NEW:
        assert read(name, record()) is None, name
        assert read(name, record(**other)) is None, name
        assert read(name, record(**TRACE)) is None, name
    # Kimi-K2's spans carry counts of routing and no space: its file's keys
    # are another family's, and these readers leave it alone
    kimi = json.loads((BENCH / "configs"
                       / "kimi-k2-instruct.serve-ep32-d7.json").read_text())
    spans([dict(e, args={k: v for k, v in e["args"].items()
                         if k not in mine[:2] + mine[4:]}) for e in TICKS])
    for name in NEW:
        assert read(name, record(kimi, **TRACE)) is None, name
    spans([])
    for name in NEW:
        assert read(name, record(**TRACE)) is None, name


def test_every_reader_of_the_cell_is_a_file_and_in_the_manifest():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    listed = {m["name"] for m in bench["per_layer"]
              if "trinitymini.serve.mixedlen" in m.get("workloads", ())}
    assert listed == set(CELL["per_layer"]) and set(NEW) <= listed
    for name in CELL["per_layer"] + CELL["end_to_end"]:
        assert (BENCH / "metrics" / f"{name}.py").is_file(), name


# ----------------------------------------------------------- the toy cell
def toy(seed):
    return harness.run_cell("tiny-trinity.backlog", seed, 1.5, False,
                            root=CELLS, need_tpu=False)


def test_the_toy_trinity_cell_runs_through_the_harness_and_is_correct():
    code, res = toy(2 ** 31 + 5)
    assert code == 0 and res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 5
    want = json.loads((CELLS / "workloads"
                       / "tiny-trinity.backlog.json").read_text())
    assert set(res["metrics"]) == set(want["end_to_end"])


@pytest.mark.parametrize("what", control.CONTROLS)
def test_the_toy_cell_with_a_control_planted_is_not_correct(what):
    """In float32 the toy's limits are those of sums in another order:
    weights that int8 holds, a gate that drops its bias, window layers
    that read everything and a full layer that is rotated each move the
    gaps far above them."""
    with control.CONTROLS[what]():
        code, res = toy(11)
    assert code == 0 and res["correct"] is False
