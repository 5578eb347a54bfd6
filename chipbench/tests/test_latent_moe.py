"""What the Kimi-K2 cell adds to the harness, by hand on the CPU:
``latent_moe.py``'s counts against ISSUE 41's table, the traffic file's
means, its six readers on a span list written by hand, and a toy cell
through ``run_cell`` with the new driver, sound and with each control
planted."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

from chipbench import control_kimi_k2 as control
from chipbench import latent_moe as lm
from chipbench import run as harness
from chipbench.traffic import sessions

CELLS = Path(__file__).parent / "cells"
BENCH = Path(__file__).parents[1]
KIMI = json.loads((BENCH / "configs"
                   / "kimi-k2-instruct.serve-ep32-d7.json").read_text())
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_the_cut_configuration_counts_what_the_issue_counted():
    # 7168 x 1536 + 1536 x 12288 + 7168 x 576 + 512 x 16384 + 8192 x 7168
    # and the two latent norms
    assert lm.attention_params(KIMI) == 101_124_096
    assert lm.expert_params(KIMI) == 3 * 7168 * 2048 == 44_040_192
    assert lm.expert_layer_params(KIMI, held=0) == 147_931_520
    assert lm.expert_layer_params(KIMI) == 676_413_824
    assert lm.dense_layer_params(KIMI) == 497_500_160
    assert lm.head_params(KIMI) == 146_800_640
    assert lm.model_params(KIMI) == 4_849_591_552
    # 9.70 GB in bf16 and the six routers' float32 matrices and biases
    assert lm.weight_bytes(KIMI) == 2 * 4_849_591_552 + 2 * 6 * (
        7168 * 384 + 384)
    assert lm.cache_bytes_per_token_layer(KIMI) == 1_152
    assert lm.cache_bytes_per_token(KIMI) == 8_064
    # the whole model by the same count: 1.026T parameters
    whole = dict(KIMI, num_hidden_layers=61, n_routed_experts=384,
                 vocab_size=163840)
    assert round(lm.model_params(whole) / 1e9) == 1026
    # 41 kFLOP a token a cached key a layer; 8 x 12 / 384 pairs a layer
    assert lm.attention_flops_per_key(KIMI) == 7 * 40_960
    assert lm.even_routed_pairs_per_token(KIMI) == 6 * 0.25
    flat = lm.forward_flops(KIMI, 1, 0, 0)
    assert flat == 2.0 * (7 * 101_122_048 + 3 * 7168 * 18432
                          + 6 * (44_040_192 + 7168 * 384) + 146_800_640)
    assert lm.forward_flops(KIMI, 1, 10, 3) - flat == \
        10 * 7 * 40_960 + 3 * 2 * 44_040_192


def test_the_traffic_file_is_mooncakes_means():
    mix = json.loads((BENCH / "traffic"
                      / "sessions_longshared_backlog.json").read_text())
    reqs = sessions.requests(1, mix["params"], KIMI["vocab_size"])
    prompt = np.array([len(q["prompt"]) for q in reqs])
    shared = np.array([q["shared"] >= 0 for q in reqs])
    assert round(prompt.mean()) == 7566
    # the answers of the requests not cut to a running engine's remainder
    assert round(np.mean([q["max_new_tokens"] for q in reqs[32:]])) == 188
    assert round(100 * shared.sum() * 4096 / prompt.sum(), 1) == 40.6
    assert max(len(q["prompt"]) + q["max_new_tokens"] for q in reqs) \
        <= 16896
    assert all(q["prompt"].max() < KIMI["vocab_size"] for q in reqs[:8])


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


ARGS = dict(ut_steps=1, cache_layers=7)
# two ticks that only decode and one that sends a chunk at offset 4,096
TICKS = [
    ev(1, None, "serving.step", 0, 16_000, tick=1),
    ev(2, 1, "serving.decode", 10, 15_000, slots=32, kv_blocks=10_000,
       routed_pairs=50, experts_hit=36, **ARGS),
    ev(3, None, "serving.step", 20_000, 17_000, tick=2),
    ev(4, 3, "serving.decode", 20_010, 16_000, slots=30, kv_blocks=12_000,
       routed_pairs=40, experts_hit=30, **ARGS),
    ev(5, None, "serving.step", 50_000, 220_000, tick=3),
    ev(6, 5, "exe.prefill_chunk", 50_010, 100, rows=2048, useful=2000,
       kv_blocks=381, ctx_tokens=6096, **ARGS),
    ev(7, 5, "serving.decode", 250_000, 18_000, slots=32, kv_blocks=11_000,
       routed_pairs=44, experts_hit=33, **ARGS),
    ev(8, 7, "exe.routed", 260_000, 5, program="chunk", seq=9,
       routed_pairs=3000, experts_hit=72),
]
TRACE = dict(busy_s=0.24, window_s=0.3, device_ops=[
    ["%fusion", 0.1], ["%paged_latent_chunk_attention", 0.06],
    ["%grouped_matmul", 0.03], ["%paged_latent_decode_attention", 0.012]])


def record(cfg=KIMI, block=16, **trace):
    return {"config": cfg, "device_kind": "TPU v5 lite",
            "cell": {"engine": {"block_size": block}},
            **({"trace": trace} if trace else {})}


def test_mfu_counts_every_token_at_its_context_and_its_routed_pairs(spans):
    spans(TICKS)
    value, n = read("serve_mfu_mla_moe.backlog", record(**TRACE))
    tokens = 2000 + 32 + 30 + 32
    keys = 2000 * 4096 + 2000 * 2001 / 2 + 16 * (10_000 + 12_000 + 11_000)
    pairs = 50 + 40 + 44 + 3000
    flops = (tokens * lm.forward_flops(KIMI, 1, 0, 0)
             + keys * 7 * 40_960 + pairs * 2 * 44_040_192)
    assert n == 4
    assert value == pytest.approx(100 * flops / 0.3 / 197e12)
    assert 10 < value < 20


def test_grouped_roofline_is_each_calls_floor_over_the_kernels_seconds(
        spans):
    spans(TICKS)
    value, n = read("grouped_matmul_roofline.backlog", record(**TRACE))
    # the ticks are bound by the experts' weights (88.1 MB each), and so
    # is the chunk call: 3,000 pairs are 0.79 TFLOP (4.0 ms), its 72
    # experts 6.34 GB (7.7 ms)
    tick_s = (36 + 30 + 33) * 88_080_384 / 819e9
    chunk_s = max(3000 * 2 * 44_040_192 / 197e12, 72 * 88_080_384 / 819e9)
    assert chunk_s == pytest.approx(72 * 88_080_384 / 819e9)
    assert n == 4
    assert value == pytest.approx(100 * (tick_s + chunk_s) / 0.03)
    assert lm.grouped_floor_seconds(KIMI, 30_000, 72, PEAK) == \
        pytest.approx(30_000 * 2 * 44_040_192 / 197e12)


def test_latent_decode_roofline_is_the_live_rows_over_the_kernels_seconds(
        spans):
    spans(TICKS)
    value, n = read("latent_decode_roofline.backlog", record(**TRACE))
    # 33,000 blocks x 16 tokens x 1,152 B x 7 layers = 4.26 GB in 12 ms
    assert n == 3
    assert value == pytest.approx(
        100 * 33_000 * 16 * 1_152 * 7 / 0.012 / 819e9)
    assert value == pytest.approx(43.3, abs=0.1)


def test_decode_stream_roofline_is_a_ticks_floor_over_its_median(spans):
    spans(TICKS)
    value, n = read("decode_stream_roofline_mla_moe.backlog", record())
    # the weights a tick always streams: all the chip holds (9.73 GB with
    # the float32 routers) less the embedding and the 72 held experts;
    # the median tick touched 33 of them and read 11,000 blocks a layer
    always = lm.weight_bytes(KIMI) - 146_800_640 * 2 - 72 * 88_080_384
    assert always == pytest.approx(3.09e9, rel=0.01)
    moved = always + 33 * 88_080_384 + 11_000 * 16 * 1_152 * 7
    assert n == 2                      # two ticks that only decode
    assert value == pytest.approx(100 * moved / 819e9 / 0.0165)
    assert 50 < value < 60


def test_the_shares_are_kernel_seconds_over_busy_seconds(spans):
    spans(TICKS)
    assert read("latent_attention_share.backlog", record(**TRACE)) == \
        pytest.approx(100 * 0.072 / 0.24)
    assert read("expert_share.backlog", record(**TRACE)) == \
        pytest.approx(100 * 0.03 / 0.24)
    value, n = read("experts_hit_share.backlog", record(**TRACE))
    assert n == 3 and value == pytest.approx(100 * 33 / 72)


NEW = ("serve_mfu_mla_moe.backlog", "grouped_matmul_roofline.backlog",
       "latent_decode_roofline.backlog", "latent_attention_share.backlog",
       "expert_share.backlog", "experts_hit_share.backlog",
       "decode_stream_roofline_mla_moe.backlog")


def test_the_readers_return_none_where_there_is_nothing_to_read(spans):
    # a program without the counts (the parent's), with and without a trace
    plain = [e for e in TICKS if e["name"] != "exe.routed"]
    plain = [dict(e, args={k: v for k, v in e["args"].items()
                           if k not in ("routed_pairs", "experts_hit")})
             for e in plain]
    spans(plain)
    other = dict(busy_s=0.2, window_s=0.3, device_ops=[["%fusion", 0.2]])
    for name in NEW:
        assert read(name, record()) is None, name
        assert read(name, record(**other)) is None, name
    spans([])
    for name in NEW:
        assert read(name, record(**TRACE)) is None or name in (
            "latent_attention_share.backlog", "expert_share.backlog")


# ----------------------------------------------------------- the toy cell
def toy(seed):
    return harness.run_cell("tiny-kimi-k2.backlog", seed, 1.5, False,
                            root=CELLS, need_tpu=False)


def test_the_toy_kimi_cell_runs_through_the_harness_and_is_correct():
    code, res = toy(2 ** 31 + 5)
    assert code == 0 and res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 5
    want = json.loads((CELLS / "workloads"
                       / "tiny-kimi-k2.backlog.json").read_text())
    assert set(res["metrics"]) == set(want["end_to_end"])


@pytest.mark.parametrize("what", control.CONTROLS)
def test_the_toy_cell_with_a_control_planted_is_not_correct(what):
    """In float32 the toy's limits are those of sums in another order:
    weights that int8 holds move the gaps far above them, and so does a
    gate that drops its selection bias and chooses other experts."""
    with control.CONTROLS[what]():
        code, res = toy(11)
    assert code == 0 and res["correct"] is False
