"""What the Olmo-Hybrid cell adds to the harness, by hand on the CPU:
``hybrid.py``'s counts against hand arithmetic (the numbers of ISSUE 35),
its four readers on a span list written by hand, the copied verdict's
choice of requests, and a toy cell through ``run_cell`` with the new driver,
sound and with each control planted."""
import json
import re
import sys
from pathlib import Path

import numpy as np
import pytest

from chipbench import control_olmo_hybrid as control
from chipbench import correct_olmo_hybrid, hybrid
from chipbench import run as harness

CELLS = Path(__file__).parent / "cells"
BENCH = Path(__file__).parents[1]
OLMO = json.loads((BENCH / "configs"
                   / "olmo-hybrid-7b.serve-d16.json").read_text())
MISTRAL = json.loads((BENCH / "configs"
                      / "mistral-7b-v0.2.serve-d16.json").read_text())


def test_the_cut_configuration_counts_what_the_issue_counted():
    assert hybrid.kinds(OLMO) == (["linear_attention"] * 3
                                  + ["full_attention"]) * 4
    assert (hybrid.layers(OLMO, hybrid.LINEAR),
            hybrid.layers(OLMO, hybrid.FULL)) == (12, 4)
    # 3840 x (2880 + 2880 + 5760 + 5760) + 5760 x 3840 + 2 x 3840 x 30
    assert hybrid.mixer_matmul_params(OLMO, hybrid.LINEAR) == 88_704_000
    assert hybrid.mlp_params(OLMO) == 3 * 3840 * 11008 == 126_812_160
    assert hybrid.layer_matmul_params(OLMO, hybrid.LINEAR) == 215_516_160
    assert hybrid.layer_matmul_params(OLMO, hybrid.FULL) \
        == 4 * 3840 ** 2 + 126_812_160 == 185_794_560
    assert hybrid.head_params(OLMO) == 385_351_680
    # 12 x 215.5 + 4 x 185.8 + 770.8 = 4.10B parameters, 8.20 GB
    assert hybrid.weight_bytes(OLMO) == 2 * (
        12 * 215_516_160 + 4 * 185_794_560 + 2 * 385_351_680) \
        == 8_200_151_040
    assert hybrid.kv_bytes_per_token(OLMO) == 4 * 2 * 30 * 128 * 2 == 61_440
    # a layer: 30 x 96 x 192 x 4 B and 3 x 11,520 x 2 B
    assert hybrid.state_bytes_per_layer(OLMO) == 2_211_840 + 69_120
    assert hybrid.state_bytes_per_slot(OLMO) == 27_371_520
    # 64 x (3 x 96 + 2 x 192) + 3 x 96 x 192 + 64^2 = 102,400 MACs a head
    assert hybrid.rule_flops_per_token(OLMO) == 2 * 30 * 102_400
    # (96 + 96 + 192) x 2 B in, 192 x 4 B out, 8 B of decay and beta: a head
    assert hybrid.rule_bytes_per_token(OLMO) == 30 * (768 + 768 + 8)
    flat = hybrid.forward_flops_per_token(OLMO, 0.0)
    assert flat == 2.0 * (12 * 215_516_160 + 4 * 185_794_560
                          + 385_351_680) + 12 * 6_144_000
    assert hybrid.forward_flops_per_token(OLMO, 1000.0) - flat \
        == 4 * 4 * 1000 * 30 * 128
    # a decode tick: 7.43 GB of weights, 8 slots' K/V and state
    assert hybrid.decode_tick_bytes(OLMO, 40_000, 8) == pytest.approx(
        2 * 3_714_723_840 + 40_000 * 61_440 + 2 * 8 * 27_371_520)


def test_the_manifests_entries_for_the_hybrid_keep_the_form_of_the_file():
    # the driver refuses the whole file for one line over 200 characters
    # (this cell's `why` once had 211) or one name outside its alphabet
    manifest = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    name = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
    unit = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")

    def line(text):
        return 1 <= len(text) <= 200 and text.isascii() and text.isprintable()
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert name.fullmatch(c["name"]) and line(c["why"]), c["name"]
        assert line(c["source"]) and len(c["reduced"]) <= 16
        assert all(name.fullmatch(k) for k in c["reduced"])
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(name.fullmatch(w[k]) for k in ("name", "config", "traffic"))
        assert line(w["why"]) and w["chips"] in (1, 4), w["name"]
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}, m["name"]
        assert name.fullmatch(m["name"]) and unit.fullmatch(m["unit"])
        assert line(m["layer"]) and m["better"] in ("lower", "higher")
    assert (BENCH.parent / "BENCHMARK.json").stat().st_size <= 64 * 1024


def ev(id, parent, name, ts, dur, **args):
    return {"name": name, "ph": "X", "cat": "host", "ts": ts, "dur": dur,
            "pid": 1, "tid": 1, "id": id, "parent": parent, "args": args}


@pytest.fixture
def spans(monkeypatch):
    mod = harness.reader("_spans")
    monkeypatch.setitem(sys.modules, "_spans", mod)

    def give(events):
        monkeypatch.setattr(mod, "program_events", lambda: list(events))
    give([])
    return give


def read(name, run):
    return harness.reader(name).read(run)


DECODE = dict(ut_steps=1, cache_layers=4, state_layers=12)
# two ticks that only decode (16 and 20 ms) and one with two chunk calls
TICKS = [
    ev(1, None, "serving.step", 0, 16_000, tick=1),
    ev(2, 1, "serving.decode", 10, 15_000, slots=8, kv_blocks=2000,
       state_slots=8, **DECODE),
    ev(3, None, "serving.step", 20_000, 20_000, tick=2),
    ev(4, 3, "serving.decode", 20_010, 19_000, slots=7, kv_blocks=3000,
       state_slots=7, **DECODE),
    ev(5, None, "serving.step", 50_000, 140_000, tick=3),
    ev(6, 5, "exe.prefill_chunk", 50_010, 100, rows=1024, useful=1024,
       kv_blocks=192, ctx_tokens=3072, **DECODE),
    ev(7, 5, "exe.prefill_chunk", 50_200, 100, rows=1024, useful=500,
       kv_blocks=220, ctx_tokens=3572, **DECODE),
    ev(8, 5, "serving.decode", 170_000, 19_000, slots=8, kv_blocks=2500,
       state_slots=8, **DECODE),
]
TRACE = dict(busy_s=0.16, window_s=0.2, device_ops=[
    ["%fusion", 0.1], ["%gated_delta_chunk", 0.02]])


def record(cfg, block=16, **trace):
    return {"config": cfg, "device_kind": "TPU v5 lite",
            "cell": {"engine": {"block_size": block}},
            **({"trace": trace} if trace else {})}


def test_chunk_roofline_is_the_rules_floor_over_the_kernels_seconds(spans):
    spans(TICKS)
    value, n = read("gated_delta_chunk_roofline.backlog",
                    record(OLMO, **TRACE))
    # 1,524 tokens x 12 layers; a token-layer is 6,144,000 FLOP (31.2 ns at
    # 197 TFLOP/s) and 46,320 B (56.6 ns at 819 GB/s): the bytes bind
    assert n == 2
    assert value == pytest.approx(100 * 1524 * 12 * (46_320 / 819e9) / 0.02)


def test_linear_share_is_the_chunk_kernel_over_busy(spans):
    assert read("linear_attention_share.backlog",
                record(OLMO, **TRACE)) == pytest.approx(100 * 0.02 / 0.16)
    none = dict(TRACE, device_ops=[["%fusion", 0.1]])
    assert read("linear_attention_share.backlog",
                record(OLMO, **none)) is None


def test_stream_roofline_is_the_ticks_bytes_over_the_median_decode_tick(
        spans):
    spans(TICKS)
    value, n = read("decode_stream_roofline.backlog", record(OLMO))
    # the median tick of the three: 2500 blocks, 8 slots; over 18 ms
    moved = 7_429_447_680 + 2500 * 16 * 61_440 + 2 * 8 * 12 * 2_280_960
    assert n == 2
    assert value == pytest.approx(100 * (moved / 819e9) / 0.018)
    assert value < 100


def test_mfu_counts_every_token_at_its_context_and_no_padding(spans):
    spans(TICKS)
    value, n = read("serve_mfu.backlog", record(OLMO, **TRACE))
    flat = hybrid.forward_flops_per_token(OLMO, 0.0)
    per_key = 4 * 4 * 30 * 128
    keys = (1024 * 2048 + 1024 * 1025 / 2 + 500 * 3072 + 500 * 501 / 2
            + 7500 * 16)
    assert n == 5
    assert value == pytest.approx(
        100 * ((1524 + 23) * flat + keys * per_key) / 0.2 / 197e12)


def test_the_readers_return_none_where_there_is_nothing_to_read(spans):
    names = ("gated_delta_chunk_roofline.backlog",
             "linear_attention_share.backlog",
             "decode_stream_roofline.backlog", "serve_mfu.backlog")
    for name in names:
        spans([])                                 # untraced, or no spans
        assert read(name, record(OLMO)) is None
        assert harness.reader(name).UNIT == "%"
    # the parent of this PR: its spans carry none of the new arguments, and
    # its trace no kernel of these names
    bare = [dict(e, args={k: v for k, v in e["args"].items() if k not in (
        "state_layers", "state_slots", "ctx_tokens")}) for e in TICKS]
    spans(bare)
    old = dict(busy_s=0.16, window_s=0.2, device_ops=[["%fusion", 0.1]])
    for name in names:
        assert read(name, record(MISTRAL, **old)) is None


def test_the_dozen_compared_holds_the_longest_and_the_shared():
    reqs = [{"comparable": True, "prompt_len": 100 + i, "tokens": [1] * 4,
             "shared": (i % 4 if i % 3 == 0 else -1), "index": i}
            for i in range(40)]
    reqs[17]["prompt_len"] = 9000
    reqs[5]["comparable"] = False
    check = {"requests": 12, "shared": 4}
    picks = correct_olmo_hybrid.choose(reqs, 3, check)
    assert len(picks) == 12 and len({q["index"] for q in picks}) == 12
    assert picks[0]["index"] == 17
    # behind a document, those with the fewest tokens of their own
    assert [q["index"] for q in picks[1:5]] == [0, 3, 6, 9]
    assert all(q["comparable"] for q in picks)
    assert picks == correct_olmo_hybrid.choose(reqs, 3, check)
    assert correct_olmo_hybrid.choose([], 3, check) == []


def test_the_dozen_holds_the_live_requests_with_the_most_tokens_served():
    reqs = [{"comparable": True, "prompt_len": 100 + i, "tokens": [1] * i,
             "shared": -1, "index": i} for i in range(1, 30)]
    check = {"requests": 12, "shared": 4, "live": 3}
    picks = correct_olmo_hybrid.choose(reqs, 3, check, live={4, 9, 2, 7, 29})
    # 29 is the longest and comes first; then the live ones by tokens served
    assert [q["index"] for q in picks[:4]] == [29, 9, 7, 4]
    assert len({q["index"] for q in picks}) == 12


def test_a_states_gap_is_its_distance_from_the_references_over_its_norm():
    rng = np.random.default_rng(0)
    ref = [rng.normal(size=(3, 8, 4)).astype(np.float32) for _ in range(2)]
    served = [r.transpose(0, 2, 1).copy() for r in ref]   # [H, d_k, d_v]
    assert correct_olmo_hybrid.state_gaps(served, ref).max() == 0.0
    served[1][2] *= 1.01
    gaps = correct_olmo_hybrid.state_gaps(served, ref)
    assert gaps.shape == (2, 3) and gaps[0].max() == 0.0
    assert gaps[1] == pytest.approx([0.0, 0.0, 0.01], abs=1e-6)


def test_a_cell_that_limits_the_state_is_not_correct_without_one():
    check = {"limits": {"mean_gap": 1.0, "state_gap": 1.0}}
    rows = [(np.arange(4, dtype=np.int32), [1, 2])]
    res = correct_olmo_hybrid.served(OLMO, 1, rows, check, states={})
    assert res["correct"] is False and "state" in res["why"]


def toy(seed):
    return harness.run_cell("tiny-olmo-hybrid.backlog", seed, 1.5, False,
                            root=CELLS, need_tpu=False)


def test_the_toy_hybrid_cell_runs_through_the_harness_and_is_correct():
    code, res = toy(2 ** 31 + 5)
    assert code == 0 and res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 5
    want = json.loads((CELLS / "workloads"
                       / "tiny-olmo-hybrid.backlog.json").read_text())
    assert set(res["metrics"]) == set(want["end_to_end"])


@pytest.mark.parametrize("what", control.CONTROLS)
def test_the_toy_cell_with_a_control_planted_is_not_correct(what):
    """In float32 the toy's limits are those of sums in another order: a
    state rounded to bfloat16, and a hit that restores a state one chunk
    older than the K/V it adopts, both read far above them. (No comfort for
    the cell's own limits: the toy runs in float32, the cell in bfloat16,
    where only ``state_gap`` tells a bfloat16 state from a sound run:
    PERF.md, section 6, PR 35.)"""
    with control.CONTROLS[what]():
        code, res = toy(11)
    assert code == 0 and res["correct"] is False
