"""What the Ouro cell adds to the harness, by hand on the CPU: its two
readers on records written by hand, its copy of the verdict against
``correct.served``'s own arithmetic, and a toy cell through ``run_cell``
with the new driver."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

from chipbench import correct, correct_ouro
from chipbench import run as harness

CELLS = Path(__file__).parent / "cells"
BENCH = Path(__file__).parents[1]
OURO = json.loads((BENCH / "configs" / "ouro-2.6b.serve.json").read_text())
MISTRAL = json.loads((BENCH / "configs"
                      / "mistral-7b-v0.2.serve-d16.json").read_text())


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


# Two ticks that only decode (40 and 50 ms) and one that also prefills.
TICKS = [
    ev(1, None, "serving.step", 0, 40_000, tick=1),
    ev(2, 1, "serving.decode", 10, 39_000, slots=8, kv_blocks=100,
       ut_steps=4, cache_layers=192),
    ev(3, None, "serving.step", 50_000, 50_000, tick=2),
    ev(4, 3, "serving.decode", 50_010, 49_000, slots=8, kv_blocks=120,
       ut_steps=4, cache_layers=192),
    ev(5, None, "serving.step", 110_000, 300_000, tick=3),
    ev(6, 5, "exe.prefill", 110_010, 100, rows=2048, useful=300),
    ev(7, 5, "serving.decode", 300_000, 100_000, slots=8, kv_blocks=80,
       ut_steps=4, cache_layers=192),
]


def record(cfg, block=16, **trace):
    return {"config": cfg, "device_kind": "TPU v5 lite",
            "cell": {"engine": {"block_size": block}},
            **({"trace": trace} if trace else {})}


def test_weight_roofline_is_the_weight_stream_over_the_median_decode_tick(spans):
    spans(TICKS)
    value, n = read("decode_weight_roofline.backlog", record(OURO))
    # 19,931,332,608 B at 819e9 B/s = 24.336 ms, over the median 45 ms
    assert n == 2
    assert value == pytest.approx(100 * (19_931_332_608 / 819e9) / 0.045)
    assert value == pytest.approx(54.08, abs=0.01)
    # a one-pass model: 16 layers of 218,103,808 and the head, 2 B each
    value, _ = read("decode_weight_roofline.backlog", record(MISTRAL))
    assert value == pytest.approx(
        100 * (2 * (16 * 218_103_808 + 4096 * 32000) / 819e9) / 0.045)


def test_attention_roofline_is_the_live_kv_over_the_kernels_seconds(spans):
    spans(TICKS)
    trace = dict(busy_s=0.4, window_s=0.41, device_ops=[
        ["%fusion", 0.3], ["%paged_decode_attention", 0.002]])
    value, n = read("decode_attention_roofline.backlog", record(OURO, **trace))
    # 300 blocks x 16 tokens x 8,192 B x 192 cache layers = 7,549,747,200 B
    assert n == 3
    assert value == pytest.approx(100 * 7_549_747_200 / 0.002 / 819e9)
    # the parent of the PR that brought ``cache_layers``: spans carry
    # ``kv_blocks`` alone, and the layers come from the configuration
    bare = [dict(e, args={k: v for k, v in e["args"].items()
                          if k not in ("ut_steps", "cache_layers")})
            for e in TICKS]
    spans(bare)
    value, _ = read("decode_attention_roofline.backlog",
                    record(MISTRAL, **trace))
    assert value == pytest.approx(100 * 300 * 16 * 4096 * 16 / 0.002 / 819e9)


def test_the_new_readers_return_none_where_there_is_nothing_to_read(spans):
    trace = dict(busy_s=0.4, window_s=0.41, device_ops=[["%fusion", 0.3]])
    for name in ("decode_weight_roofline.backlog",
                 "decode_attention_roofline.backlog"):
        spans([])                                  # untraced, or no spans
        assert read(name, record(OURO)) is None
        assert harness.reader(name).UNIT == "%"
    spans(TICKS)       # traced, but the kernel is not among the operations
    assert read("decode_attention_roofline.backlog",
                record(OURO, **trace)) is None
    assert read("decode_attention_roofline.backlog", record(OURO)) is None
    spans([dict(e, args={"slots": 8}) for e in TICKS])    # no kv_blocks
    assert read("decode_attention_roofline.backlog", record(
        OURO, busy_s=1.0, window_s=1.0,
        device_ops=[["%paged_decode_attention", 0.1]])) is None


def test_the_copied_verdict_is_correct_serveds_own(monkeypatch):
    """``correct.served`` and ``correct_ouro.served`` given the same logits
    (each module's reference swapped for a table of them) return the same
    verdict, number for number, sound and unsound."""
    from chipbench import reference, reference_ouro, weights
    rng = np.random.default_rng(7)
    check = {"requests": 8, "pad_multiple": 32, "max_tokens": 12,
             "limits": {"widest_gap": 0.5, "mean_gap": 0.018}}
    vocab = 64
    rows = [(rng.integers(1, vocab, n, dtype=np.int32),
             [int(t) for t in rng.integers(1, vocab, m)])
            for n, m in ((9, 5), (40, 12), (17, 20))]

    for spread in (0.001, 1.0):          # a sound run, and one that is not
        def table(cfg, ids, top, layer_weights, keep=None):
            out = []
            for row, at in zip(ids, keep):
                lg = rng.normal(size=(len(at), vocab)).astype(np.float32)
                out.append(lg * spread)
            table.made.append(out)
            return out
        table.made = []
        monkeypatch.setattr(reference, "forward", table)
        monkeypatch.setattr(weights, "make_top", lambda *a: None)
        want = correct.served({}, 1, rows, check)
        logits = table.made[-1]
        monkeypatch.setattr(reference_ouro, "forward",
                            lambda *a, keep=None: logits)
        monkeypatch.setattr(reference_ouro, "make_top", lambda *a: None)
        got = correct_ouro.served({}, 1, rows, check)
        assert got == want
        assert got["correct"] is (spread < 0.01)
    assert correct_ouro.served({}, 1, [], check) == correct.served(
        {}, 1, [], check)
    assert correct_ouro.choose is correct.choose


def test_the_toy_ouro_cell_runs_through_the_harness_and_is_correct():
    code, res = harness.run_cell("tiny-ouro.backlog", 2 ** 31 + 5, 1.5,
                                 False, root=CELLS, need_tpu=False)
    assert code == 0 and res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 5
    want = json.loads((CELLS / "workloads"
                       / "tiny-ouro.backlog.json").read_text())
    assert set(res["metrics"]) == set(want["end_to_end"])


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from paddle_tpu.serving.engine import LLMEngine
    real, n = LLMEngine._emit, [0]

    def emit(self, slot, token):
        n[0] += 1
        return real(self, slot, token ^ 1 if n[0] % 7 == 0 else token)

    monkeypatch.setattr(LLMEngine, "_emit", emit)
    code, res = harness.run_cell("tiny-ouro.backlog", 11, 1.5, False,
                                 root=CELLS, need_tpu=False)
    assert code == 0 and res["correct"] is False
