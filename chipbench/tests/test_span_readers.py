"""The readers of the program's own spans and kernel names, each on an
event list written by hand with the answer worked out by hand; then on a
tiny cell driven on the CPU, where the numbers taken inside the program
must equal the ones the driver takes from outside."""
import json
from pathlib import Path

import pytest

from chipbench import run as harness

CELLS = Path(__file__).parent / "cells"
SPAN_METRICS = ["decode_tick_ms_p50.backlog", "prefill_tick_ms_p50.backlog",
                "tick_host_ms_p50.backlog", "pad_row_share.backlog",
                "step_dispatch_ms_p50.train"]
KERNEL_METRICS = ["decode_attention_share.backlog",
                  "prefill_attention_share.backlog"]


def ev(id, parent, name, ts, dur, cat="host", **args):
    e = {"name": name, "ph": "X", "cat": cat, "ts": ts, "dur": dur,
         "pid": 1, "tid": 1, "id": id, "parent": parent}
    if args:
        e["args"] = args
    return e


# Three ticks, microseconds. Tick 1 (0..1000) only decodes: it waits in a
# fetch for 900. Tick 2 (2000..5000) sends a chunk batch of 64 token-rows, 10
# useful; its sample waits 1800..2900 and, for the test only, a nested wait
# 2000..2500 lies inside that and a fetch 2800..4900 overlaps its end: union
# 1800..4900 = 3100 of which 2000..4900 = 2900 lies in the tick, so host 100.
# Tick 3 (6000..6400) decodes and admits (an ``exe.prefill`` of 32 rows, 8
# useful) and waits 6100..6350. A ``serving.drain`` span and an instant lie
# outside every tick.
TICKS = [
    ev(1, None, "serving.step", 0, 1000, tick=1),
    ev(2, 1, "serving.decode", 10, 950, slots=4),
    ev(3, 2, "exe.decode_tick", 10, 40, slots=4),
    ev(4, 2, "serving.fetch", 50, 900, cat="device_wait"),
    ev(5, 1, "serving.bookkeeping", 980, 15),
    ev(10, None, "serving.step", 2000, 3000, tick=2),
    ev(11, 10, "serving.prefill", 2010, 900),
    ev(12, 11, "exe.prefill_chunk", 2020, 30, rows=64, useful=10),
    ev(13, 11, "exe.sample", 1800, 1100, cat="device_wait", rows=4),
    ev(14, 13, "inner.wait", 2000, 500, cat="device_wait"),
    ev(15, 10, "serving.decode", 2950, 1960, slots=4),
    ev(16, 15, "serving.fetch", 2800, 2100, cat="device_wait"),
    ev(20, None, "serving.step", 6000, 400, tick=3),
    ev(21, 20, "serving.prefill", 6010, 60),
    ev(22, 21, "exe.prefill", 6020, 20, rows=32, useful=8),
    ev(23, 20, "serving.fetch", 6100, 250, cat="device_wait"),
    ev(30, None, "serving.drain", 7000, 10),
    {"name": "fault", "ph": "i", "cat": "host", "ts": 7100, "pid": 1,
     "tid": 1},
]
# Two steps of training, the second inside a training loop's own span.
STEPS = [
    ev(40, None, "train.step", 0, 18000),
    ev(41, 40, "jit.signature", 5, 11000),
    ev(50, None, "train.loop", 30000, 90000, step=7),
    ev(51, 50, "train.step", 31000, 16000),
    ev(52, 51, "jit.signature", 31005, 9000),
]


@pytest.fixture
def spans(monkeypatch):
    """Readers load ``_spans`` from ``chipbench/metrics``; hand it events."""
    mod = harness.reader("_spans")
    import sys
    monkeypatch.setitem(sys.modules, "_spans", mod)

    def give(events):
        monkeypatch.setattr(mod, "program_events", lambda: [
            e for e in events if e.get("ph") == "X" and "id" in e])
    give([])
    return give


def read(name, run=None):
    return harness.reader(name).read(run if run is not None else {})


def test_decode_and_prefill_ticks_are_told_apart(spans):
    spans(TICKS)
    assert read("decode_tick_ms_p50.backlog") == (1.0, 1)
    value, n = read("prefill_tick_ms_p50.backlog")
    assert n == 2 and value == pytest.approx((3.0 + 0.4) / 2)


def test_host_time_is_the_tick_less_the_union_of_its_waits(spans):
    spans(TICKS)
    mod = harness.reader("_spans")
    assert mod.tick_host_ms([e for e in TICKS if "id" in e]) == \
        pytest.approx([0.1, 0.1, 0.15])
    assert read("tick_host_ms_p50.backlog") == (pytest.approx(0.1), 3)


def test_pad_row_share_adds_up_the_rows_at_the_executors_entries(spans):
    spans(TICKS)
    value, calls = read("pad_row_share.backlog")
    assert calls == 2
    assert value == pytest.approx(100.0 * (96 - 18) / 96)


def test_step_dispatch_is_the_median_train_step_span(spans):
    spans(STEPS)
    assert read("step_dispatch_ms_p50.train") == (pytest.approx(17.0), 2)


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_span_readers_return_none_without_spans(spans, name):
    spans([])                          # an untraced run, or the parent
    assert read(name) is None
    spans([e for e in TICKS if "id" not in e])
    assert read(name) is None


def test_kernel_shares_come_from_the_reduced_trace_by_name():
    trace = {"busy_s": 8.0, "window_s": 8.4, "device_ops": [
        ["%paged_decode_attention", 5.0], ["%fusion", 2.0],
        ["%paged_chunk_attention", 0.5]]}
    assert read("decode_attention_share.backlog", {"trace": trace}) == 62.5
    assert read("prefill_attention_share.backlog", {"trace": trace}) == 6.25
    old = {"busy_s": 8.0, "window_s": 8.4, "device_ops": [
        ["%llama_decode_tick", 5.0], ["%fusion", 2.0]]}
    for name in KERNEL_METRICS:        # kernels without names; untraced
        assert read(name, {"trace": old}) is None
        assert read(name, {}) is None


def test_every_reader_has_a_unit():
    for name in SPAN_METRICS + KERNEL_METRICS:
        assert harness.reader(name).UNIT == ("ms" if "_ms_" in name else "%")


def test_inside_and_outside_agree_on_a_tiny_cell():
    """The tiny backlog cell on the CPU with the tracer on (no profile can
    be reduced here): inside the driver's window the executor's own row
    counts equal the driver's wrapper's exactly, and the engine's tick
    spans are the driver's ticks, one for one, on the same clock."""
    from chipbench.drivers import serve
    from paddle_tpu.observability import TRACER
    cell = json.loads((CELLS / "workloads" / "tiny.backlog.json").read_text())
    cfg = json.loads((CELLS / "configs" / f"{cell['config']}.json").read_text())
    mix = json.loads((CELLS / "traffic" / f"{cell['traffic']}.json").read_text())
    TRACER.clear()
    TRACER.enable()
    try:
        import time
        run = serve.run(cell, cfg, mix, 2 ** 31 + 7, 1.0, None,
                        time.perf_counter(), lambda **kw: None, lambda: 0)
    finally:
        TRACER.disable()
    mod = harness.reader("_spans")
    w0, w1 = run["window"]
    events = [e for e in mod.program_events()
              if w0 <= e["ts"] * 1e-6 and (e["ts"] + e["dur"]) * 1e-6 <= w1]
    TRACER.clear()
    assert run["correct"] and len(run["ticks"]) > 5
    sent = [e["args"] for e in events if e["name"].startswith("exe.prefill")]
    assert [(a["rows"], a["useful"]) for a in sent] == \
        [(rows, useful) for _, rows, useful in run["prefill_calls"]]
    lib = harness.reader("_lib")
    assert mod.pad_row_share(events) == lib.pad_share(run)
    steps = [e for e in events if e["name"] == "serving.step"]
    assert len(steps) == len(run["ticks"])
    for e, (a, b, *_) in zip(steps, run["ticks"]):     # the driver's stamps
        assert a <= e["ts"] * 1e-6 and (e["ts"] + e["dur"]) * 1e-6 <= b
    n_dec = len(mod.tick_ms(events, prefill=False))
    n_pre = len(mod.tick_ms(events, prefill=True))
    assert n_dec + n_pre == len(run["ticks"]) and n_pre == len(
        {e["parent"] for e in events if e["name"] == "serving.prefill"
         and any(k["name"].startswith("exe.prefill")
                 for k in mod.descendants(events, e))})
    host = mod.tick_host_ms(events)
    assert len(host) == len(steps)
    assert all(0 <= h <= e["dur"] * 1e-3 for h, e in zip(host, steps))
