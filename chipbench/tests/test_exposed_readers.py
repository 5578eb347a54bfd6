"""The six readers of the host's exposed time (``metrics/_exposed.py``), each
on event lists written out by hand against sums worked out by hand; then
``exposed.py`` on a tiny cell driven on the CPU with the program's own
buffer recording."""
import json
from pathlib import Path

import pytest

from chipbench import exposed as tool
from chipbench import run as harness

CELLS = Path(__file__).parent / "cells"
READERS = list(tool.READERS)


def ev(id, parent, name, ts, end, cat="host", **args):
    e = {"name": name, "ph": "X", "cat": cat, "ts": ts, "dur": end - ts,
         "pid": 1, "tid": 1, "id": id, "parent": parent}
    if args:
        e["args"] = args
    return e


def sent(id, parent, program, seq, ts, end):
    return ev(id, parent, "exe.dispatch", ts, end, cat="dispatch",
              program=program, seq=seq)


def wait(id, parent, name, seq, ts, end):
    return ev(id, parent, name, ts, end, cat="device_wait", seq=seq)


# Microseconds. Two synchronous decode-only ticks. Tick 1 (0..10000)
# dispatches the tick program as seq 1 (400..880), the key's split as seq 2,
# and waits for seq 1 until 9400: from then nothing is in flight. A request
# is submitted between the ticks (10100..10250). Tick 2 (10400..20000)
# dispatches seq 3 at 10900 (for 550): the starved interval is
# 9400..10900 = 1500, and by span:
#   serving.decode (tick 1, after its fetch)      9400..9500          100
#   serving.step (tick 1) 9500..9510, 9900..9910, 9990..10000          30
#   serving.emit 9510..9900                                           390
#   serving.bookkeeping 9910..9990                                     80
#   between_steps 10000..10100, 10250..10400                          250
#   serving.submit                                                    150
#   serving.step (tick 2) 10400..10410, 10420..10430, 10450..10460,
#       10480..10500, 10650..10700                                    100
#   serving.expire 10, serving.admit 20, serving.prefill 20,
#   serving.stage 150, serving.decode (tick 2) 10700..10710 10,
#   exe.decode_tick 10710..10900 190                                  400
# in all 1500. Exposed: the interval and the dispatch that ended it, 2050 of
# the window 0..20000: 10.25%.
DECODE = [
    ev(1, None, "serving.step", 0, 10000, tick=1),
    ev(2, 1, "serving.expire", 10, 20),
    ev(3, 1, "serving.admit", 30, 50),
    ev(4, 1, "serving.prefill", 60, 80),
    ev(5, 1, "serving.stage", 90, 190, grown=0, preempted=0),
    ev(6, 1, "serving.decode", 200, 9500, slots=4),
    ev(7, 6, "exe.decode_tick", 210, 900, slots=4),
    sent(8, 7, "tick", 1, 400, 880),
    sent(9, 6, "split", 2, 910, 1000),
    ev(10, 6, "serving.gauges", 1010, 1400, shadow=True),
    wait(11, 6, "serving.fetch", 1, 1410, 9400),
    ev(12, 1, "serving.emit", 9510, 9900, tokens=4),
    ev(13, 1, "serving.bookkeeping", 9910, 9990),
    ev(20, None, "serving.submit", 10100, 10250, rid=7),
    ev(21, None, "serving.step", 10400, 20000, tick=2),
    ev(22, 21, "serving.expire", 10410, 10420),
    ev(23, 21, "serving.admit", 10430, 10450),
    ev(24, 21, "serving.prefill", 10460, 10480),
    ev(25, 21, "serving.stage", 10500, 10650, grown=1, preempted=0),
    ev(26, 21, "serving.decode", 10700, 19500, slots=4),
    ev(27, 26, "exe.decode_tick", 10710, 11500, slots=4),
    sent(28, 27, "tick", 3, 10900, 11450),
    sent(29, 26, "split", 4, 11510, 11600),
    wait(30, 26, "serving.fetch", 3, 11700, 19400),
    ev(31, 21, "serving.emit", 19510, 19900, tokens=4),
    ev(32, 21, "serving.bookkeeping", 19910, 19990),
]
DECODE_SPLIT = {
    "serving.decode": 110, "serving.step": 130, "serving.emit": 390,
    "serving.bookkeeping": 80, "between_steps": 250, "serving.submit": 150,
    "serving.expire": 10, "serving.admit": 20, "serving.prefill": 20,
    "serving.stage": 150, "exe.decode_tick": 190}

# The same two ticks, and a collection of generation 2 inside tick 1's emit
# (9600..9800) with one of generation 0 after the last tick (outside the
# window). The emit's own time falls to 190, ``host.gc`` holds 200; the
# collector's share is 200 of 20000: 1%.
COLLECTED = DECODE + [
    ev(40, 12, "host.gc", 9600, 9800, generation=2, collected=5),
    ev(41, None, "host.gc", 20050, 20060, generation=0, collected=0),
]

# A decode tick, then a tick that sends two chunk calls. Tick 1 (0..10000)
# waits for its program (seq 1) until 9400. Tick 2 (10000..60000): a block
# copy (seq 3, 10300..10400: microseconds on the device, it ends no
# interval), chunk calls seq 4 (dispatched 11000..11400) and seq 5
# (12100..12500, with seq 4 in flight), the sampler seq 7 behind a split,
# ``exe.sample`` waits for seq 7 until 48000; then the decode dispatch seq 9
# at 50700 (for 450). Starved: 9400..11000 = 1600 and 48000..50700 = 2700,
# 4300 a tick. Exposed adds the two dispatches that began idle (400, 450):
# 5150 of the window 0..60000.
PREFILL = [
    ev(1, None, "serving.step", 0, 10000, tick=1),
    ev(2, 1, "serving.decode", 200, 9500, slots=4),
    ev(3, 2, "exe.decode_tick", 210, 900, slots=4),
    sent(4, 3, "tick", 1, 400, 880),
    sent(5, 2, "split", 2, 910, 1000),
    wait(6, 2, "serving.fetch", 1, 1410, 9400),
    ev(10, None, "serving.step", 10000, 60000, tick=2),
    ev(11, 10, "serving.prefill", 10200, 50000, live_rows=2, calls=2),
    sent(12, 11, "cow", 3, 10300, 10400),
    ev(13, 11, "exe.prefill_chunk", 10500, 11500, rows=256, useful=256),
    sent(14, 13, "chunk", 4, 11000, 11400),
    ev(15, 11, "exe.prefill_chunk", 11600, 12600, rows=256, useful=100),
    sent(16, 15, "chunk", 5, 12100, 12500),
    sent(17, 11, "split", 6, 12650, 12750),
    sent(18, 11, "sample", 7, 12800, 12900),
    sent(19, 11, "split", 8, 12910, 12990),
    wait(20, 11, "exe.sample", 7, 13000, 48000),
    ev(21, 10, "serving.stage", 50100, 50300, grown=0, preempted=0),
    ev(22, 10, "serving.decode", 50400, 59000, slots=4),
    ev(23, 22, "exe.decode_tick", 50410, 51200, slots=4),
    sent(24, 23, "tick", 9, 50700, 51150),
    wait(25, 22, "serving.fetch", 9, 51300, 58900),
]

# The pipelined loop at depth 2. Tick 1 is synchronous (waits for its own
# seq 1 until 9000); tick 2 dispatches seq 2 at 10500 after a starved
# 9000..10500; ticks 3 and 4 dispatch seq 3 and 4 and wait for seq 2 and 3,
# each an older program than the newest: no interval opens.
PIPELINED = [
    ev(1, None, "serving.step", 0, 10000, tick=1),
    ev(2, 1, "serving.decode", 100, 9100),
    sent(3, 2, "tick", 1, 300, 800),
    wait(4, 2, "serving.fetch", 1, 900, 9000),
    ev(10, None, "serving.step", 10100, 11000, tick=2),
    ev(11, 10, "serving.decode", 10200, 10950),
    sent(12, 11, "tick", 2, 10500, 10900),
    ev(20, None, "serving.step", 11100, 19000, tick=3),
    ev(21, 20, "serving.decode", 11200, 11900),
    sent(22, 21, "tick", 3, 11300, 11800),
    wait(23, 20, "serving.fetch", 2, 12000, 18900),
    ev(30, None, "serving.step", 19100, 27000, tick=4),
    ev(31, 30, "serving.decode", 19200, 19900),
    sent(32, 31, "tick", 4, 19300, 19800),
    wait(33, 30, "serving.fetch", 3, 20000, 26900),
]

# The parent of the PR that brought the edges: the same spans, no
# ``exe.dispatch`` and no ``seq``.
NO_EDGES = [
    ev(1, None, "serving.step", 0, 10000, tick=1),
    ev(2, 1, "serving.decode", 200, 9500, slots=4),
    ev(3, 2, "exe.decode_tick", 210, 900, slots=4),
    ev(4, 2, "serving.fetch", 1410, 9400, cat="device_wait"),
    ev(5, 1, "serving.emit", 9510, 9900, tokens=4),
]


@pytest.fixture
def spans(monkeypatch):
    """Readers load ``_spans`` from ``chipbench/metrics``; hand it events."""
    mod = harness.reader("_spans")
    import sys
    monkeypatch.setitem(sys.modules, "_spans", mod)

    def give(events):
        monkeypatch.setattr(mod, "program_events", lambda: list(events))
    give([])
    return give


def read(name, run=None):
    return harness.reader(name).read(run if run is not None else {})


def test_a_decode_ticks_starved_interval_and_its_split_by_span(spans):
    spans(DECODE)
    value, note = read("tick_exposed_ms_p50.backlog")
    assert value == pytest.approx(1.5) and note["n"] == 1
    assert note["mean_ms"] == pytest.approx(1.5)
    assert {k: round(v * 1e3) for k, v in note["by_span_ms_p50"].items()} \
        == DECODE_SPLIT
    assert sum(note["by_span_ms_mean"].values()) == pytest.approx(1.5)
    assert read("prefill_exposed_ms_p50.backlog") is None


def test_tick_dispatch_is_the_median_of_the_tick_programs_edge(spans):
    spans(DECODE)
    assert read("tick_dispatch_ms_p50.backlog") == (
        pytest.approx((0.48 + 0.55) / 2), 2)
    spans(PREFILL)               # chunk, cow, split and sample edges: not it
    assert read("tick_dispatch_ms_p50.backlog") == (
        pytest.approx((0.48 + 0.45) / 2), 2)


def test_a_prefill_tick_sums_its_starved_intervals(spans):
    spans(PREFILL)
    value, note = read("prefill_exposed_ms_p50.backlog")
    assert value == pytest.approx(1.6 + 2.7) and note["n"] == 1
    split = note["by_span_ms_p50"]
    # the block copy's edge lies in the first interval and ends none
    assert split["exe.dispatch.cow"] == pytest.approx(0.1)
    # 10500..11000 before the first chunk's edge; the second entry's
    # staging has the first call in flight
    assert split["exe.prefill_chunk"] == pytest.approx(0.5)
    assert split["serving.stage"] == pytest.approx(0.2)
    assert split["exe.decode_tick"] == pytest.approx(0.29)
    # 10200..10300, 10400..10500, and 48000..50000 after the sample's wait
    assert split["serving.prefill"] == pytest.approx(0.1 + 0.1 + 2.0)
    assert sum(split.values()) == pytest.approx(4.3)
    assert read("tick_exposed_ms_p50.backlog") is None
    assert read("exposed_share.backlog") == pytest.approx(
        100.0 * (1600 + 400 + 2700 + 450) / 60000)


def test_the_pipelined_loop_opens_no_interval(spans):
    spans(PIPELINED)
    value, note = read("tick_exposed_ms_p50.backlog")
    assert value == pytest.approx(1.5) and note["n"] == 1   # tick 2 alone
    assert read("exposed_share.backlog") == pytest.approx(
        100.0 * (1500 + 400) / 27000)
    mod = harness.reader("_exposed")
    assert [(a, b) for a, b, _ in mod.starved(PIPELINED)] == [(9000, 10500)]


def test_exposed_and_unexplained_shares_reconcile_with_the_traces_idle(spans):
    spans(DECODE)
    assert read("exposed_share.backlog") == pytest.approx(10.25)
    run = {"trace": {"busy_s": 0.85, "window_s": 1.0}}
    assert read("idle_unexplained_share.backlog", run) == pytest.approx(4.75)
    assert read("idle_unexplained_share.backlog", {}) is None   # no trace


def test_a_collection_inside_emit_is_the_collectors_time(spans):
    spans(COLLECTED)
    value, note = read("gc_pause_share.backlog")
    assert value == pytest.approx(1.0)
    assert note == {"passes": {"2": 1}, "longest_ms": pytest.approx(0.2)}
    _, tick = read("tick_exposed_ms_p50.backlog")
    assert tick["by_span_ms_p50"]["host.gc"] == pytest.approx(0.2)
    assert tick["by_span_ms_p50"]["serving.emit"] == pytest.approx(0.19)
    spans(DECODE)                # the mechanism is there, no pass fell in
    assert read("gc_pause_share.backlog") == (
        0.0, {"passes": {}, "longest_ms": 0.0})


def test_step_self_time_is_what_its_children_leave(spans):
    mod = harness.reader("_exposed")
    # tick 1: 10000 less 10, 20, 20, 100, 9300, 390, 80; tick 2: 9600 less
    # 10, 20, 20, 150, 8800, 390, 80
    assert mod.step_self_ms(DECODE) == pytest.approx([0.08, 0.13])
    assert mod.step_self_ms(PREFILL, prefill=True) == pytest.approx(
        [50.0 - 39.8 - 0.2 - 8.6])


@pytest.mark.parametrize("name", READERS)
def test_readers_return_none_without_the_edges(spans, name):
    run = {"trace": {"busy_s": 0.85, "window_s": 1.0}}
    spans([])                          # an untraced run
    assert read(name, run) is None
    spans(NO_EDGES)                    # the parent: spans, no edges
    assert read(name, run) is None


def test_every_reader_has_a_unit():
    for name in READERS:
        assert harness.reader(name).UNIT == ("ms" if "_ms_" in name else "%")


def test_programs_busy_seconds_come_from_the_traces_module_line():
    """The recorded v5e trace (four steps of three matmuls): its programs'
    intervals hold every operation, and the operations leave no gap."""
    from chipbench import trace as tr
    path = str(CELLS.parent.parent / "testdata" / "small.xplane.pb")
    got = tool.programs_busy_s(path)
    reduced = tr.reduce(path)
    assert reduced["busy_s"] <= got < reduced["window_s"]
    assert got == pytest.approx(reduced["busy_s"], rel=1e-3)


def test_the_tool_reads_a_tiny_cell_with_the_buffer_alone():
    """``exposed.py --record tracer`` on the tiny backlog cell, on the CPU:
    the driver's profiler switch turns the program's buffer on instead, the
    synchronous loop gives every decode tick but the first an interval, and
    the readers that need the device's trace say nothing."""
    import time
    import jax
    from paddle_tpu.observability import TRACER
    before = jax.profiler.start_trace, jax.profiler.stop_trace
    TRACER.clear()
    try:
        code, out = tool.record("tiny.backlog", 2 ** 31 + 11, 1.0, "tracer",
                                root=CELLS.parent / "cells", need_tpu=False,
                                t_start=time.perf_counter())
        events = harness.reader("_spans").program_events()
    finally:
        TRACER.disable()
        TRACER.clear()
    assert (jax.profiler.start_trace, jax.profiler.stop_trace) == before
    assert code == 0 and out["correct"] and out["record"] == "tracer"
    json.dumps(out)                              # one line of JSON
    m = out["metrics"]
    assert m["idle_unexplained_share.backlog"] is None
    assert m["device_idle_share.backlog"] is None
    decode_ticks = m["decode_tick_ms_p50.backlog"]
    assert decode_ticks["value"] > 0
    got = m["tick_exposed_ms_p50.backlog"]
    n_decode = sum(1 for e in events if e["name"] == "serving.step") - len(
        {e["parent"] for e in events if e["name"] == "serving.prefill"
         and any(k["name"].startswith("exe.prefill") for k in events
                 if k["parent"] == e["id"])})
    assert n_decode - 1 <= got["note"]["n"] <= n_decode
    assert 0 < got["value"] < decode_ticks["value"]
    assert 0 < m["tick_dispatch_ms_p50.backlog"]["value"] < got["value"] + \
        decode_ticks["value"]
    assert 0 < m["exposed_share.backlog"]["value"] < 100
    assert m["gc_pause_share.backlog"]["value"] >= 0
    assert {"serving.emit", "serving.stage", "exe.decode_tick",
            "between_steps"} <= set(got["note"]["by_span_ms_mean"])
    assert out["step_self_ms_p50"] is not None
    assert out["longest_starved"] and out["longest_starved"][0]["ms"] > 0
    top = out["longest_waits"][0]
    assert top["for"] in ("tick", "sample")
    assert top["ms"] >= top["median_ms_of_its_name"] > 0
