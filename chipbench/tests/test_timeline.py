"""``chipbench/timeline.py``: the driver's window arithmetic on timelines
written out by hand, what a stall costs by where the window ends, and the
recorder on two tiny cells driven on the CPU."""
import json
from pathlib import Path

import pytest

from chipbench import timeline as tool

CELLS = Path(__file__).parent / "cells"


def replay(ticks):
    """[(seconds, tokens), ...] -> (starts, stamps): ticks back to back from
    0, each tick's tokens stamped at its end (just before the next starts)."""
    starts, stamps, t = [], [], 0.0
    for seconds, tokens in ticks:
        starts.append(t)
        t += seconds
        stamps += [t - 1e-6] * tokens
    return starts, stamps


# Every length is a binary fraction, so the sums are exact. Ten decode ticks
# of 0.125 s with 4 tokens each (starts 0, 0.125 .. 1.125), three chunk ticks
# of 0.5 s with 1 token (1.25, 1.75, 2.25), ten decode ticks again (2.75 ..)
HAND = replay([(0.125, 4)] * 10 + [(0.5, 1)] * 3 + [(0.125, 4)] * 10)


@pytest.mark.parametrize("lead_in, seconds, tokens, span, ticks", [
    # opens at the tick that starts at 0.25, closes at the first that starts
    # 0.5 later (0.75): four decode ticks
    (0.25, 0.5, 16, 0.5, 4),
    # asked for between two ticks: opens at the next tick's start (0.375)
    (0.3, 0.5, 16, 0.5, 4),
    # the end falls inside a chunk tick (1.0 + 0.5 = 1.5): the window runs to
    # the next tick's start, 1.75: ticks 1.0, 1.125 and the chunk tick at 1.25
    (1.0, 0.5, 9, 0.75, 3),
    # a window of chunk ticks alone: 1.25 .. 2.75, three tokens
    (1.25, 1.5, 3, 1.5, 3),
])
def test_rate_at_is_the_drivers_window(lead_in, seconds, tokens, span, ticks):
    rate, n = tool.rate_at(*HAND, lead_in, seconds)
    assert n == ticks
    assert rate == pytest.approx(tokens / span, rel=1e-9)


def test_rate_at_says_nothing_past_the_record():
    assert tool.rate_at(*HAND, 3.5, 1.0) is None
    assert tool.rate_at(*HAND, 9.0, 0.1) is None


def test_spread_is_the_contracts_and_the_checks():
    runs = [100.0, 100.2, 100.4, 100.6, 100.8, 103.0]
    q = tool.statistics.quantiles(runs, n=4)
    assert tool.spread(runs) == pytest.approx((q[2] - q[0]) / 100.5)
    # the farthest run (103.0) left out: five runs 0.2 apart
    rest = tool.statistics.quantiles(runs[:-1], n=4)
    assert tool.spread_left_out(runs) == pytest.approx(
        (rest[2] - rest[0]) / 100.4)
    assert tool.spread_left_out(runs) < tool.spread(runs)
    # where leaving it out widens the quartiles, all runs count
    even = [100.0, 100.0, 100.0, 101.0, 101.0, 101.0]
    assert tool.spread_left_out(even) == pytest.approx(
        min(tool.spread(even), tool.spread(even[:-1])))


def stalled(ticks, at, seconds):
    """The same replay with tick ``at`` taking ``seconds`` longer."""
    return [(s + seconds, n) if k == at else (s, n)
            for k, (s, n) in enumerate(ticks)]


# a period of the replay below: 16 decode ticks of 1/64 s then 6 chunk ticks
# of 1/8 s, 32 tokens a tick: 1.0 s, 704 tokens; every sum is exact
PERIOD = [(1 / 64, 32)] * 16 + [(1 / 8, 32)] * 6


def test_a_stall_costs_the_rate_at_the_windows_end():
    """Forty periods; a stall of 1/8 s about 10 s in. The window of a
    stalled run holds 1/8 s less of the replay, cut off its END: a window
    that ends among decode ticks (2,048 tokens/s) loses eight times what
    one that ends among chunk ticks (256 tokens/s) loses."""
    clean = PERIOD * 40
    hit = stalled(clean, 22 * 10 + 3, 1 / 8)

    def loss(lead_in):
        a = tool.rate_at(*replay(clean), lead_in, 20.0)[0]
        b = tool.rate_at(*replay(hit), lead_in, 20.0)[0]
        assert a == 704.0
        return (a - b) / a
    # lead-in 5.125: the window ends 0.125 s into a period, after 8 of its 16
    # decode ticks; the stalled run's ends at the period's start: 8 ticks less
    assert loss(5.125) == pytest.approx(8 * 32 / (704 * 20))
    assert loss(5.125) == pytest.approx((1 / 8) * 2048 / (704 * 20))
    # lead-in 5.5: it ends 0.5 s into a period, after two chunk ticks; the
    # stalled run's after one
    assert loss(5.5) == pytest.approx(32 / (704 * 20))
    assert loss(5.5) == pytest.approx((1 / 8) * 256 / (704 * 20))
    # a stall BEFORE the window moves both edges: it costs the difference of
    # the two ends' rates (here none: both edges lie among chunk ticks)
    early = stalled(clean, 22 * 2 + 3, 1 / 8)
    assert tool.rate_at(*replay(early), 5.5, 20.0)[0] == pytest.approx(704.0)


def test_stalls_are_ticks_over_the_runs_median_and_rows_cover_the_records():
    ticks = PERIOD * 4

    def timeline(seed, these):
        starts, stamps = replay(these)
        ends = starts[1:] + [starts[-1] + these[-1][0]]
        return {"seed": seed, "lead_in_s": 1.0, "stamps": stamps, "gc": [],
                "ticks": [[a, b, 0.0, 0.0] for a, b in zip(starts, ends)]}
    # tick 30 is the second period's ninth decode tick: it starts at 1.125
    runs = [timeline(1, ticks), timeline(2, stalled(ticks, 30, 3 / 32)),
            timeline(3, ticks)]
    found = tool.stalls(runs)
    assert found[0] == [] and found[2] == []
    [(tick, at, over)] = found[1]
    assert (tick, at, over) == (30, 1.125, 3 / 32)
    rows = tool.by_lead_in(runs, 1.0, step=0.5, first=0.5)
    # a clean record ends at 4.0, where the driver's loop would close a
    # window: 3.0 + 1.0 is the last lead-in the records cover
    assert [r["lead_in"] for r in rows] == [0.5, 1.0, 1.5, 2.0, 2.5, 3.0]
    for r in rows:
        assert r["rates"][0] == r["rates"][2] == 704.0
        assert {"spread", "spread_left_out", "rate_at_start",
                "rate_at_end", "ticks"} <= set(r)
    # the stall lies before 1.5 s of the stalled run: its window from there on
    # holds the same second of the replay, 3/32 s later
    assert rows[2]["rates"][1] == 704.0
    # from 0.5 s on it holds the same second's tokens in 1 + 3/32 s
    assert rows[0]["rates"][1] == pytest.approx(704 / (1 + 3 / 32))


@pytest.mark.parametrize("cell", ["tiny.backlog", "tiny-trinity.backlog"])
def test_the_recorder_holds_the_run_the_driver_timed(cell, tmp_path):
    """``timeline.py record`` on a tiny cell, on the CPU: the record holds
    every tick and every stamp from the marks on, the driver's function and
    the family's reference are put back, and the driver's own rate is found
    again from the record."""
    import importlib
    from chipbench import run as harness
    driver = importlib.import_module(
        "chipbench.drivers." + harness.load("workloads", cell, CELLS)["driver"])
    before = driver._drive
    served = {m: m.served for m in vars(driver).values()
              if tool.inspect.ismodule(m) and hasattr(m, "served")}
    out = tmp_path / "timeline.json"
    code, result = tool.record(cell, 2 ** 31 + 17, 1.2, out, root=CELLS,
                               need_tpu=False, marks_from_s=0.3)
    assert code == 0 and result["correct"]
    assert driver._drive is before
    assert served and all(m.served is f for m, f in served.items())
    tl = json.loads(out.read_text())
    assert tl["workload"] == cell and tl["lead_in_s"] == 0.5
    starts = [t[0] for t in tl["ticks"]]
    assert starts == sorted(starts) and 0 <= starts[0] < 0.3
    assert all(b > a for a, b, *_ in tl["ticks"])
    assert tl["stamps"] == sorted(tl["stamps"])
    host = [t[2] for t in tl["ticks"]]
    assert host == sorted(host) and host[-1] > host[0]
    tops = tool.loop_tops(tl)
    assert tops[:-1] == starts and tops[-1] == tl["ticks"][-1][1]
    got, ticks = tool.rate_at(tops, tl["stamps"], 0.3, 1.2)
    # the driver reads its clock a few microseconds before a tick starts:
    # the two windows differ by at most a tick at an edge
    assert got == pytest.approx(
        result["metrics"]["serve_tokens_per_s"]["value"], rel=0.03)
    assert ticks > 20
    rows = tool.by_lead_in([tl], 1.0, step=0.1, first=0.3)
    assert rows and rows[0]["lead_in"] == 0.3 and "spread" not in rows[0]
