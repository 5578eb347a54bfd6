"""``trace.py`` on a trace recorded on a v5e (``_scratch`` script of PR 24:
four ``serving.step`` annotations, each three dispatches of a jitted 2048^3
bf16 matmul then a 20 ms sleep; 10 ms between steps; Python tracer off).
The numbers below were added up by hand from the 36 events of the device's
``XLA Ops`` line, which do not overlap."""
from pathlib import Path

import pytest

from chipbench import trace

SMALL = Path(__file__).parents[1] / "testdata" / "small.xplane.pb"


def test_union_of_intervals():
    total, merged = trace._union([(0, 10), (5, 20), (30, 40), (40, 45)])
    assert total == 35 and merged == [[0, 20], [30, 45]]


def test_op_name_drops_the_hlo_text_and_the_running_number():
    assert trace.op_name("%llama_decode_tick.80 = bf16[1]{0} custom-call("
                         "s32[16]{0} %x)") == "%llama_decode_tick"
    assert trace.op_name("%copy-done = bf16[2,2]{1,0} copy-done(%c)") == "%copy-done"


def test_recorded_trace_gives_the_hand_computed_busy_share():
    r = trace.reduce(str(SMALL))
    fusion, copy_done, copy_start = 1_091_047, 126_924, 157      # ns, by hand
    assert r["devices"] == 1
    assert r["busy_s"] == pytest.approx((fusion + copy_done + copy_start) * 1e-9)
    assert r["window_s"] == pytest.approx((143_562_387 - 45_245_084) * 1e-9)
    assert 100 * r["busy_s"] / r["window_s"] == pytest.approx(1.2390, abs=1e-4)
    assert r["device_ops"][0][0] == "%convolution_multiply_fusion"
    assert r["device_ops"][0][1] == pytest.approx(fusion * 1e-9)
    assert [n for n, _ in r["device_ops"]] == [
        "%convolution_multiply_fusion", "%copy-done", "%copy-start"]
    assert r["collective_s"] == 0.0
    # the three long gaps lie inside an annotated step (its sleep)
    assert [n for n, _ in r["idle_gaps"][:3]] == ["serving.step"] * 3
    assert all(0.031 < s < 0.033 for _, s in r["idle_gaps"][:3])
