"""The grouped expert products' share of their roofline, for a model whose
expert layers hold every expert: over the traced calls that say what they
routed (``window_moe.routed_calls``), each call's floor
(``window_moe.grouped_floor_seconds``: the larger of ``routed_pairs`` x 2 x
3 x hidden x width FLOPs at the bf16 peak and ``experts_hit`` x an expert's
weights at the HBM rate), over the seconds of ``grouped_matmul`` in the
reduced trace: ``grouped_matmul_roofline.backlog``'s floor on this
configuration's keys. None where the trace holds no such kernel or no span
carries the counts."""
import _spans
from chipbench import hybrid, window_moe

UNIT = "%"
KERNEL = "grouped_matmul"


def read(run):
    seconds = hybrid.kernel_seconds(run, KERNEL)
    routed = window_moe.routed_calls(_spans.program_events())
    if not seconds or not routed or "num_experts" not in run["config"]:
        return None
    peak = hybrid.peaks(run)
    floor_s = sum(window_moe.grouped_floor_seconds(run["config"], p, h, peak)
                  for p, h in routed)
    return 100.0 * floor_s / seconds, len(routed)
