"""The grouped expert products' share of their roofline: over the traced
calls that say what they routed (``latent_moe.routed_calls``), each call's
floor (``latent_moe.grouped_floor_seconds``: the larger of ``routed_pairs``
x 2 x 3 x hidden x width FLOPs at the bf16 peak and ``experts_hit`` x an
expert's weights at the HBM rate), over the seconds of ``grouped_matmul``
in the reduced trace. The same work is read whatever implements it. None
where the trace holds no such kernel or no span carries the counts."""
import _spans
from chipbench import hybrid, latent_moe

UNIT = "%"
KERNEL = "grouped_matmul"


def read(run):
    seconds = hybrid.kernel_seconds(run, KERNEL)
    routed = latent_moe.routed_calls(_spans.program_events())
    if not seconds or not routed:
        return None
    peak = hybrid.peaks(run)
    floor_s = sum(latent_moe.grouped_floor_seconds(run["config"], p, h, peak)
                  for p, h in routed)
    return 100.0 * floor_s / seconds, len(routed)
