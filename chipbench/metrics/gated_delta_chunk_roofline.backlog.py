"""The chunked delta-rule kernel's share of its roofline: over the traced
``exe.prefill*`` spans, the rule's work for the prompt tokens sent
(``useful`` x the span's ``state_layers`` x ``hybrid.py``'s FLOPs and bytes
a token a linear layer; the larger of the FLOP seconds at the chip's
published bf16 peak and the byte seconds at its HBM rate), over the seconds
of ``gated_delta_chunk`` in the reduced trace. None where the program has
no such kernel or its spans carry no ``state_layers``."""
import _spans
from chipbench import hybrid

UNIT = "%"
KERNEL = "gated_delta_chunk"


def read(run):
    seconds = hybrid.kernel_seconds(run, KERNEL)
    calls = [e["args"] for e in _spans.program_events()
             if e["name"].startswith("exe.prefill")
             and "state_layers" in e["args"]]
    if not seconds or not calls:
        return None
    cfg, peak = run["config"], hybrid.peaks(run)
    token_layers = sum(a["useful"] * a["state_layers"] for a in calls)
    floor_s = token_layers * max(
        hybrid.rule_flops_per_token(cfg) / peak["bf16_flops_per_s"],
        hybrid.rule_bytes_per_token(cfg) / peak["hbm_bytes_per_s"])
    return 100.0 * floor_s / seconds, len(calls)
