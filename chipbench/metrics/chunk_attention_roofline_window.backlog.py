"""The paged chunk-attention kernel's share of its roofline over BOTH kinds
of layer: over the traced ``exe.prefill_chunk`` spans that count each
space's blocks, each call's floor (the larger of its FLOPs at the bf16 peak:
the (query, key) pairs inside the window and under the causal edge,
``window_moe.window_keys`` / ``causal_keys``, x q.k and p.v of every head x
that kind's layers; and its K/V bytes at the HBM rate: ``kv_blocks_window``
/ ``kv_blocks_full`` x the block x a token's K and V x the layers), over
the seconds of ``paged_chunk_attention`` in the reduced trace: the same
work whatever implements it. None where the trace holds no such kernel or
no span counts the spaces."""
import _spans
from chipbench import hybrid, window_moe

UNIT = "%"
KERNEL = "paged_chunk_attention"


def read(run):
    seconds = hybrid.kernel_seconds(run, KERNEL)
    calls = window_moe.chunk_calls(_spans.program_events())
    if not seconds or not calls:
        return None
    cfg, peak = run["config"], hybrid.peaks(run)
    block, window = run["cell"]["engine"]["block_size"], cfg["sliding_window"]
    floor_s = sum(max(
        window_moe.attention_flops(
            cfg, window_moe.window_keys(off, n, window),
            window_moe.causal_keys(off, n)) / peak["bf16_flops_per_s"],
        window_moe.kv_read_bytes(cfg, bw, bf, block)
        / peak["hbm_bytes_per_s"]) for off, n, bw, bf in calls)
    return 100.0 * floor_s / seconds, len(calls)
