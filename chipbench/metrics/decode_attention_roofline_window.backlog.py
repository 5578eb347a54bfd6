"""The paged decode-attention kernel's share of its roofline over BOTH
kinds of layer, which is its bytes (it reads every live K/V block once and
computes little): over the traced ``serving.decode`` spans, the blocks a
layer of each space walks (``kv_blocks_window``, ``kv_blocks_full``) x that
space's layers x the block's tokens x a token's K and V in one layer
(``window_moe.kv_read_bytes``), over the seconds of
``paged_decode_attention`` in the reduced trace, over the chip's published
HBM bytes a second: the same work whatever implements it. None where the
trace holds no such kernel or no span counts the spaces."""
import _spans
from chipbench import hybrid, window_moe

UNIT = "%"
KERNEL = "paged_decode_attention"


def read(run):
    seconds = hybrid.kernel_seconds(run, KERNEL)
    ticks = window_moe.space_ticks(_spans.program_events())
    if not seconds or not ticks:
        return None
    block = run["cell"]["engine"]["block_size"]
    read_bytes = sum(window_moe.kv_read_bytes(
        run["config"], a["kv_blocks_window"], a["kv_blocks_full"], block)
        for a in ticks)
    return (100.0 * read_bytes / seconds
            / hybrid.peaks(run)["hbm_bytes_per_s"], len(ticks))
