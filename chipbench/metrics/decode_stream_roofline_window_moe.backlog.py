"""How near a whole decode tick, host included, comes to the floor of what
it must move (``window_moe.decode_tick_bytes``): every weight outside the
routed experts once (attention, the dense MLP, the shared experts, the
routers, the head; the embedding is gathered, not streamed), the weights of
the experts the tick touched (``experts_hit``) and the live K/V of each
block space (``kv_blocks_window`` / ``kv_blocks_full`` x the block x a
token's K/V x that space's layers), the median over the traced
``serving.decode`` spans, over the median of the ``serving.step`` spans that
only decode, over the chip's published HBM bytes a second. None where the
spans carry no ``kv_blocks_window`` or the trace holds no tick that only
decodes."""
import numpy as np

import _spans
from _lib import percentile
from chipbench import hybrid, window_moe

UNIT = "%"


def read(run):
    events = _spans.program_events()
    got = percentile(_spans.tick_ms(events, prefill=False), 50)
    ticks = [a for a in window_moe.space_ticks(events) if "experts_hit" in a]
    if got is None or not ticks:
        return None
    ms, samples = got
    block = run["cell"]["engine"]["block_size"]
    moved = float(np.median([window_moe.decode_tick_bytes(
        run["config"], a["kv_blocks_window"], a["kv_blocks_full"], block,
        a["experts_hit"]) for a in ticks]))
    floor_s = moved / hybrid.peaks(run)["hbm_bytes_per_s"]
    return 100.0 * floor_s / (ms * 1e-3), samples
