"""How near a whole decode tick, host included, comes to the floor of what
it must move (``latent_moe.decode_tick_bytes``): every weight outside the
routed experts once (MLA, the dense MLP, the shared experts, the routers,
the head; the embedding is gathered, not streamed), the weights of the held
experts the tick touched (``experts_hit``) and the live latent rows
(``kv_blocks`` x the block x the model's row x ``cache_layers``), the
median over the traced ``serving.decode`` spans, over the median of the
``serving.step`` spans that only decode, over the chip's published HBM
bytes a second. None where the spans carry no ``experts_hit`` or the trace
holds no tick that only decodes."""
import numpy as np

import _spans
from _lib import percentile
from chipbench import hybrid, latent_moe

UNIT = "%"


def read(run):
    events = _spans.program_events()
    got = percentile(_spans.tick_ms(events, prefill=False), 50)
    ticks = [e["args"] for e in events
             if e["name"] == "serving.decode" and "experts_hit" in e["args"]
             and "kv_blocks" in e["args"]]
    if got is None or not ticks:
        return None
    ms, samples = got
    block = run["cell"]["engine"]["block_size"]
    moved = float(np.median([latent_moe.decode_tick_bytes(
        run["config"], a["kv_blocks"] * block, a["experts_hit"],
        a["cache_layers"]) for a in ticks]))
    floor_s = moved / hybrid.peaks(run)["hbm_bytes_per_s"]
    return 100.0 * floor_s / (ms * 1e-3), samples
