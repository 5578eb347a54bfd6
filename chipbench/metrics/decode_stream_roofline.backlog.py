"""How near a whole decode tick, host included, comes to the floor of what
it must move (``hybrid.decode_tick_bytes``): the matmul weights of both
layer kinds and the head once, the live K/V (``kv_blocks`` x the block x
``cache_layers``) and the running slots' recurrent state read and written
(``state_slots`` x ``state_layers``), the medians over the traced
``serving.decode`` spans, over the median of the ``serving.step`` spans
that only decode, over the chip's published HBM bytes a second. None where
the spans carry no ``state_slots``: the program is not a hybrid's."""
import numpy as np

import _spans
from _lib import percentile
from chipbench import hybrid

UNIT = "%"


def read(run):
    events = _spans.program_events()
    got = percentile(_spans.tick_ms(events, prefill=False), 50)
    ticks = [e["args"] for e in events
             if e["name"] == "serving.decode" and "state_slots" in e["args"]]
    if got is None or not ticks:
        return None
    ms, samples = got
    block = run["cell"]["engine"]["block_size"]
    moved = float(np.median([hybrid.decode_tick_bytes(
        run["config"], a["kv_blocks"] * block, a["state_slots"],
        a["cache_layers"], a["state_layers"]) for a in ticks]))
    floor_s = moved / hybrid.peaks(run)["hbm_bytes_per_s"]
    return 100.0 * floor_s / (ms * 1e-3), samples
