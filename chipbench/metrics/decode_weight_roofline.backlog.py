"""How near a whole decode tick, host included, comes to the floor its
weights set: the bytes of matmul weights a decode tick has to stream from
HBM (every layer's once a pass, the head's once: ``looped.py``) over the
median of the engine's ``serving.step`` spans that only decode, over the
chip's published HBM bytes a second."""
import _spans
from _lib import percentile

from chipbench import looped

UNIT = "%"


def read(run):
    got = percentile(_spans.tick_ms(_spans.program_events(), prefill=False),
                     50)
    if got is None:
        return None
    ms, samples = got
    floor_s = (looped.matmul_weight_bytes_per_tick(run["config"])
               / looped.hbm_bytes_per_s(run))
    return 100.0 * floor_s / (ms * 1e-3), samples
