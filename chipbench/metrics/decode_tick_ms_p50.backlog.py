"""Median of the engine's own ``serving.step`` spans that hold no
``exe.prefill`` or ``exe.prefill_chunk`` span: the ticks that only decode."""
import _spans
from _lib import percentile

UNIT = "ms"


def read(run):
    return percentile(_spans.tick_ms(_spans.program_events(),
                                    prefill=False), 50)
