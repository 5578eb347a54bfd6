"""Median of the engine's own ``serving.step`` spans that hold an
``exe.prefill`` or ``exe.prefill_chunk`` span: the ticks that also send a
padded batch to a prefill program."""
import _spans
from _lib import percentile

UNIT = "ms"


def read(run):
    return percentile(_spans.tick_ms(_spans.program_events(),
                                    prefill=True), 50)
