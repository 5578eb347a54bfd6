"""Median over ticks of the ``serving.step`` span's duration less the union
of the ``device_wait`` spans below it (``exe.sample``, ``serving.fetch``):
what a tick costs beyond waiting for the device."""
import _spans
from _lib import percentile

UNIT = "ms"


def read(run):
    return percentile(_spans.tick_host_ms(_spans.program_events()), 50)
