"""Seconds of the ``host.gc`` spans over the recorded window of whole ticks;
the note line counts the passes by generation and gives the longest."""
import _exposed
import _spans

UNIT = "%"


def read(run):
    return _exposed.gc_pauses(_spans.program_events())
