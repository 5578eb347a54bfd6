"""Median duration of ``exe.dispatch(program=tick)``: the decode tick's
jitted call alone (flatten, upload, enqueue), which begins with nothing in
flight in the synchronous loop."""
import _spans
from _lib import percentile

UNIT = "ms"


def read(run):
    return percentile([e["dur"] * 1e-3 for e in _spans.program_events()
                       if e["name"] == "exe.dispatch"
                       and e["args"]["program"] == "tick"], 50)
