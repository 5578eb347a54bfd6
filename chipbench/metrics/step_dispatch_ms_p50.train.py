"""Median of the program's ``train.step`` span around the step's call
(``InstrumentedJit.__call__``: signature pass, cache lookup, dispatch of
the compiled step; it returns before the device is done)."""
import _spans
from _lib import percentile

UNIT = "ms"


def read(run):
    return percentile(_spans.step_dispatch_ms(_spans.program_events()), 50)
