"""Per decode-only tick, the starved interval its decode dispatch ended: from
the end of the wait that left nothing in flight (the tick before's fetch) to
the begin of the tick's ``exe.dispatch(program=tick)``; the median over
ticks. The note line splits it by span (``_exposed.tick_reading``): what
each name costs a tick with the device idle."""
import _exposed
import _spans

UNIT = "ms"


def read(run):
    return _exposed.tick_reading(_spans.program_events(), prefill=False)
