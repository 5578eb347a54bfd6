"""Of the recorded window of whole ticks, the part in which the host's
clock says nothing was in flight: the starved intervals plus the dispatches
that ended them. What ``device_idle_share.backlog`` should read if launch
and wake latencies were nothing."""
import _exposed
import _spans

UNIT = "%"


def read(run):
    return _exposed.exposed_share(_spans.program_events())
