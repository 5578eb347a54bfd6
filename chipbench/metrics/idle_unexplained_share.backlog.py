"""``device_idle_share.backlog`` less ``exposed_share.backlog``, in points
of the window: each side summed on its own clock over the same recording.
What is left is the launch of a program after its enqueue, the return of a
fetch after the device's last operation, and whatever has no name yet."""
import _exposed
import _spans
from _lib import idle_share

UNIT = "%"


def read(run):
    idle = idle_share(run)
    exposed = _exposed.exposed_share(_spans.program_events())
    if idle is None or exposed is None:
        return None
    return idle - exposed
