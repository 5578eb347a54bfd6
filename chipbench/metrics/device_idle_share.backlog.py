"""From the trace: 1 - union of device-op intervals over the traced window.
Half depth makes the host's share per tick about twice a deployment's."""
from _lib import idle_share as read

UNIT = "%"
