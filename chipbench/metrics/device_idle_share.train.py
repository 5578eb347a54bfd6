"""From the trace: 1 - union of device-op intervals over the traced window,
averaged over the chips."""
from _lib import idle_share as read

UNIT = "%"
