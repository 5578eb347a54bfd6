from _lib import pad_share as read

UNIT = "%"
