"""90th percentile, over the requests due in the window, of first token
(stamped by the benchmark's stream callback) minus the instant the request
was due. A request with no token by the end of the drain has no sample and
is counted in ``failed``."""
from _lib import percentile

UNIT = "ms"


def read(run):
    return percentile([(q["stamps"][0] - q["due"]) * 1e3
                       for q in run["requests"] if q["stamps"]], 90)
