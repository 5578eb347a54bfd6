"""95th percentile of the benchmark's clock around ``engine.step()``."""
from _lib import percentile, tick_ms

UNIT = "ms"


def read(run):
    return percentile(tick_ms(run), 95)
