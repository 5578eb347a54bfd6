"""Median of the benchmark's clock from a step's call to the return of
``loss.block_until_ready()``."""
from _lib import percentile

UNIT = "ms"


def read(run):
    return percentile([(b - a) * 1e3 for a, b, _ in run["steps"]], 50)
