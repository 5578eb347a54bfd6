"""Of the decode ticks' time, the part the engine spent on the host
(``engine.stats``: ``host_s`` over ``host_s + device_s``, where ``device_s``
is host time waiting for the tick's tokens, not device busy time)."""
UNIT = "%"


def read(run):
    s = run["stats"]
    total = s["host_s"] + s["device_s"]
    return 100.0 * s["host_s"] / total if total > 0 else None
