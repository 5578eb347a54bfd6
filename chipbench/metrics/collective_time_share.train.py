"""From the trace: the union of device 0's all-gather, reduce-scatter,
all-reduce, collective-permute and all-to-all intervals over the traced
window, hidden behind compute or not."""
UNIT = "%"


def read(run):
    t = run.get("trace")
    return 100.0 * t["collective_s"] / t["window_s"] if t else None
