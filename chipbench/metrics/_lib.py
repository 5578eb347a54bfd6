"""What several readers share. A reader is ``read(run) -> value``, ``(value,
samples)`` or None where the run has nothing for it to read; ``run`` is the
record a driver returns (``drivers/serve.py: _drive``), with the reduced
trace under ``run["trace"]`` in a traced run."""
import numpy as np


def percentile(values, q):
    """-> (the q-th percentile, the sample count) or None without samples."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q)), len(values)


def tick_ms(run):
    return [(b - a) * 1e3 for a, b, *_ in run["ticks"]]


def pad_share(run):
    """Token-rows sent to the two prefill programs that carried no prompt
    token, over all token-rows sent, in percent."""
    sent = sum(size for _, size, _ in run["prefill_calls"])
    if not sent:
        return None
    used = sum(n for _, _, n in run["prefill_calls"])
    return 100.0 * (sent - used) / sent, len(run["prefill_calls"])


def idle_share(run):
    t = run.get("trace")
    if not t:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
