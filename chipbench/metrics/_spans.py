"""What the readers of the program's own spans and kernel names share.

The program marks its intervals with ``paddle_tpu.observability.span``; a
span records while a ``jax.profiler`` trace runs, so in a ``--trace 1`` run
the process's ``TRACER`` buffer holds the spans of exactly the profiled
seconds, the period of the device metrics. Each event carries ``name``,
``ts`` and ``dur`` (microseconds on ``time.perf_counter``'s clock), ``cat``
(``"device_wait"`` where the host waits for the device), an ``id``, the
``parent`` id and ``args``. A program without such spans (the parent of the
PR that brought them) leaves the buffer empty, and every reader returns None.
None of this reads the benchmark's clock.
"""
def program_events():
    """The span events of this process, oldest first."""
    try:
        from paddle_tpu.observability import TRACER
    except ImportError:
        return []
    return [e for e in TRACER.export()["traceEvents"]
            if e.get("ph") == "X" and "id" in e]


def _children(events):
    kids = {}
    for e in events:
        kids.setdefault(e.get("parent"), []).append(e)
    return kids


def descendants(events, root, kids=None):
    """Every span below ``root`` (``kids``: ``_children(events)``, where
    the caller asks for several roots)."""
    kids = _children(events) if kids is None else kids
    out, todo = [], [root["id"]]
    while todo:
        for e in kids.get(todo.pop(), ()):
            out.append(e)
            todo.append(e["id"])
    return out


def ticks(events):
    """[(the ``serving.step`` span, every span below it), ...]."""
    kids = _children(events)
    return [(e, descendants(events, e, kids)) for e in events
            if e["name"] == "serving.step"]


def _prefilled(inside):
    return any(e["name"].startswith("exe.prefill") for e in inside)


def tick_ms(events, prefill: bool):
    """Durations in ms of the ticks that sent a batch to one of the two
    prefill programs (``prefill``), or of those that did not."""
    return [step["dur"] * 1e-3 for step, inside in ticks(events)
            if _prefilled(inside) == prefill]


def _union_us(intervals):
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total, end = total + (e - s), e
        elif e > end:
            total, end = total + (e - end), e
    return total


def tick_host_ms(events):
    """Per tick: its duration less the union of the ``device_wait`` spans
    below it (clipped to the tick), in ms."""
    out = []
    for step, inside in ticks(events):
        t0, t1 = step["ts"], step["ts"] + step["dur"]
        waits = [(max(t0, e["ts"]), min(t1, e["ts"] + e["dur"]))
                 for e in inside if e.get("cat") == "device_wait"]
        out.append((step["dur"] - _union_us([w for w in waits
                                             if w[1] > w[0]])) * 1e-3)
    return out


def pad_row_share(events):
    """Of the token-rows the two prefill programs were sent (``rows`` of the
    ``exe.prefill*`` spans), the share that carried no prompt token (``rows``
    less ``useful``), in percent, and the number of calls."""
    sent = [e["args"] for e in events if e["name"].startswith("exe.prefill")]
    rows = sum(a["rows"] for a in sent)
    if not rows:
        return None
    return 100.0 * (rows - sum(a["useful"] for a in sent)) / rows, len(sent)


def step_dispatch_ms(events):
    """Durations in ms of the ``train.step`` spans: the train step's call
    (``InstrumentedJit.__call__``), which returns once the compiled step is
    dispatched."""
    return [e["dur"] * 1e-3 for e in events if e["name"] == "train.step"]


def kernel_share(run, kernel: str):
    """Device seconds of the operation the trace prints as ``kernel`` over
    the device's busy seconds, in percent; None on an untraced run and where
    no operation of that name is among the reduced trace's ``device_ops``."""
    t = run.get("trace")
    if not t or not t.get("busy_s"):
        return None
    for name, seconds in t["device_ops"]:
        if name.lstrip("%") == kernel:
            return 100.0 * seconds / t["busy_s"]
    return None
