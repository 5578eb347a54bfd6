"""From a profiler trace (``.xplane.pb``) to what this benchmark reads from
it: device busy seconds, the operations with most device time under the
names the trace prints, collective time, and the longest idle gaps with the
host span that covers each. Read with ``jax.profiler.ProfileData`` alone.

A TPU's plane is named ``/device:TPU:<n>``. Its line ``XLA Ops`` holds one
event per operation that ran on the device; ``XLA Modules`` one per program;
``Steps`` the profiler's own step markers. Busy time is the union of the
``XLA Ops`` intervals (the module line where a plane has no op line). Host
threads are lines of the ``/host:CPU`` plane; spans written by
``jax.profiler.TraceAnnotation`` sit there under their own names.
"""
import glob
import os

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all")
OP_LINE, MODULE_LINE = "XLA Ops", "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _union(intervals):
    """Total length and the merged intervals of [(start, end), ...]."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def _device_planes(data):
    return sorted((p for p in data.planes if p.name.startswith("/device:TPU:")),
                  key=lambda p: int(p.name.rsplit(":", 1)[1]))


def _line(plane, name):
    for line in plane.lines:
        if line.name == name:
            return line
    return None


def _events(line):
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for ev in line.events]


def op_name(printed: str) -> str:
    """The trace prints an operation as its whole HLO line. Its name is what
    stands before `` = ``; the compiler's running number goes, so that the
    sixteen layers' calls of one kernel count as one operation."""
    name = printed.split(" = ", 1)[0].strip()
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def reduce(path: str, host_spans=("serving.step",), top=10, gaps=5) -> dict:
    """-> {"devices", "window_s", "busy_s" (mean over devices), "busy_s_by_
    device", "device_ops" [[name, s]...] of device 0, "collective_s" (union
    on device 0), "idle_gaps" [[what the host was doing, s]...]}. The window
    is from the first to the last device event over all device planes."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = _device_planes(data)
    if not planes:
        raise ValueError(f"{path}: no /device:TPU plane "
                         f"(planes: {[p.name for p in data.planes]})")
    per_dev = []
    for p in planes:
        line = _line(p, OP_LINE) or _line(p, MODULE_LINE)
        per_dev.append(_events(line) if line is not None else [])
    if not per_dev[0]:
        raise ValueError(f"{path}: no operation ran on device 0")
    t0 = min(s for evs in per_dev for _, s, _ in evs)
    t1 = max(e for evs in per_dev for _, _, e in evs)
    busy, merged0 = [], None
    for evs in per_dev:
        total, merged = _union([(s, e) for _, s, e in evs])
        busy.append(total * 1e-9)
        merged0 = merged if merged0 is None else merged0

    by_name = {}
    for name, s, e in per_dev[0]:
        name = op_name(name)
        by_name[name] = by_name.get(name, 0) + (e - s)
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    coll, _ = _union([(s, e) for name, s, e in per_dev[0]
                      if any(c in name for c in COLLECTIVES)])

    idle = [(b[0] - a[1], a[1], b[0]) for a, b in zip(merged0, merged0[1:])]
    idle.sort(reverse=True)
    idle = idle[:gaps]
    # what the host was doing in each gap: the host event that overlaps the
    # gap most (the device's clock and the host's differ by about a
    # millisecond, so a span rarely covers a gap to the nanosecond); among
    # equals the shortest, which is the innermost frame (the profiler's
    # Python tracer names a function ``$file:line name``). A span named in
    # ``host_spans`` wins if it overlaps at least half the gap.
    cover = [None] * len(idle)          # (name, -overlap or -inf, duration)
    for p in data.planes:
        if not p.name.startswith("/host:CPU"):
            continue
        for line in p.lines:
            for n, hs, he in _events(line):
                for k, (length, gs, ge) in enumerate(idle):
                    over = min(he, ge) - max(hs, gs)
                    if over <= 0:
                        continue
                    if n in host_spans and over >= length / 2:
                        over = float("inf")
                    key = (-over, he - hs)
                    if cover[k] is None or key < cover[k][1:]:
                        cover[k] = (n, *key)
    named = [[c[0] if c else "no host event", length * 1e-9]
             for c, (length, _, _) in zip(cover, idle)]
    return {"devices": len(planes), "window_s": (t1 - t0) * 1e-9,
            "busy_s": sum(busy) / len(busy), "busy_s_by_device": busy,
            "device_ops": [[n, d * 1e-9] for n, d in ops],
            "collective_s": coll * 1e-9, "idle_gaps": named}
