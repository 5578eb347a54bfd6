"""What the readers of the host's exposed time share: from the program's
own spans, on the host's clock alone, the intervals in which nothing was in
flight on the device, and which span each of their microseconds belongs to.

The program marks every jitted call of its serving executor with a span
``exe.dispatch`` (``args``: ``program``, and ``seq``, the executor's
running count of programs dispatched) and names on every ``device_wait``
span the ``seq`` it waited for. A forward of the model (``FORWARDS``) keeps
the device busy for milliseconds; every other program (the key's split, a
block copy, a state snapshot, the sampler of a prompt's first token) is
microseconds behind one. So nothing is in flight from the end of a wait
whose ``seq`` is at or past the newest forward's until the next forward's
``exe.dispatch`` begins: a **starved interval**. The host's work in it is
exposed: the device idles until the dispatch that ends it has enqueued its
program. In the pipelined loop a wait names an older ``seq`` and no
interval opens. A program without these spans (the parent of the PR that
brought them) gives every reader here None.

Times are the spans' own (microseconds, ``time.perf_counter``'s clock); no
stamp of the device's clock is compared with one of the host's.
"""
import bisect

import numpy as np

import _spans

FORWARDS = ("tick", "prefill", "chunk", "verify")
BETWEEN = "between_steps"          # the caller's own time: under no span


def _end(e):
    return e["ts"] + e["dur"]


def has_edges(events) -> bool:
    return any(e["name"] == "exe.dispatch" for e in events)


def starved(events):
    """-> [(begin, end, the forward's ``exe.dispatch`` that ended it), ...]
    in time order."""
    edges = []
    for e in events:
        args = e.get("args", {})
        if e["name"] == "exe.dispatch" and args["program"] in FORWARDS:
            edges.append((e["ts"], 1, e))
        elif e.get("cat") == "device_wait" and "seq" in args:
            edges.append((_end(e), 0, e))
    out, newest, since = [], None, None
    for t, is_dispatch, e in sorted(edges, key=lambda x: x[:2]):
        if is_dispatch:
            if since is not None:
                out.append((since, t, e))
            newest, since = e["args"]["seq"], None
        elif newest is not None and since is None \
                and e["args"]["seq"] >= newest:
            since = t
    return out


def window(events):
    """(begin of the first ``serving.step``, end of the last), or None."""
    steps = [e for e in events if e["name"] == "serving.step"]
    if not steps:
        return None
    return min(e["ts"] for e in steps), max(_end(e) for e in steps)


def _label(e):
    if e["name"] == "exe.dispatch":
        return "exe.dispatch." + e["args"]["program"]
    return e["name"]


def self_segments(events):
    """The spans of the ticks' thread flattened: [(begin, end, name), ...]
    in time order, each stretch under the innermost span that covers it (a
    span's self time is what its children leave of it)."""
    steps = [e for e in events if e["name"] == "serving.step"]
    if not steps:
        return []
    tid = steps[0].get("tid")
    mine = [e for e in events if e.get("tid") == tid]
    ids = {e["id"] for e in mine}
    kids = {}
    for e in sorted(mine, key=lambda e: e["ts"]):
        parent = e.get("parent")
        kids.setdefault(parent if parent in ids else None, []).append(e)
    out = []

    def walk(e):
        cursor = e["ts"]
        for c in kids.get(e["id"], ()):
            if c["ts"] > cursor:
                out.append((cursor, c["ts"], _label(e)))
            walk(c)
            cursor = max(cursor, _end(c))
        if _end(e) > cursor:
            out.append((cursor, _end(e), _label(e)))

    for top in kids.get(None, ()):
        walk(top)
    return out


def by_span(segments, begins, t0, t1):
    """{name: microseconds of [t0, t1] under that span and none below it},
    ``BETWEEN`` for what no span covers. ``begins``: the segments' begins."""
    got, covered = {}, 0.0
    i = max(0, bisect.bisect_right(begins, t0) - 1)
    while i < len(segments) and segments[i][0] < t1:
        a, b, name = segments[i]
        over = min(b, t1) - max(a, t0)
        if over > 0:
            got[name] = got.get(name, 0.0) + over
            covered += over
        i += 1
    if t1 - t0 - covered > 1e-9:
        got[BETWEEN] = t1 - t0 - covered
    return got


def _step_of(e, by_id):
    while e is not None and e["name"] != "serving.step":
        e = by_id.get(e.get("parent"))
    return e


def per_tick(events, prefill: bool):
    """For each tick that ran a prefill program (``prefill``) or ran none:
    (its starved microseconds, their split by span). A tick's intervals are
    those its own forwards' dispatches ended; a tick none of whose
    dispatches ended one (the pipelined loop; the first of a recording) is
    left out."""
    by_id = {e["id"]: e for e in events}
    segments = self_segments(events)
    begins = [s[0] for s in segments]
    ticks = {}
    for t0, t1, sent in starved(events):
        step = _step_of(sent, by_id)
        if step is None:
            continue
        entry = ticks.setdefault(step["id"], [0.0, {}])
        entry[0] += t1 - t0
        for name, us in by_span(segments, begins, t0, t1).items():
            entry[1][name] = entry[1].get(name, 0.0) + us
    ran_prefill = {step["id"] for step, inside in _spans.ticks(events)
                   if _spans._prefilled(inside)}
    return [(total, split) for sid, (total, split) in ticks.items()
            if (sid in ran_prefill) == prefill]


def tick_reading(events, prefill: bool):
    """-> (median starved ms a tick, its note) or None. The note: the
    sample count and, by span, the median and the mean self time a tick in
    those intervals, in ms (the means add up to the mean a tick)."""
    if not has_edges(events):
        return None
    got = per_tick(events, prefill)
    if not got:
        return None
    names = sorted({n for _, split in got for n in split})
    cols = {n: np.array([split.get(n, 0.0) for _, split in got]) * 1e-3
            for n in names}
    totals = np.array([t for t, _ in got]) * 1e-3
    note = {"n": len(got), "mean_ms": float(totals.mean()),
            "by_span_ms_p50": {n: float(np.median(c))
                               for n, c in cols.items()},
            "by_span_ms_mean": {n: float(c.mean()) for n, c in cols.items()}}
    return float(np.median(totals)), note


def exposed_share(events):
    """Starved intervals plus the dispatches that ended them (each began
    with nothing in flight), over the window of whole ticks, in percent;
    None without the dispatch edges."""
    w = window(events)
    if w is None or not has_edges(events):
        return None
    total = sum((t1 - t0) + sent["dur"] for t0, t1, sent in starved(events)
                if t0 >= w[0] and _end(sent) <= w[1])
    return 100.0 * total / (w[1] - w[0])


def gc_pauses(events):
    """-> (the collector's share of the window in percent, {"passes":
    {generation: count}, "longest_ms"}) or None without the edges."""
    w = window(events)
    if w is None or not has_edges(events):
        return None
    inside = [e for e in events if e["name"] == "host.gc"
              and e["ts"] >= w[0] and _end(e) <= w[1]]
    passes = {}
    for e in inside:
        g = str(e["args"]["generation"])
        passes[g] = passes.get(g, 0) + 1
    note = {"passes": passes,
            "longest_ms": max((e["dur"] for e in inside), default=0.0) * 1e-3}
    return 100.0 * sum(e["dur"] for e in inside) / (w[1] - w[0]), note


def step_self_ms(events, prefill=False):
    """Self time in ms of each ``serving.step`` that ran a prefill program
    (``prefill``) or none: its duration less its children's."""
    return [(step["dur"] - sum(e["dur"] for e in inside
                               if e["parent"] == step["id"])) * 1e-3
            for step, inside in _spans.ticks(events)
            if _spans._prefilled(inside) == prefill]
