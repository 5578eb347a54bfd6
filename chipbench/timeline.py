"""What a served cell's rate is made of, tick by tick, and what it would
read at another lead-in:

    python chipbench/timeline.py record --workload <cell> --seed <n>
        --seconds <s> --out <file> [--verdict 1]
    python chipbench/timeline.py lead-in <file> [<file> ...] [--seconds 20]

``record`` is ``run.py``'s run (same driver, same traffic, same set-up)
with the window's marks set from 1 s of the traffic on, so that the record
holds nearly all of it: every tick's start and end on the driver's clock
with the engine's ``host_s`` and ``device_s`` after it, every token stamp,
every pass of Python's collector. The reference is skipped unless
``--verdict 1`` (``correct`` is then no verdict): a spread run needs none.
Ask for ``--seconds`` of the cell's lead-in + its window + what you want to
try beyond.

``lead-in`` recomputes ``serve_tokens_per_s`` from such records with the
driver's own arithmetic (the window opens at the first tick that starts at
or after the lead-in and closes at the first that starts ``--seconds``
later) for every lead-in the records cover, and prints by lead-in: each
run's rate, the runs' spread (between the quartiles over the median, and
with the run farthest from the median left out, as the check counts it)
and the rate the tokens come at around the window's two edges. The traffic
is a fixed replay, so runs differ by where time was lost: ``stalls`` lists
the ticks a run spent 20 ms longer in than the runs' median for that tick.
A stall of d seconds inside the window cuts the last d seconds of the
replay off it: it costs d x the rate at the window's END, which is why a
window that ends in a run of decode ticks spreads five times as widely as
one that ends in a long prompt's chunk calls (PERF.md section 6, PR 44).
"""
import time

T_PROCESS_START = time.perf_counter()

import argparse
import bisect
import gc
import importlib
import inspect
import json
import statistics
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

STALL_S = 0.020


# ---- record ---------------------------------------------------------------

def record(workload, seed, seconds, out, verdict=False, root=HERE,
           need_tpu=True, marks_from_s=1.0):
    """-> (exit code, result or None); the timeline is written to ``out``."""
    from chipbench import run as harness
    from chipbench.drivers.serve import clock
    cell = harness.load("workloads", workload, root)
    driver = importlib.import_module("chipbench.drivers." + cell["driver"])
    real = driver._drive
    patched = {}
    if not verdict:
        for mod in vars(driver).values():
            if inspect.ismodule(mod) and hasattr(mod, "served"):
                patched[mod] = mod.served
                mod.served = lambda *a, **k: {"correct": True,
                                              "reference": "skipped"}

    def drive(engine, reqs, cell, *rest):
        ticks, passes, began = [], [], [0.0]
        step = engine.step

        def logged():
            a = clock()
            got = step()
            ticks.append((a, clock(), engine.stats["host_s"],
                          engine.stats["device_s"]))
            return got

        def on_gc(phase, info):
            if phase == "start":
                began[0] = clock()
            else:
                passes.append((began[0], clock(), info["generation"]))

        engine.step = logged
        gc.callbacks.append(on_gc)
        t0 = clock()
        try:
            rec = real(engine, reqs, dict(cell, lead_in_s=marks_from_s),
                       *rest)
        finally:
            gc.callbacks.remove(on_gc)
            engine.step = step
        Path(out).write_text(json.dumps({
            "workload": workload, "seed": seed, "lead_in_s": cell["lead_in_s"],
            "ticks": [[x - t0 for x in t[:2]] + list(t[2:]) for t in ticks],
            "stamps": sorted(s - t0 for q in rec["requests"]
                             for s in q["stamps"]),
            "gc": [(a - t0, b - t0, g) for a, b, g in passes]}))
        return rec

    driver._drive = drive
    try:
        return harness.run_cell(workload, seed, seconds, False, root=root,
                                need_tpu=need_tpu, t_start=T_PROCESS_START)
    finally:
        driver._drive = real
        for mod, served in patched.items():
            mod.served = served


# ---- lead-in --------------------------------------------------------------

def rate_at(starts, stamps, lead_in, seconds):
    """The driver's rate for a window asked for at ``lead_in``: (tokens/s,
    the window's ticks), or None where the record ends before the window.
    ``starts`` are the loop's tops (``loop_tops``) and ``stamps`` the token
    stamps, both sorted, in seconds from the start of the traffic."""
    i = bisect.bisect_left(starts, lead_in)
    if i == len(starts):
        return None
    j = bisect.bisect_left(starts, starts[i] + seconds)
    if j == len(starts):
        return None
    w0, w1 = starts[i], starts[j]
    tokens = bisect.bisect_left(stamps, w1) - bisect.bisect_left(stamps, w0)
    return tokens / (w1 - w0), j - i


def loop_tops(timeline):
    """The instants at which the driver's loop could open or close a
    window: every tick's start, and the last tick's end (where the driver
    closed its own)."""
    ticks = timeline["ticks"]
    return [t[0] for t in ticks] + [ticks[-1][1]]


def rate_around(stamps, t, half=0.15):
    """Tokens a second over ``t - half .. t + half``."""
    return (bisect.bisect_left(stamps, t + half)
            - bisect.bisect_left(stamps, t - half)) / (2 * half)


def spread(values):
    """Between the quartiles over the median, as the contract counts it."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def spread_left_out(values):
    """``spread`` with the run farthest from the median left out where that
    narrows it, as the check counts a set of runs."""
    m = statistics.median(values)
    rest = sorted(values, key=lambda v: abs(v - m))[:-1]
    return min(spread(values), spread(rest)) if len(rest) > 1 \
        else spread(values)


def stalls(timelines, over_s=STALL_S):
    """By run: [(tick, seconds from the start, seconds over the runs'
    median for that tick), ...]. Needs three runs to tell a stall from the
    replay's own long ticks."""
    n = min(len(t["ticks"]) for t in timelines)
    dur = [[t["ticks"][k][1] - t["ticks"][k][0] for k in range(n)]
           for t in timelines]
    med = [statistics.median(d[k] for d in dur) for k in range(n)]
    return [[(k, t["ticks"][k][0], d[k] - med[k]) for k in range(n)
             if d[k] - med[k] > over_s] for t, d in zip(timelines, dur)]


def by_lead_in(timelines, seconds, step=0.1, first=None):
    """Rows ``{"lead_in", "rates", "ticks", "spread", "spread_left_out",
    "rate_at_start", "rate_at_end"}`` for every lead-in from ``first`` (the
    cell's own less 2 s by default) on that every record covers."""
    runs = [(loop_tops(tl), tl["stamps"]) for tl in timelines]
    lead = max(1.0, timelines[0]["lead_in_s"] - 2.0) if first is None \
        else first
    rows = []
    while True:
        got = [rate_at(a, s, lead, seconds) for a, s in runs]
        if any(g is None for g in got):
            return rows
        rates = [g[0] for g in got]
        row = {"lead_in": round(lead, 3), "rates": rates,
               "ticks": [g[1] for g in got],
               "rate_at_start": statistics.median(
                   rate_around(s, lead) for _, s in runs),
               "rate_at_end": statistics.median(
                   rate_around(s, lead + seconds) for _, s in runs)}
        if len(rates) > 1:
            row["spread"] = spread(rates) if len(rates) > 2 else \
                (max(rates) - min(rates)) / statistics.median(rates)
            row["spread_left_out"] = spread_left_out(rates) \
                if len(rates) > 3 else row["spread"]
        rows.append(row)
        lead += step


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="what", required=True)
    rec = sub.add_parser("record")
    rec.add_argument("--workload", required=True)
    rec.add_argument("--seed", type=int, required=True)
    rec.add_argument("--seconds", type=float, required=True)
    rec.add_argument("--out", required=True)
    rec.add_argument("--verdict", type=int, choices=(0, 1), default=0)
    lead = sub.add_parser("lead-in")
    lead.add_argument("files", nargs="+")
    lead.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    if args.what == "record":
        code, result = record(args.workload, args.seed, args.seconds,
                              args.out, bool(args.verdict))
        if result is not None:
            print(json.dumps(result), flush=True)
        return code
    timelines = [json.loads(Path(f).read_text()) for f in args.files]
    if len(timelines) > 2:
        for tl, found in zip(timelines, stalls(timelines)):
            print(json.dumps({"seed": tl["seed"], "stalls": [
                {"tick": k, "at_s": round(at, 2), "over_ms": round(1e3 * d, 1)}
                for k, at, d in found],
                "longest_gc_ms": round(1e3 * max(
                    (b - a for a, b, _ in tl["gc"]), default=0.0), 2)}))
    for row in by_lead_in(timelines, args.seconds):
        print(json.dumps({k: ([round(x, 2) for x in v] if k == "rates" else
                              round(v, 5) if isinstance(v, float) else v)
                          for k, v in row.items()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
