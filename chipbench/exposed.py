"""Where a serving tick's idle time goes, from one run of a served cell:

    python chipbench/exposed.py --workload <cell> --seed <n> --seconds <s>
        --record <tracer|profiler>

The run is ``run.py``'s (same driver, same window, same set-up); what
differs is what records the program's spans over the last ``trace_seconds``
of the window:

    tracer    the program's own buffer alone (``TRACER.enable()`` where the
              driver would start the profiler): about 4 us a span, the mode
              nearest the untraced run the cell's rate comes from. No device
              trace, so no idle share.
    profiler  ``jax.profiler``, as ``--trace 1``: the device's idle share,
              and the profiler's Python tracer on every host frame (host
              work reads up to three times its untraced size).

Last line: the cell's rate and ticks, the six readers of the host's exposed
time (``metrics/_exposed.py``) with their notes, the median self time of
``serving.step``, the longest starved intervals with their split by span,
and the longest waits beside their kind's median. No cell lists these
readers yet, so ``run.py`` does not print them. A program without the
dispatch edges prints the rate and the ticks alone.
"""
import time

T_PROCESS_START = time.perf_counter()

import argparse
import importlib
import json
import shutil
import statistics
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

READERS = ("tick_exposed_ms_p50.backlog", "tick_dispatch_ms_p50.backlog",
           "prefill_exposed_ms_p50.backlog", "exposed_share.backlog",
           "idle_unexplained_share.backlog", "gc_pause_share.backlog")
ALSO = ("serve_tokens_per_s", "device_idle_share.backlog",
        "host_share_of_tick.backlog", "decode_tick_ms_p50.backlog",
        "prefill_tick_ms_p50.backlog", "tick_host_ms_p50.backlog")


def programs_busy_s(path):
    """Seconds in which a program was running on device 0 (the union of
    the trace's ``XLA Modules`` intervals), or None without that line.
    The window less this is what the device idles BETWEEN programs, which
    is what the host's clock can explain; this less ``busy_s`` is the
    device's own gaps between the operations of a running program."""
    from jax.profiler import ProfileData
    from chipbench import trace as tr
    plane = tr._device_planes(ProfileData.from_file(path))[0]
    line = tr._line(plane, tr.MODULE_LINE)
    if line is None:
        return None
    total, _ = tr._union([(s, e) for _, s, e in tr._events(line)])
    return total * 1e-9


def record(workload, seed, seconds, how, root=HERE, need_tpu=True,
           t_start=None):
    """-> (exit code, what the last line holds or None). ``need_tpu=False``
    is for the tests, which drive a tiny cell on the CPU with ``tracer``."""
    from chipbench import run as harness
    cell = harness.load("workloads", workload, root)
    cfg = harness.load("configs", cell["config"], root)
    mix = harness.load("traffic", cell["traffic"], root)
    import jax
    devs = jax.devices()
    if need_tpu and devs[0].platform != "tpu":
        print(f"chipbench: needs a TPU, found {devs[0].platform}",
              file=sys.stderr)
        return 2, None
    if need_tpu:
        from paddle_tpu.core.device import enable_compilation_cache
        enable_compilation_cache()
    seen = harness.listen()
    from paddle_tpu.observability import TRACER
    driver = importlib.import_module("chipbench.drivers." + cell["driver"])
    trace_dir = tempfile.mkdtemp(prefix="chipbench_exposed_")
    start, stop = jax.profiler.start_trace, jax.profiler.stop_trace
    if how == "tracer":          # where the driver starts and stops a trace
        jax.profiler.start_trace = lambda *a, **kw: TRACER.enable()
        jax.profiler.stop_trace = TRACER.disable
    try:
        run = driver.run(cell, cfg, mix, seed, seconds, trace_dir,
                         T_PROCESS_START if t_start is None else t_start,
                         harness.note, lambda: seen["events"])
        if how == "profiler":
            from chipbench import trace as tr
            path = tr.find_xplane(trace_dir)
            run["trace"] = tr.reduce(path,
                                     host_spans=tuple(cell["host_spans"]))
            run["trace"]["programs_busy_s"] = programs_busy_s(path)
    finally:
        jax.profiler.start_trace, jax.profiler.stop_trace = start, stop
        shutil.rmtree(trace_dir, ignore_errors=True)

    def value(name):
        got = harness.reader(name).read(run)
        if got is None:
            return None
        v, note = got if isinstance(got, tuple) else (got, None)
        return {"value": float(v), **({"note": note} if note is not None
                                      else {})}

    exposed = harness.reader("_exposed")
    events = harness.reader("_spans").program_events()
    segments = exposed.self_segments(events)
    begins = [s[0] for s in segments]
    longest = sorted(exposed.starved(events),
                     key=lambda i: i[0] - i[1])[:5]
    selfs = exposed.step_self_ms(events)
    # a wait far past its kind's median with a program in flight is the
    # device's or the runtime's stall, not the host's: no interval holds it
    sent_as = {e["args"]["seq"]: e["args"]["program"] for e in events
               if e["name"] == "exe.dispatch"}
    waits = sorted((e for e in events if e.get("cat") == "device_wait"),
                   key=lambda e: -e["dur"])

    def median_ms(name):
        return statistics.median(
            w["dur"] for w in waits if w["name"] == name) * 1e-3

    out = {"workload": workload, "seed": seed, "record": how,
           "device": {"platform": devs[0].platform,
                      "kind": devs[0].device_kind},
           "correct": bool(run["correct"]), "ticks": len(run["ticks"]),
           "setup_s": run["setup_s"], "spans": len(events),
           "metrics": {n: value(n) for n in ALSO + READERS},
           "step_self_ms_p50": statistics.median(selfs) if selfs else None,
           "longest_starved": [
               {"ms": (t1 - t0) * 1e-3,
                "ends_at": sent["args"]["program"],
                "by_span_ms": {n: us * 1e-3 for n, us in sorted(
                    exposed.by_span(segments, begins, t0, t1).items(),
                    key=lambda kv: -kv[1])}}
               for t0, t1, sent in longest],
           "longest_waits": [
               {"ms": e["dur"] * 1e-3, "name": e["name"],
                "for": sent_as.get(e.get("args", {}).get("seq")),
                "median_ms_of_its_name": median_ms(e["name"])}
               for e in waits[:3]]}
    if run.get("trace"):
        t = run["trace"]
        out["idle_gaps"] = t["idle_gaps"]
        out["device_trace"] = {k: t[k] for k in (
            "window_s", "busy_s", "programs_busy_s")}
    return 0, out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--record", choices=("tracer", "profiler"),
                    required=True)
    args = ap.parse_args(argv)
    code, out = record(args.workload, args.seed, args.seconds, args.record)
    if out is not None:
        print(json.dumps(out), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
