"""One run of one cell:

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process. Needs a TPU and as many devices as the cell's ``chips``;
anything else exits non-zero and prints no result. Everything about a cell
is found by name: ``workloads/<cell>.json`` names its configuration
(``configs/<name>.json``), its driver (``drivers/<name>.py``), its traffic
mix (``traffic/<mix>.json``, which names its generator) and the metrics it
reports (``metrics/<metric>.py``). Earlier lines of output are one JSON
object each; the last line is the result.
"""
import time

T_PROCESS_START = time.perf_counter()

import argparse
import importlib.util
import json
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


def note(**kw):
    print(json.dumps(kw), flush=True)


def load(kind: str, name: str, root: Path = HERE) -> dict:
    path = root / kind / f"{name}.json"
    if not path.is_file():
        raise SystemExit(f"chipbench: no {kind[:-1]} file {path}")
    return json.loads(path.read_text())


def reader(name: str):
    """The metric's own file: ``UNIT`` and ``read(run)`` (see
    ``metrics/_lib.py``)."""
    path = HERE / "metrics" / f"{name}.py"
    if str(path.parent) not in sys.path:
        sys.path.insert(0, str(path.parent))
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def listen():
    """What JAX itself reports it spent tracing, lowering and compiling (or
    fetching from the persistent cache), as a count of events and seconds."""
    import jax.monitoring as mon
    seen = {"events": 0, "seconds": 0.0, "cache_hits": 0, "cache_misses": 0}

    def on_duration(event, seconds, **_):
        if event in COMPILE_EVENTS:
            seen["events"] += 1
            seen["seconds"] += seconds

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            seen["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            seen["cache_misses"] += 1

    mon.register_event_duration_secs_listener(on_duration)
    mon.register_event_listener(on_event)
    return seen


def run_cell(workload, seed, seconds, trace, root=HERE, need_tpu=True,
             t_start=None):
    """-> (exit code, result or None). ``need_tpu=False`` is for the tests
    under ``chipbench/tests`` only: they drive a tiny cell on the CPU and
    never print a result line."""
    t_start = T_PROCESS_START if t_start is None else t_start
    cell = load("workloads", workload, root)
    cfg = load("configs", cell["config"], root)
    import jax
    devs = jax.devices()
    if need_tpu and devs[0].platform != "tpu":
        print(f"chipbench: needs a TPU, found {devs[0].platform}",
              file=sys.stderr)
        return 2, None
    if len(devs) < cell["chips"]:
        print(f"chipbench: {workload} needs {cell['chips']} chip(s), found "
              f"{len(devs)}", file=sys.stderr)
        return 2, None
    cache_dir = None
    if need_tpu:
        from paddle_tpu.core.device import enable_compilation_cache
        cache_dir = enable_compilation_cache()
    seen = listen()
    note(workload=workload, seed=seed, seconds=seconds, trace=trace,
         platform=devs[0].platform, device_kind=devs[0].device_kind,
         devices=len(devs), jax=jax.__version__, compile_cache_dir=cache_dir)

    mix = load("traffic", cell["traffic"], root) if "traffic" in cell else None
    driver = importlib.import_module("chipbench.drivers." + cell["driver"])
    trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_") if trace else None
    try:
        run = driver.run(cell, cfg, mix, seed, seconds, trace_dir, t_start,
                         note, lambda: seen["events"])
        if trace:
            from chipbench import trace as tr
            run["trace"] = tr.reduce(tr.find_xplane(trace_dir),
                                     host_spans=tuple(cell["host_spans"]))
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    note(compile_seconds=seen["seconds"], cache_hits=seen["cache_hits"],
         cache_misses=seen["cache_misses"], setup_s=run["setup_s"])

    metrics = {}
    for name in cell["per_layer" if trace else "end_to_end"]:
        mod = reader(name)
        got = mod.read(run)
        if got is None:          # nothing to read: left out of the line
            continue
        value, samples = got if isinstance(got, tuple) else (got, None)
        metrics[name] = {"value": float(value), "unit": mod.UNIT}
        if samples is not None:
            note(metric=name, samples=samples)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": cell["chips"],
              "memory_peak_bytes": run["memory_peak_bytes"]}
    result = {"correct": bool(run["correct"]), "attempted": run["attempted"],
              "failed": run["failed"], "metrics": metrics, "device": device}
    if trace:
        t = run["trace"]
        device.update(busy_s=t["busy_s"], window_s=t["window_s"])
        result["breakdown"] = {"device_ops": t["device_ops"],
                               "idle_gaps": t["idle_gaps"]}
    return 0, result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    code, result = run_cell(args.workload, args.seed, args.seconds,
                            bool(args.trace))
    if result is not None:
        print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
