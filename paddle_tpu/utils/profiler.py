"""Profiling & tracing (ref: ``python/paddle/profiler/`` — Profiler,
RecordEvent, chrome-trace export; SURVEY.md §2.9).

TPU-native: wraps ``jax.profiler`` (XLA's own tracer → TensorBoard/perfetto
trace with per-op HLO timings, HBM usage, ICI traffic) plus a host-side
step-timer with MFU accounting, and HLO/jaxpr dump helpers for graph debug.

Since the observability subsystem landed, the names here are THIN
DELEGATES: Profiler also drives the host-side span tracer (and writes
its Chrome trace next to the XLA artifact on stop), RecordEvent is an
observability span (which annotates the XLA trace itself), and StepTimer feeds the
shared ``train_tokens_per_sec``/``train_mfu`` gauges through the same
:func:`~paddle_tpu.observability.flops.record_throughput` choke point the
Trainer uses.
"""
from __future__ import annotations

import contextlib
import json
import os
import time
from dataclasses import dataclass, field
from typing import Optional

import jax

from paddle_tpu.observability import METRICS, TRACER, span as _span
from paddle_tpu.observability.flops import record_throughput

_STEPTIMER_S = METRICS.histogram(
    "steptimer_step_seconds", "wall time per StepTimer start/stop window")
_DEV_MEM = METRICS.gauge(
    "device_bytes_in_use", "per-device bytes in use (0 when the backend "
    "does not report memory stats)", labelnames=("device",))
_DEV_MEM_PEAK = METRICS.gauge(
    "device_bytes_peak", "per-device peak bytes in use (0 when the "
    "backend does not report memory stats)", labelnames=("device",))
_DEV_MEM_LIMIT = METRICS.gauge(
    "device_bytes_limit", "per-device memory capacity visible to the "
    "allocator (0 when the backend does not report it)",
    labelnames=("device",))


class Profiler:
    """Reference-shaped API: Profiler(targets=..., scheduler=...,
    on_trace_ready=...) ... start/stop. ``targets`` is accepted for parity
    (XLA traces always cover host + device); ``on_trace_ready`` runs
    BEFORE the trace starts so export_chrome_tracing can direct the
    output directory. Also drives the host span tracer: host spans are
    collected while active and written to ``<log_dir>/host_trace.json``
    (Chrome/Perfetto format) on stop."""

    def __init__(self, log_dir: str = "profile_out", targets=None,
                 scheduler=None, on_trace_ready=None):
        self.log_dir = log_dir
        self.targets = targets
        self.scheduler = scheduler
        self._on_trace_ready = on_trace_ready
        self._active = False
        self._owns_tracer = False
        self.host_trace_path: Optional[str] = None

    def start(self):
        if self._on_trace_ready is not None:
            self._on_trace_ready(self)  # may redirect self.log_dir
        jax.profiler.start_trace(self.log_dir)
        # only take over the host tracer if nobody else enabled it —
        # a surrounding `with TRACER:` keeps ownership of its buffer
        self._owns_tracer = not TRACER._enabled
        if self._owns_tracer:
            TRACER.enable()
        self._active = True
        return self

    def stop(self):
        if self._active:
            jax.profiler.stop_trace()
            if self._owns_tracer:
                os.makedirs(self.log_dir, exist_ok=True)
                self.host_trace_path = os.path.join(
                    self.log_dir, "host_trace.json")
                TRACER.export_chrome_trace(self.host_trace_path)
                TRACER.disable()
                self._owns_tracer = False
            self._active = False

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()


@contextlib.contextmanager
def record_event(name: str):
    """Ref: paddle.profiler.RecordEvent — one observability span, which
    lands in the host span timeline and in the XLA trace."""
    with _span(name):
        yield


def device_memory_stats() -> dict:
    """Per-device HBM usage (ref: paddle.device.cuda.memory_allocated).
    Backends without memory stats (CPU) report explicit zeroed
    placeholders with the backend named, never an empty dict."""
    out = {}
    for d in jax.local_devices():
        try:
            s = d.memory_stats() or {}
        except Exception:
            s = {}
        if s:
            rec = {"backend": d.platform,
                   "bytes_in_use": s.get("bytes_in_use"),
                   "peak_bytes_in_use": s.get("peak_bytes_in_use"),
                   "bytes_limit": s.get("bytes_limit")}
        else:
            rec = {"backend": d.platform, "bytes_in_use": 0,
                   "peak_bytes_in_use": 0, "bytes_limit": 0}
        out[str(d)] = rec
        _DEV_MEM.set(rec["bytes_in_use"] or 0, device=str(d))
        _DEV_MEM_PEAK.set(rec["peak_bytes_in_use"] or 0, device=str(d))
        _DEV_MEM_LIMIT.set(rec["bytes_limit"] or 0, device=str(d))
    return out


@dataclass
class StepTimer:
    """Host-side step timing + MFU meter. Each stop() also lands in the
    ``steptimer_step_seconds`` histogram and (when tokens are reported)
    the shared throughput/MFU gauges."""
    flops_per_token: float = 0.0
    peak_flops: float = 197e12
    _t0: float = field(default=0.0, repr=False)
    records: list = field(default_factory=list)

    def start(self):
        self._t0 = time.perf_counter()

    def stop(self, tokens: int = 0) -> dict:
        dt = time.perf_counter() - self._t0
        rec = {"step_s": dt}
        _STEPTIMER_S.observe(dt)
        if tokens and dt > 0:
            rec["tokens_per_sec"] = tokens / dt
            mfu = record_throughput(tokens / dt, self.flops_per_token,
                                    self.peak_flops)
            if self.flops_per_token:
                rec["mfu"] = mfu
        self.records.append(rec)
        return rec


def dump_cost_analysis(fn, *args) -> dict:
    """XLA FLOPs/bytes estimate for `fn(*args)` (feeds MFU accounting)."""
    compiled = jax.jit(fn).lower(*args).compile()
    try:
        return dict(compiled.cost_analysis())
    except Exception:
        return {}


def compiled_memory_analysis(fn, *args) -> dict:
    compiled = jax.jit(fn).lower(*args).compile()
    try:
        m = compiled.memory_analysis()
        return {"temp_size": m.temp_size_in_bytes,
                "argument_size": m.argument_size_in_bytes,
                "output_size": m.output_size_in_bytes,
                "generated_code_size": m.generated_code_size_in_bytes}
    except Exception:
        return {}



class ProfilerTarget:
    """Ref profiler.ProfilerTarget — device classes to trace. On this
    stack traces always cover host + the XLA device."""
    CPU = "cpu"
    GPU = "gpu"
    CUSTOM_DEVICE = "custom_device"
    TPU = "tpu"


class RecordEvent:
    """Ref profiler.RecordEvent: context manager annotating the trace
    (an observability span, which reaches the XLA trace by itself)."""

    def __init__(self, name: str):
        self.name = name
        self._span = None

    def begin(self):
        self._span = _span(self.name).begin()

    def end(self):
        if self._span is not None:
            self._span.end()
            self._span = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()


def make_scheduler(*, closed, ready, record, repeat=0, skip_first=0):
    """Ref profiler.make_scheduler — step-state schedule. Returns a
    callable step -> one of "closed"/"ready"/"record" mirroring the
    reference's ProfilerState for Profiler(scheduler=...)."""
    if record <= 0:
        raise ValueError("make_scheduler: record must be > 0")
    if closed < 0 or ready < 0:
        raise ValueError("make_scheduler: closed/ready must be >= 0")
    cycle = closed + ready + record

    def schedule(step: int) -> str:
        if step < skip_first:
            return "closed"
        s = step - skip_first
        if repeat and s >= repeat * cycle:
            return "closed"
        pos = s % cycle
        if pos < closed:
            return "closed"
        if pos < closed + ready:
            return "ready"
        return "record"

    return schedule


def export_chrome_tracing(dir_name: str, worker_name: str = None):
    """Ref profiler.export_chrome_tracing — the jax trace is already a
    TensorBoard/perfetto artifact; this callback (run by Profiler.start
    before tracing begins) directs it to ``dir_name``."""
    def on_export(prof):
        prof.log_dir = dir_name
        return dir_name
    return on_export
