"""Observability subsystem (ISSUE 2): metrics registry, trace spans,
and the shared FLOPs/MFU accounting.

Three layers, all host-side and CPU-safe:

  * :mod:`paddle_tpu.observability.metrics` — process-global
    Counter/Gauge/Histogram registry (:data:`METRICS`), exportable as
    one-line JSON and Prometheus text.
  * :mod:`paddle_tpu.observability.tracing` — :func:`span` context
    manager/decorator + :func:`instant` markers over the global
    :data:`TRACER`, exported as a Chrome-trace/Perfetto JSON timeline.
  * :mod:`paddle_tpu.observability.flops` — the peak-FLOPs table and
    :func:`record_throughput`, the single MFU choke point shared by the
    Trainer and ``utils.profiler.StepTimer``.

Built-in instrumentation (serving engine, Trainer, checkpoints, elastic
restarts, collectives, fault injection) emits through these singletons;
``metrics_snapshot()``/``dump()`` give a one-call export of everything.

The second layer (ISSUE 4) turns the registry into an operable
telemetry pipeline:

  * :mod:`paddle_tpu.observability.flight` — :data:`FLIGHT`, the
    bounded ring of structured runtime events, atomically dumped to
    ``flight_<step>.json`` on crash/give-up/watchdog trip.
  * :mod:`paddle_tpu.observability.compile` — :func:`instrumented_jit`,
    compile spans + cache hit/miss counters + cost_analysis FLOPs.
  * :mod:`paddle_tpu.observability.shipper` — the ``pt-metrics-shipper``
    thread appending registry snapshots (with deltas) to a rotating
    JSONL ring on disk.
  * :mod:`paddle_tpu.observability.health` — :data:`HEALTH`, declarative
    OK/WARN/CRIT rules served at ``/healthz`` (with ``/flight``) by the
    metrics HTTP server.

The request layer (ISSUE 9) adds per-request views on top of the
aggregates:

  * :mod:`paddle_tpu.observability.requests` — :data:`REQUESTS`, a
    bounded ring of per-request lifecycle timelines, stitched across
    serving replicas via TRACER flow events and served at ``/requests``.
  * :mod:`paddle_tpu.observability.goodput` — :data:`GOODPUT`, the
    useful-vs-wasted device-token ledger behind
    ``serving_goodput_tokens_total`` / ``serving_waste_total{why}``.

The memory layer (ISSUE 13) accounts for where the KV pool's blocks are:

  * :mod:`paddle_tpu.observability.memledger` — :class:`MemLedger`, the
    per-pool block-state ledger (active/parked/cow_pending/reserved/
    free, ``sum == num_blocks`` by construction) behind
    ``serving_kv_blocks{state}``, per-request peak attribution,
    admission-stall forensics, and the ``GET /memory`` endpoint
    (:func:`memory_doc`).

The SLO layer (ISSUE 19) turns the aggregates into objectives:

  * :mod:`paddle_tpu.observability.windows` — :class:`WindowedReads`,
    the delta-since-last-poll read machinery shared by the degradation
    ladder and the SLO tracker.
  * :mod:`paddle_tpu.observability.slo` — :class:`SLOTracker`,
    declarative per-tenant :class:`Objective` targets with SRE-style
    multi-window burn-rate alerting, plus :class:`CostLedger`, the
    usage-metering ledger attributing device-seconds, KV block-seconds
    and goodput/waste tokens to tenants (``GET /slo`` /
    ``GET /tenants``). ``PT_SLO=0`` kills the whole layer.

``python -m paddle_tpu.observability`` prints a generated reference of
every registered metric instrument.
"""
from __future__ import annotations

from paddle_tpu.observability.metrics import (Counter, Gauge, Histogram,
                                              METRICS, MetricsRegistry,
                                              DEFAULT_BUCKETS)
from paddle_tpu.observability.tracing import (TRACER, Tracer, span, instant,
                                              export_chrome_trace)
from paddle_tpu.observability.flops import (PEAK_BF16, chip_peak_flops, mfu,
                                            record_throughput)
from paddle_tpu.observability.roofline import (PEAK_HBM_BPS, ModelGeometry,
                                               chip_peak_hbm_bw,
                                               record_serving_throughput,
                                               serving_roofline_report)
from paddle_tpu.observability.httpd import (MetricsServer,
                                            start_metrics_server,
                                            stop_metrics_server)
from paddle_tpu.observability.flight import FLIGHT, FlightRecorder
from paddle_tpu.observability.compile import InstrumentedJit, instrumented_jit
from paddle_tpu.observability.shipper import (MetricsShipper,
                                              start_metrics_shipper,
                                              stop_metrics_shipper)
from paddle_tpu.observability.health import (HEALTH, HealthEvaluator,
                                             HealthRule,
                                             install_default_rules)
from paddle_tpu.observability.requests import REQUESTS, RequestTracker
from paddle_tpu.observability.goodput import GOODPUT, GoodputLedger
from paddle_tpu.observability.memledger import MemLedger, memory_doc
from paddle_tpu.observability.windows import WindowedReads
from paddle_tpu.observability.slo import (CostLedger, Objective, SLOTracker,
                                          default_objectives, slo_doc,
                                          slo_enabled, tenants_doc)

__all__ = [
    "METRICS", "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "DEFAULT_BUCKETS",
    "TRACER", "Tracer", "span", "instant", "export_chrome_trace",
    "PEAK_BF16", "chip_peak_flops", "mfu", "record_throughput",
    "PEAK_HBM_BPS", "ModelGeometry", "chip_peak_hbm_bw",
    "record_serving_throughput", "serving_roofline_report",
    "MetricsServer", "start_metrics_server", "stop_metrics_server",
    "FLIGHT", "FlightRecorder",
    "InstrumentedJit", "instrumented_jit",
    "MetricsShipper", "start_metrics_shipper", "stop_metrics_shipper",
    "HEALTH", "HealthEvaluator", "HealthRule", "install_default_rules",
    "REQUESTS", "RequestTracker", "GOODPUT", "GoodputLedger",
    "MemLedger", "memory_doc",
    "WindowedReads",
    "SLOTracker", "Objective", "CostLedger", "default_objectives",
    "slo_enabled", "slo_doc", "tenants_doc",
    "enable", "disable", "metrics_snapshot", "dump",
]


def enable(tracing: bool = True):
    """Turn the whole layer on (metrics are on by default; this also
    starts span collection when ``tracing``)."""
    METRICS.enable()
    if tracing:
        TRACER.enable()


def disable():
    """No-op every instrument and stop span collection."""
    METRICS.disable()
    TRACER.disable()


def metrics_snapshot() -> dict:
    return METRICS.snapshot()


def dump(prefix: str) -> dict:
    """Write ``<prefix>.metrics.json`` (one line), ``<prefix>.prom``
    (Prometheus text), and ``<prefix>.trace.json`` (Chrome trace);
    returns the three paths."""
    paths = {"json": prefix + ".metrics.json", "prom": prefix + ".prom",
             "trace": prefix + ".trace.json"}
    with open(paths["json"], "w") as f:
        f.write(METRICS.to_json() + "\n")
    with open(paths["prom"], "w") as f:
        f.write(METRICS.to_prometheus())
    TRACER.export_chrome_trace(paths["trace"])
    return paths
