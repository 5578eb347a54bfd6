"""Generated metrics reference: ``python -m paddle_tpu.observability``
prints every registered instrument (name, kind, labels, help) as a
markdown table (ISSUE 9 doc satellite).

Instruments register at module import, so the reference is built by
importing every instrument-bearing module and then walking the global
registry — the listing can never drift from the code the way a
hand-maintained table would. Importing the training stack pulls in jax;
that is fine here (an offline doc command), and any module that fails
to import is reported rather than silently skipped.
"""
from __future__ import annotations

import importlib

from paddle_tpu.observability.metrics import METRICS

# every module that registers instruments at import time (a test_lint
# rule asserts every METRICS.counter/gauge/histogram caller is listed)
_INSTRUMENT_MODULES = (
    "paddle_tpu.observability.tracing",
    "paddle_tpu.observability.flops",
    "paddle_tpu.observability.roofline",
    "paddle_tpu.observability.compile",
    "paddle_tpu.observability.goodput",
    "paddle_tpu.observability.memledger",
    "paddle_tpu.observability.slo",
    "paddle_tpu.serving.telemetry",
    "paddle_tpu.serving.quant",
    "paddle_tpu.serving.cp",
    "paddle_tpu.ops.pallas.paged_attention",
    "paddle_tpu.ops.pallas.grouped_matmul",
    "paddle_tpu.train.trainer",
    "paddle_tpu.train.checkpoint",
    "paddle_tpu.train.elastic",
    "paddle_tpu.distributed.collective",
    "paddle_tpu.io.prefetch",
    "paddle_tpu.utils.faults",
    "paddle_tpu.utils.profiler",
)


def metrics_reference() -> str:
    """Import all instrument-bearing modules, then render the registry
    as a markdown table sorted by instrument name."""
    failures = []
    for mod in _INSTRUMENT_MODULES:
        try:
            importlib.import_module(mod)
        except Exception as e:
            failures.append(f"{mod}: {type(e).__name__}: {e}")
    rows = []
    for name in sorted(METRICS._instruments):
        inst = METRICS._instruments[name]
        labels = ", ".join(inst.labelnames) if inst.labelnames else "—"
        rows.append(f"| `{name}` | {inst.kind} | {labels} | {inst.help} |")
    lines = ["# paddle_tpu metrics reference", "",
             f"{len(rows)} instruments registered by "
             f"{len(_INSTRUMENT_MODULES)} modules.", "",
             "| name | kind | labels | help |",
             "|------|------|--------|------|", *rows]
    if failures:
        lines += ["", "## import failures", ""]
        lines += [f"- {f}" for f in failures]
    return "\n".join(lines) + "\n"


if __name__ == "__main__":
    print(metrics_reference(), end="")
