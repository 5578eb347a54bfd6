"""Peak-FLOPs table + MFU accounting — the ONE copy the Trainer and
``utils.profiler.StepTimer`` both read.

Nothing at this module's top level imports jax.
"""
from __future__ import annotations

from paddle_tpu.observability.metrics import METRICS

__all__ = ["PEAK_BF16", "chip_peak", "chip_peak_flops", "mfu",
           "record_throughput"]

# Peak dense bf16 FLOP/s per chip, by device_kind prefix. (The serving
# and training MFU numbers and the profiler's StepTimer all divide by
# THIS table.)
PEAK_BF16 = {
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v4": 275e12,
    "TPU v6": 918e12,
}


def chip_peak(table: dict, dev=None, kind: str = None) -> float:
    """Look a jax device (or an explicit ``device_kind`` string) up in a
    per-chip peak table. Non-TPU backends (cpu debugging runs) and
    devices with no evidence of being a TPU return 0.0 — callers treat 0
    peak as "utilisation undefined" rather than dividing by a made-up
    number. A TPU whose kind is not in the table raises: a peak is a
    published figure, never a default."""
    platform = None
    if kind is None:
        kind = getattr(dev, "device_kind", "") or ""
        platform = getattr(dev, "platform", "") or ""
        if platform and platform != "tpu":
            return 0.0
    for k, v in table.items():
        if kind.startswith(k) or k in kind:
            return v
    if "TPU" in kind.upper() or platform == "tpu":
        raise ValueError(
            f"no published peak for TPU device kind {kind!r}; add it to "
            f"the table (known: {sorted(table)})")
    return 0.0


def chip_peak_flops(dev=None, kind: str = None) -> float:
    """Peak bf16 FLOP/s — see :func:`chip_peak`."""
    return chip_peak(PEAK_BF16, dev, kind)


def mfu(tokens_per_sec: float, flops_per_token: float,
        peak_flops: float) -> float:
    """Model FLOPs utilisation; 0.0 when the peak is unknown."""
    if not peak_flops or not flops_per_token:
        return 0.0
    return tokens_per_sec * flops_per_token / peak_flops


_TOKENS_PER_SEC = METRICS.gauge(
    "train_tokens_per_sec", "training throughput, tokens/sec")
_MFU = METRICS.gauge(
    "train_mfu", "model FLOPs utilisation vs the chip peak-bf16 table")
_MFU_OVERLAP = METRICS.gauge(
    "train_mfu_overlap", "MFU with host time hidden behind in-flight "
    "device steps subtracted from the wall-clock denominator")


def record_throughput(tokens_per_sec: float, flops_per_token: float = 0.0,
                      peak_flops: float = 0.0, hidden_host_s: float = 0.0,
                      window_s: float = 0.0) -> float:
    """Single choke point for throughput/MFU accounting: computes MFU
    from the shared table's peak, sets the ``train_tokens_per_sec`` and
    ``train_mfu`` gauges, returns the (naive) MFU. Trainer and StepTimer
    both land here — there is exactly one FLOPs model.

    ``hidden_host_s``/``window_s`` enable the overlap-aware variant
    (ROADMAP leftover): the pipelined trainer measures how much host
    input/dispatch time rode in the shadow of in-flight device steps
    during the ``window_s``-second logging window; that time belongs to
    neither the device nor the critical path, so the overlap-aware MFU
    removes it from the denominator —
    ``mfu(tps * window / (window - hidden), ...)``. With no overlap
    information (sync loop, StepTimer) the overlap gauge
    mirrors the naive value, so the two series are always comparable."""
    m = mfu(tokens_per_sec, flops_per_token, peak_flops)
    if window_s > 0.0 and 0.0 < hidden_host_s < window_s:
        m_ov = mfu(tokens_per_sec * window_s / (window_s - hidden_host_s),
                   flops_per_token, peak_flops)
    else:
        m_ov = m
    _TOKENS_PER_SEC.set(tokens_per_sec)
    _MFU.set(m)
    _MFU_OVERLAP.set(m_ov)
    return m
