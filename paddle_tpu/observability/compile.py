"""Compile introspection (ISSUE 4): make every XLA compile visible.

``jax.jit`` hides its trace/lower/compile pipeline behind the first
call; a production stack needs to see a compile happen — they cost
seconds to minutes on real models, and an unexpected RE-compile (a
shape bucket miss, a donation change) silently halves throughput.

:class:`InstrumentedJit` wraps one function with an EXPLICIT AOT cache
keyed on the abstract signature (pytree structure + shape/dtype of
every array leaf, value of every static leaf). A miss runs the
``trace → lower → compile`` pipeline under trace spans (``jit.trace``,
``jit.lower``, ``jit.compile``), lands the wall time in the
``compile_seconds`` histogram, counts a ``compile_cache_misses_total``,
pulls the XLA ``cost_analysis`` FLOPs estimate into the
``compile_flops_estimate`` gauge (the Trainer feeds it into
``flops.record_throughput`` when no analytic FLOPs model was given),
and drops a ``compile`` event on the flight recorder. A hit is one
dict lookup and a ``compile_cache_hits_total`` increment — no new
compile span. Every call is one span under the function's name (so
``train.step``) with a child ``jit.signature`` around the signature
pass, which flattens all arguments in Python on every call: the two
say what the wrapper itself costs a step.

Robustness: jax's own dispatch cache stays the backstop. If the AOT
path fails for a function (an exotic backend, a remote-compile quirk),
the wrapper permanently falls back to the plain jitted callable — same
program, same numerics, just without the introspection.

``PT_COMPILE_INTROSPECTION=0`` turns the whole layer off at creation
time (:func:`instrumented_jit` then returns a bare ``jax.jit``).
"""
from __future__ import annotations

import functools
import os
import time
from typing import Callable, Optional

from paddle_tpu.observability.flight import FLIGHT
from paddle_tpu.observability.metrics import METRICS
from paddle_tpu.observability.tracing import span as _span

__all__ = ["InstrumentedJit", "instrumented_jit", "introspection_enabled",
           "cost_analysis_flops"]

# compiles are seconds-to-minutes shaped, not request-latency shaped
_COMPILE_BUCKETS = (0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 15.0, 60.0, 300.0)

_HITS = METRICS.counter(
    "compile_cache_hits_total",
    "jitted calls served from an already-compiled executable",
    labelnames=("fn",))
_MISSES = METRICS.counter(
    "compile_cache_misses_total",
    "jitted calls that had to trace/lower/compile first",
    labelnames=("fn",))
_COMPILE_S = METRICS.histogram(
    "compile_seconds", "wall time of one trace+lower+compile",
    labelnames=("fn",), buckets=_COMPILE_BUCKETS)
_COMPILE_FLOPS = METRICS.gauge(
    "compile_flops_estimate",
    "XLA cost_analysis FLOPs per call of the newest compiled program",
    labelnames=("fn",))


def introspection_enabled() -> bool:
    return os.environ.get("PT_COMPILE_INTROSPECTION", "1").lower() \
        not in ("0", "false", "off")


def cost_analysis_flops(compiled) -> float:
    """FLOPs-per-call estimate from an AOT-compiled executable; 0.0 when
    the backend does not report one. Normalises the jax version drift
    (list-of-dicts vs one dict)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return 0.0
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    try:
        return float(ca.get("flops", 0.0) or 0.0)
    except Exception:
        return 0.0


def _leaf_sig(leaf):
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is not None and dtype is not None:
        return (tuple(shape), str(dtype))
    try:
        hash(leaf)
        return ("py", leaf)
    except TypeError:
        return ("py", repr(leaf))


class InstrumentedJit:
    """One jitted function + an explicit signature→executable cache."""

    def __init__(self, fn: Callable, name: Optional[str] = None, **jit_kwargs):
        import jax
        self._jax = jax
        self._jit = jax.jit(fn, **jit_kwargs)
        self.name = name or getattr(fn, "__name__", None) or "jit"
        self._compiled: dict = {}
        self._broken = False      # AOT path failed once → plain jit forever
        self.flops_per_call: float = 0.0   # newest compile's estimate
        self._hits = _HITS.labels(fn=self.name)
        self._misses = _MISSES.labels(fn=self.name)
        functools.update_wrapper(self, fn)

    # ------------------------------------------------------------- introspection
    @property
    def cache_size(self) -> int:
        return len(self._compiled)

    def lower(self, *args, **kwargs):
        return self._jit.lower(*args, **kwargs)

    # ------------------------------------------------------------------ call
    def _sig(self, args, kwargs):
        leaves, treedef = self._jax.tree_util.tree_flatten((args, kwargs))
        return (treedef, tuple(_leaf_sig(l) for l in leaves))

    def _compile(self, args, kwargs):
        t0 = time.monotonic()
        if hasattr(self._jit, "trace"):      # jax >= 0.4.3x: 3-stage AOT
            with _span("jit.trace", fn=self.name):
                traced = self._jit.trace(*args, **kwargs)
            with _span("jit.lower", fn=self.name):
                lowered = traced.lower()
        else:
            with _span("jit.lower", fn=self.name):
                lowered = self._jit.lower(*args, **kwargs)
        with _span("jit.compile", fn=self.name):
            compiled = lowered.compile()
        dt = time.monotonic() - t0
        _COMPILE_S.observe(dt, fn=self.name)
        flops = cost_analysis_flops(compiled)
        if flops:
            self.flops_per_call = flops
            _COMPILE_FLOPS.set(flops, fn=self.name)
        FLIGHT.record("compile", fn=self.name, seconds=round(dt, 6),
                      flops=flops, cached=len(self._compiled) + 1)
        return compiled

    def __call__(self, *args, **kwargs):
        with _span(self.name):
            return self._call(args, kwargs)

    def _call(self, args, kwargs):
        if self._broken:
            return self._jit(*args, **kwargs)
        try:
            with _span("jit.signature"):
                key = self._sig(args, kwargs)
        except Exception:
            self._broken = True
            return self._jit(*args, **kwargs)
        entry = self._compiled.get(key)
        if entry is not None:
            self._hits.inc()
            try:
                return entry(*args, **kwargs)
            except (TypeError, ValueError):
                # aval/sharding drift the shape/dtype signature could not
                # see — jax validates inputs BEFORE execution, so nothing
                # ran; let jax's own cache handle this call
                return self._jit(*args, **kwargs)
        self._misses.inc()
        try:
            compiled = self._compile(args, kwargs)
        except Exception:
            self._broken = True
            return self._jit(*args, **kwargs)
        self._compiled[key] = compiled
        return compiled(*args, **kwargs)


def instrumented_jit(fn: Callable = None, *, name: Optional[str] = None,
                     **jit_kwargs):
    """``jax.jit`` with compile introspection. Usable as a decorator
    (with or without arguments) or a direct call; honours the
    ``PT_COMPILE_INTROSPECTION`` kill switch."""
    if fn is None:
        return functools.partial(instrumented_jit, name=name, **jit_kwargs)
    if not introspection_enabled():
        import jax
        return jax.jit(fn, **jit_kwargs)
    return InstrumentedJit(fn, name=name, **jit_kwargs)
