"""Failure detection (ref: Fleet elastic / ``paddle.distributed.fleet``
fault-tolerance hooks; SURVEY.md §2.9/§5).

Two detectors:
  * NaN/inf sentinel — the Trainer skips poisoned updates in-graph (see
    trainer.py nan_guard) and raises WatchdogTrip after N bad steps.
  * Stall watchdog — a host thread that trips if the step callback hasn't
    been poked within `timeout_s` (hung collective / lost device), running
    an emergency callback (e.g. checkpoint) before raising in the main
    thread via a flag the loop checks.
"""
from __future__ import annotations

import threading
import time
from typing import Callable, Optional


class WatchdogTrip(RuntimeError):
    pass


class StallWatchdog:
    def __init__(self, timeout_s: float = 600.0,
                 on_trip: Optional[Callable[[], None]] = None):
        self.timeout_s = timeout_s
        self.on_trip = on_trip
        self._last_poke = time.monotonic()
        self._tripped = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self):
        self._thread = threading.Thread(target=self._run, name="pt-watchdog",
                                        daemon=True)
        self._thread.start()
        return self

    def poke(self):
        self._last_poke = time.monotonic()
        if self._tripped.is_set():
            raise WatchdogTrip(
                f"no progress for > {self.timeout_s}s (stalled step detected)")

    def _run(self):
        while not self._stop.wait(min(self.timeout_s / 4, 30.0)):
            if time.monotonic() - self._last_poke > self.timeout_s:
                self._tripped.set()
                # the trip is detected on THIS thread — record + dump
                # here so a hung main thread (the very thing a watchdog
                # exists for) still leaves its flight file behind
                try:
                    from paddle_tpu.observability.flight import FLIGHT
                    FLIGHT.record("watchdog.trip", timeout_s=self.timeout_s)
                    FLIGHT.dump(reason="watchdog.trip")
                except Exception:
                    pass
                if self.on_trip:
                    try:
                        self.on_trip()
                    except Exception:
                        pass
                return

    def stop(self):
        self._stop.set()
        if self._thread:
            self._thread.join(timeout=5)

    @property
    def tripped(self) -> bool:
        return self._tripped.is_set()


def check_finite(tree) -> bool:
    """Host-side check that every float leaf is finite."""
    import jax
    import numpy as np
    import jax.numpy as jnp
    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.floating):
            if not bool(jnp.all(jnp.isfinite(leaf))):
                return False
    return True
