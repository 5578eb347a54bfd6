"""Test config: tests run on the CPU, on an 8-device virtual mesh, so the
distributed tests need no TPU hardware (SURVEY.md §4). XLA_FLAGS must be
set before the CPU client is instantiated (it is created lazily, so doing
it here is early enough).
"""
import os
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "chaos: deterministic fault-injection tests — seeded "
        "schedules, CPU-safe, run in tier-1 (no slow marker)")
    config.addinivalue_line("markers", "slow: long-running; excluded from "
                            "the tier-1 '-m not slow' run")


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as pt
    pt.seed(0)
    yield


@pytest.fixture(autouse=True)
def _clear_faults():
    """Chaos hygiene: no fault rule ever leaks across tests."""
    from paddle_tpu.utils.faults import FAULTS
    FAULTS.clear()
    yield
    FAULTS.clear()


@pytest.fixture(autouse=True)
def _no_leaked_threads():
    """Background-thread hygiene: every paddle_tpu helper thread carries a
    ``pt-`` name prefix (prefetch producers, the async checkpoint writer,
    the metrics HTTP server, stall watchdogs). None may outlive the test
    that started it. A short grace join absorbs threads that are already
    winding down (e.g. a prefetch producer observing its closed flag)."""
    import threading
    import time
    yield
    deadline = time.monotonic() + 2.0
    while time.monotonic() < deadline:
        leaked = [t for t in threading.enumerate()
                  if t.name.startswith("pt-") and t.is_alive()]
        if not leaked:
            return
        time.sleep(0.02)
    assert not leaked, f"leaked background threads: {[t.name for t in leaked]}"


@pytest.fixture(autouse=True)
def _clear_observability():
    """Telemetry hygiene: every test starts with zeroed metric series,
    an empty span buffer, the tracer disabled (its default), and an
    empty flight-recorder ring with NO dump directory — a chaos test
    that crashes a trainer must not scatter flight_*.json into the
    repo. Tests that want dumps set FLIGHT.dir (or pass directory=)
    themselves; capacity/dir are restored afterwards either way. The
    request tracker (ISSUE 9) gets the same treatment: cleared and
    disabled (its default) on both sides, capacity restored. The SLO
    layer (ISSUE 19) too: the goodput ledger's metering sink is
    detached so a tracker built in one test never bills another's
    tokens, and the tenant label-cardinality seen-set resets."""
    from paddle_tpu.observability import FLIGHT, GOODPUT, METRICS, \
        REQUESTS, TRACER

    def _reset_slo_state():
        GOODPUT.attach_sink(None)
        # serving.telemetry pulls in jax via the engine stack; only
        # reset the seen-set if some test already imported it
        tel = sys.modules.get("paddle_tpu.serving.telemetry")
        if tel is not None:
            tel.reset_tenant_labels()

    _reset_slo_state()
    METRICS.reset()
    METRICS.enable()
    TRACER.disable()
    TRACER.clear()
    FLIGHT.clear()
    REQUESTS.disable()
    REQUESTS.clear()
    saved_dir, saved_cap = FLIGHT.dir, FLIGHT.capacity
    saved_rcap = REQUESTS.capacity
    FLIGHT.dir = None
    yield
    METRICS.reset()
    METRICS.enable()
    TRACER.disable()
    TRACER.clear()
    FLIGHT.clear()
    REQUESTS.disable()
    REQUESTS.clear()
    _reset_slo_state()
    FLIGHT.dir = saved_dir
    if FLIGHT.capacity != saved_cap:
        FLIGHT.set_capacity(saved_cap)
    if REQUESTS.capacity != saved_rcap:
        REQUESTS.set_capacity(saved_rcap)
