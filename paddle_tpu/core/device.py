"""Device / place management (ref: ``paddle.set_device``, ``paddle/phi/common/place.h``).

Paddle routes ops to a Place (CPUPlace/CUDAPlace/XPUPlace). Under JAX the
platform is process-global and arrays carry their sharding, so "set_device"
reduces to selecting the default platform and exposing topology queries used
by the distributed layer.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax


def set_device(name: str) -> None:
    """Accepts 'tpu', 'cpu', 'gpu' (ref signature). Affects default backend
    only. Raises RuntimeError when the platform is not present."""
    platform = {"xla": "tpu", "tpu": "tpu", "gpu": "gpu", "cpu": "cpu"}.get(name, name)
    jax.config.update("jax_default_device", jax.devices(platform)[0])


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache; entry points call this
    before their first jit (it never runs on ``import paddle_tpu``).
    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and
    no other directory is set here; otherwise the cache lives at the
    fixed path ``<checkout>/.jax_cache`` (the path is part of the cache
    key, so it must not move between runs). Returns the directory."""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(Path(__file__).resolve().parents[2] / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache the sub-second Pallas kernels and small programs too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def get_device() -> str:
    return jax.default_backend()


def device_count() -> int:
    return jax.device_count()


def local_device_count() -> int:
    return jax.local_device_count()


def process_index() -> int:
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()


def is_tpu() -> bool:
    return jax.default_backend() == "tpu"



def is_compiled_with_cuda() -> bool:
    """Ref paddle.device.is_compiled_with_cuda — this build targets TPU."""
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_custom_device(device_type: str = "tpu") -> bool:
    """TPU is the custom device this framework is built for."""
    return device_type == "tpu"


def get_all_device_type():
    return sorted({d.platform for d in jax.devices()})


def synchronize(device=None):
    """Ref paddle.device.synchronize — block until pending work completes.
    XLA has no global stream; a device executes its programs in dispatch
    order, so waiting on a freshly dispatched computation fences all
    prior work on that device."""
    import jax.numpy as jnp
    devices = [device] if device is not None else jax.local_devices()
    for d in devices:
        (jax.device_put(jnp.zeros(()), d) + 0).block_until_ready()
