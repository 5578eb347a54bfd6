"""Drivers: ``run(cell, cfg, mix, seed, seconds, trace_dir, t_process_start,
note, compiles) -> the run's record``, which ``run.py`` hands to the metric
readers. ``compiles()`` is the number of compile events JAX has reported so
far; ``note(**kw)`` prints one line of JSON."""


def peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
