"""Build the ctypes libraries under ``native/`` from their sources.

The binaries are build products, never committed: the first use on a
checkout runs ``make`` there (fixed output path ``native/<lib>``), and
make's own mtime rule rebuilds a binary older than its source. A missing
compiler is an error for whoever asked for the native path.
"""
from __future__ import annotations

import fcntl
import subprocess
from pathlib import Path

NATIVE_DIR = Path(__file__).resolve().parents[2] / "native"


def build_native(lib: str) -> str:
    """Make ``native/<lib>`` up to date and return its path."""
    # one builder at a time: test workers share the checkout
    with open(NATIVE_DIR / ".build.lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        proc = subprocess.run(["make", "-C", str(NATIVE_DIR), lib],
                              capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(
            f"building native/{lib} failed (rc {proc.returncode}):\n"
            f"{proc.stdout}{proc.stderr}")
    return str(NATIVE_DIR / lib)
