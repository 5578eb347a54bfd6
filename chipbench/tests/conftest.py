"""Run by hand: ``python -m pytest chipbench/tests -q`` (CPU; not part of the
repo's tier-1 run). The tests drive tiny cells from ``tests/cells``."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
