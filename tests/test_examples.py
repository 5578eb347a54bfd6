"""Examples smoke tests: each example script runs end-to-end at tiny scale."""
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent


def _run(script, *args):
    out = subprocess.run(
        [sys.executable, str(REPO / "examples" / script), *args],
        capture_output=True, text=True, timeout=600,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
             "HOME": "/root", "PYTHONUNBUFFERED": "1"})
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout


def test_train_llama_example(tmp_path):
    out = _run("train_llama.py", "--steps", "6", "--batch", "2", "--seq", "32",
               "--ckpt-dir", str(tmp_path / "ck"))
    assert "loss" in out and "saved checkpoint" in out
    losses = [float(l.rsplit(" ", 1)[1]) for l in out.splitlines()
              if l.startswith("step")]
    assert losses[-1] < losses[0]  # trains


@pytest.mark.slow
def test_train_resnet_example():
    out = _run("train_resnet.py", "--steps", "4", "--batch", "4")
    assert "loss" in out


def test_train_multichip_example():
    out = _run("train_multichip.py", "--cpu-dryrun", "--devices", "8",
               "--steps", "2")
    assert "mesh dp=2 fsdp=2 tp=2" in out
    losses = [float(l.split("loss ")[1].split(" ")[0])
              for l in out.splitlines() if l.startswith("step")]
    assert np.isfinite(losses).all()


def test_generate_example():
    out = _run("generate.py", "--model", "mistral", "--strategy", "greedy",
               "--max-new-tokens", "4")
    assert "mistral/greedy" in out


@pytest.mark.slow
def test_long_context_example():
    out = _run("long_context.py", "--mode", "ring", "--steps", "2",
               "--seq", "64")
    assert "step 1" in out
