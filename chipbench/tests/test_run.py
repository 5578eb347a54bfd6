"""The harness end to end on the CPU, through ``run_cell`` with the look for
a chip skipped, and the real entry point, which must refuse a CPU."""
import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).parent
BENCH = HERE.parent
ROOT = BENCH.parent
CELLS = HERE / "cells"


def run_cell(*a, **kw):
    from chipbench import run
    return run.run_cell(*a, need_tpu=False, **kw)


def test_entry_point_refuses_a_cpu_and_prints_no_result():
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "mistral7b.serve.backlog", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, capture_output=True, text=True,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


@pytest.mark.parametrize("cell", ["tiny.backlog", "tiny.steady", "tiny.train"])
def test_a_sound_run_is_correct(cell):
    code, res = run_cell(cell, 2 ** 31 + 5, 1.5, False, root=CELLS)
    assert code == 0 and res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 5
    assert set(res) == {"correct", "attempted", "failed", "metrics", "device"}
    want = json.loads((CELLS / "workloads" / f"{cell}.json").read_text())
    assert set(res["metrics"]) == set(want["end_to_end"])
    assert all(m["value"] > 0 for m in res["metrics"].values())


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    """The timed path broken underneath: every 7th token the engine emits is
    another one. The rest of the run is driven as always."""
    from paddle_tpu.serving.engine import LLMEngine
    real, n = LLMEngine._emit, [0]

    def emit(self, slot, token):
        n[0] += 1
        return real(self, slot, token ^ 1 if n[0] % 7 == 0 else token)

    monkeypatch.setattr(LLMEngine, "_emit", emit)
    code, res = run_cell("tiny.backlog", 11, 1.5, False, root=CELLS)
    assert code == 0 and res["correct"] is False


def calibrate(capsys, *argv):
    """``calibrate.py`` on the tiny cells -> its lines of output."""
    import chipbench.calibrate as cal
    from chipbench import run
    real = run.run_cell
    try:
        cal.HERE = CELLS
        run.run_cell = lambda *a, **kw: real(*a, need_tpu=False, **kw)
        cal.main(list(argv))
    finally:
        run.run_cell, cal.HERE = real, BENCH
    return [json.loads(l) for l in capsys.readouterr().out.splitlines()
            if l.startswith("{")]


def test_the_programs_own_int8_paths_are_not_correct(capsys):
    """The served control, kept at a size a test run can hold: the engine
    with the program's own int8 K/V cache and weight-only int8 weights
    switched on, on three seeds, against sound runs of the same seeds. On
    the chip the same tool reads both at the cell's own size and PERF.md
    gives the readings; here the control's smallest mean gap must be three
    times the sound runs' largest, the separation a limit needs, and the
    control must come out as not correct."""
    seeds = ["3", str(2 ** 31 + 4), "5"]
    lines = calibrate(capsys, "tiny.backlog", "1.0", *seeds, "--control",
                      "kv_dtype=int8,weights=weight_only_int8", *seeds)
    sound = [l for l in lines if l.get("reading") == "sound"]
    low = [l for l in lines if l.get("reading") == "control"]
    assert len(sound) == len(low) == 3
    assert all(l["run_correct"] is True for l in sound)
    assert all(l["run_correct"] is False for l in low)
    assert min(l["numbers"]["mean_gap"] for l in low) > 3 * max(
        max(l["numbers"]["mean_gap"] for l in sound), 1e-6), (sound, low)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    """The training path broken underneath: the step computes its loss and
    hands the state back as it came."""
    import paddle_tpu.train as pt_train
    real = pt_train.make_train_step

    def make(loss_fn, optimizer, mesh=None, **kw):
        step = real(loss_fn, optimizer, mesh, donate=False)
        return lambda state, *batch: (state, step(state, *batch)[1])

    monkeypatch.setattr(pt_train, "make_train_step", make)
    code, res = run_cell("tiny.train", 13, 1.0, False, root=CELLS)
    assert code == 0 and res["correct"] is False


def test_the_fp8_control_of_training_reads_far_above_a_sound_run(capsys):
    """The training control at toy size: the reference in fp8 in the
    program's place, against the sound program's own readings."""
    lines = calibrate(capsys, "tiny.train", "0.5", "--control",
                      "reference=float8_e4m3fn", "17")
    control = next(l for l in lines if l.get("reading") == "control")
    sound = next(l for l in lines if l.get("reading") == "sound")
    for k in ("loss_gap", "grad_norm_gap", "delta_norm_gap"):
        assert control["numbers"][k] > 3 * sound["numbers"][k], (k, control, sound)
