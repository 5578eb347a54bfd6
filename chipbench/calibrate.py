"""Readings for a cell's limits, taken on the chip in one process:

    python chipbench/calibrate.py <cell> <seconds> <seed> ...
        [--control <what>[,<what>] <seed> ...] ...

First a sound run of the cell for every seed before ``--control``, then,
for each ``--control``, a control run for every seed after it
(``chipbench/control.py``). ``<what>``:

    kv_dtype=int8               a served cell: any engine option, here the
    weights=weight_only_int8    program's own int8 K/V cache, and the
                                program's own weight-only quantisation of
                                the model it serves
    reference=float8_e4m3fn+act a trained cell: the plain reference in that
                                type (``+act``: matmul inputs too), compared
                                as the program with the float32 reference

Each run prints ``{"reading": "sound" | "control", "seed", "numbers", ...}``:
the numbers ``correct`` compares, beside the cell's limits. A limit goes
above the sound runs' largest reading and below the control's smallest;
PERF.md has the readings and the commands that gave them.
"""
import importlib
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))


def main(argv):
    from chipbench import control, correct, correct_train, reference, run
    cell, seconds, rest = argv[0], float(argv[1]), argv[2:]
    groups = [[]]
    for word in rest:
        if word == "--control":
            groups.append([])
        else:
            groups[-1].append(word)
    runs = [("sound", {}, [int(x) for x in groups[0]])] + [
        ("control", dict(kv.split("=") for kv in g[0].split(",")),
         [int(x) for x in g[1:]]) for g in groups[1:]]
    lower = next((w["reference"] for _, w, _ in runs if "reference" in w), None)
    last = {}

    served, trained, train = (correct.served, correct_train.trained,
                              reference.train)

    def noted(fn):
        def call(*a, **kw):
            last["verdict"] = fn(*a, **kw)
            return last["verdict"]
        return call

    def train_both(cfg, opt, batches, top, layer_weights, devices):
        ref = train(cfg, opt, batches, top, layer_weights, devices)
        if last.get("lowered"):
            from chipbench.builders import llama
            with control.lowered(lower):
                low = train(cfg, opt, batches, top, layer_weights, devices)
            fold = lambda d: correct_train.group_norms(d, llama.GROUPS)
            as_program = {"loss": low["loss"], "not_finite": 0,
                          "grad_norm": fold(low["grad_norm"]),
                          "delta_norm": fold(low["delta_norm"])}
            run.note(reading="control", seed=last["seed"], reference_in=lower,
                     numbers=trained(as_program, ref, llama.GROUPS,
                                     {})["numbers"])
        return ref

    correct.served, correct_train.trained = noted(served), noted(trained)
    reference.train = train_both
    root = Path(tempfile.mkdtemp(prefix="chipbench_calibrate_"))
    builder = importlib.import_module(run.load(
        "configs", run.load("workloads", cell, HERE)["config"], HERE)["builder"])
    build = builder.build
    try:
        for kind in ("configs", "workloads", "traffic"):
            shutil.copytree(HERE / kind, root / kind)
        path = root / "workloads" / f"{cell}.json"
        plain = json.loads(path.read_text())
        for label, what, which in runs:
            what = dict(what)
            weights = what.pop("weights", None)
            what.pop("reference", None)
            path.write_text(json.dumps(dict(
                plain, engine=dict(plain.get("engine", {}), **what))
                if what else plain))
            builder.build = (control.quantized_serving(build, weights)
                             if weights else build)
            for seed in which:
                last.update(seed=seed, verdict={},
                            lowered=label == "control" and lower is not None)
                try:
                    code, res = run.run_cell(cell, seed, seconds, False,
                                             root=root,
                                             t_start=time.perf_counter())
                except Exception as e:     # a control that crashes has failed
                    if label != "control":
                        raise
                    code, res = f"{type(e).__name__}: {e}"[:300], None
                # a trained cell's control run is a sound run of the program
                # beside which the lowered reference was read (above)
                run.note(reading="sound" if last["lowered"] else label,
                         seed=seed, code=code, **what,
                         **({"weights": weights} if weights else {}),
                         numbers=last["verdict"].get("numbers"),
                         tokens_compared=last["verdict"].get("tokens_compared"),
                         run_correct=res and res["correct"],
                         metrics=res and res["metrics"])
    finally:
        builder.build = build
        correct.served, correct_train.trained = served, trained
        reference.train = train
        shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1:])
